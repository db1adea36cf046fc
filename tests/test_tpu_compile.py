"""Real-width compiles of the served Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered at qwen2-1.5b's widths (12 query / 2 KV
heads of 128, d_model 1536, d_ff 8960) and compiled by the TPU compiler for a
v5e chip that is described, not attached. That catches what interpret mode
cannot: Mosaic refusing an operand type, a tile that is not aligned, a block
that overflows VMEM. Each test asserts the kernel survived as a
`tpu_custom_call` in the compiled module.

The topology is described inside a module fixture (only one process at a
time may hold the TPU compiler's library), and the persistent compilation
cache is off for these compiles: a cached entry for a described chip cannot
be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import formats as F
from repro.kernels.aio_matmul.ops import aio_matmul_resident
from repro.kernels.common import interpret_override
from repro.kernels.flash_attention.decode import (
    flash_decode_paged_pallas, flash_decode_pallas, flash_decode_quant_pallas)
from repro.kernels.flash_attention.prefill import (
    flash_prefill_paged_pallas, flash_prefill_pallas,
    flash_prefill_quant_pallas)

B, HQ, HKV, D = 8, 12, 2, 128         # qwen2-1.5b attention, 8 serving slots
L, BQ, BKV, BS = 4096, 32, 128, 16    # cache length, q/KV tiles, paged block
D_MODEL, D_FF = 1536, 8960


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache):
    """compile_tpu(fn, *(shape, dtype)) -> compiled HLO text of `fn` lowered
    for one described v5e chip (kernels pinned out of interpret mode)."""
    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        return jax.jit(fn).lower(*args).compile().as_text()
    return compile_


def _assert_kernel(hlo: str):
    assert "tpu_custom_call" in hlo, "the Pallas kernel did not survive"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_decode_compiles(compile_tpu, dtype):
    hlo = compile_tpu(
        lambda q, k, v, pos: flash_decode_pallas(q, k, v, pos=pos, bkv=BKV,
                                                 interpret=False),
        ((B, HQ, 1, D), dtype), ((B, HKV, L, D), dtype),
        ((B, HKV, L, D), dtype), ((B,), jnp.int32))
    _assert_kernel(hlo)


def test_flash_decode_int8_kv_compiles(compile_tpu):
    hlo = compile_tpu(
        lambda q, kc, ks, vc, vs, pos: flash_decode_quant_pallas(
            q, kc, ks, vc, vs, pos=pos, bkv=BKV, interpret=False),
        ((B, HQ, 1, D), jnp.float32), ((B, HKV, L, D), jnp.int8),
        ((B, HKV, L, 1), jnp.float32), ((B, HKV, L, D), jnp.int8),
        ((B, HKV, L, 1), jnp.float32), ((B,), jnp.int32))
    _assert_kernel(hlo)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_varlen_prefill_compiles(compile_tpu, dtype):
    hlo = compile_tpu(
        lambda q, k, v, pos, n: flash_prefill_pallas(
            q, k, v, pos=pos, lengths=n, bq=BQ, bkv=BKV, interpret=False),
        ((B, HQ, BQ, D), dtype), ((B, HKV, L, D), dtype),
        ((B, HKV, L, D), dtype), ((B,), jnp.int32), ((B,), jnp.int32))
    _assert_kernel(hlo)


def test_varlen_prefill_int8_kv_compiles(compile_tpu):
    hlo = compile_tpu(
        lambda q, kc, ks, vc, vs, pos, n: flash_prefill_quant_pallas(
            q, kc, ks, vc, vs, pos=pos, lengths=n, bq=BQ, bkv=BKV,
            interpret=False),
        ((B, HQ, BQ, D), jnp.float32), ((B, HKV, L, D), jnp.int8),
        ((B, HKV, L, 1), jnp.float32), ((B, HKV, L, D), jnp.int8),
        ((B, HKV, L, 1), jnp.float32), ((B,), jnp.int32), ((B,), jnp.int32))
    _assert_kernel(hlo)


def test_paged_decode_compiles(compile_tpu):
    nblk = L // BS
    hlo = compile_tpu(
        lambda q, k, v, tbl, pos: flash_decode_paged_pallas(
            q, k, v, table=tbl, pos=pos, interpret=False),
        ((B, HQ, 1, D), jnp.float32), ((B * nblk, HKV, BS, D), jnp.float32),
        ((B * nblk, HKV, BS, D), jnp.float32), ((B, nblk), jnp.int32),
        ((B,), jnp.int32))
    _assert_kernel(hlo)


def test_paged_prefill_compiles(compile_tpu):
    nblk = L // BS
    hlo = compile_tpu(
        lambda q, k, v, tbl, pos, n: flash_prefill_paged_pallas(
            q, k, v, table=tbl, pos=pos, lengths=n, bq=BQ, interpret=False),
        ((B, HQ, BQ, D), jnp.float32), ((B * nblk, HKV, BS, D), jnp.float32),
        ((B * nblk, HKV, BS, D), jnp.float32), ((B, nblk), jnp.int32),
        ((B,), jnp.int32), ((B,), jnp.int32))
    _assert_kernel(hlo)


@pytest.mark.parametrize("fmt", ["int8", "int4"])
def test_matmul_codes_compiles(compile_tpu, fmt):
    """The resident-weight GEMM at qwen2's d_model x d_ff: int8 x int8 ->
    int32 on the MXU (int4 codes unpacked to int8 in VMEM first)."""
    kpacked = D_MODEL // 2 if fmt == "int4" else D_MODEL

    def fn(xq, codes, xs, scale):
        qw = F.QuantWeight(codes=codes, scale=scale, fmt=fmt, k=D_MODEL)
        with interpret_override(False):
            return aio_matmul_resident(xq, qw, xs)

    hlo = compile_tpu(fn, ((B * BQ, D_MODEL), jnp.int32),
                      ((kpacked, D_FF), jnp.int8),
                      ((B * BQ, 1), jnp.float32), ((1, D_FF), jnp.float32))
    _assert_kernel(hlo)
