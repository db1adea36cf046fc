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
import dataclasses
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.configs import get_config
from repro.core import formats as F
from repro.kernels.aio_matmul.ops import aio_matmul_resident
from repro.kernels.common import interpret_override
from repro.kernels.flash_attention.decode import (
    flash_decode_paged_pallas, flash_decode_pallas, flash_decode_quant_pallas)
from repro.kernels.flash_attention.prefill import (
    flash_prefill_paged_pallas, flash_prefill_pallas,
    flash_prefill_quant_pallas)
from repro.models import transformer as T
from repro.serving.engine import ServingEngine

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


# ------------------------------------------- the engine's step program
_SLOTS, _CACHE, _LAYERS = 32, 3072, 2         # the chat cell's cache, cut
_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")
_SKIP = ("parameter", "get-tuple-element", "tuple", "bitcast", "constant",
         "while", "custom-call")


def _computations(hlo: str) -> dict:
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _ops(hlo: str):
    """(computation, name, opcode, dtype, element count) of every
    instruction XLA schedules — entry, loop bodies and conditions, not the
    insides of fusions or of the Pallas custom call."""
    comps = _computations(hlo)
    inner = {m.group(1) for lines in comps.values() for line in lines
             if " fusion(" in line or "custom-call(" in line
             for m in re.finditer(r"calls=%?([\w.\-]+)", line)}
    for comp, lines in comps.items():
        if comp in inner:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if m:
                name, dtype, shape, op = m.groups()
                n = 1
                for dim in filter(None, shape.split(",")):
                    n *= int(dim)
                yield comp, name, op, dtype, n


@pytest.mark.parametrize("width", [1, 32])
def test_engine_step_updates_kv_cache_in_place(one_chip, no_persistent_cache,
                                               width):
    """qwen2-1.5b's engine step program (2 layers, 32 slots, a 3072-token
    cache) makes no pass over the KV cache outside the attention kernel:
    the kernel writes each step's rows into the stacked cache in place.

    Cache-sized means an element count that is a whole multiple of one
    layer's K (or V) cache: a layer's slice, the stacked cache, or an f32
    copy of either. The f32 weights' bf16 copies XLA makes before the layer
    loop, and the logits, are other sizes; the copies are the only other
    temporaries allowed beside one layer's cache."""
    cfg = dataclasses.replace(get_config("qwen2_1p5b"), n_layers=_LAYERS)
    layer = _SLOTS * cfg.n_kv_heads * _CACHE * cfg.hd      # elements

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = spec(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    caches = spec(jax.eval_shape(lambda: T.init_caches(cfg, _SLOTS, _CACHE)))
    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((_SLOTS, width), (_SLOTS,))]
    engine = types.SimpleNamespace(cfg=cfg)

    def step(p, c, tok, lens):
        return ServingEngine._step_program(engine, p, c, tok, lens, None)

    with api.policy(backend="pallas", interpret=False):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            params, caches, *ints).compile()
    hlo = compiled.as_text()

    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    ops = [o for o in _ops(hlo) if o[2] not in _SKIP]
    passes = [o[:4] for o in ops if o[4] >= layer and o[4] % layer == 0]
    assert not passes, f"cache-sized XLA ops: {passes}"
    weight_copies = sum(2 * o[4] for o in ops
                        if o[2] == "convert" and o[3] == "bf16")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * layer + weight_copies, (temp, weight_copies)  # bf16
