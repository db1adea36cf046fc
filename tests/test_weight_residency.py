"""Quantized-resident weights: QuantWeight round trips, the matmul_codes
dispatch path, the quantize_params pass, and greedy-serving byte-identity
against the fake-quant reference path (the PR-4 acceptance gate)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs import get_smoke
from repro.core import formats as F
from repro.kernels.aio_matmul import aio_matmul_ref
from repro.kernels.aio_matmul.ops import aio_matmul_resident
from repro.models import (QuantPolicy, init_params, quantize_params,
                          resident_format)
from repro.models import transformer as T
from repro.models.layers import _maybe_quant_weight
from repro.serving import Request, ServingEngine

FORMATS = ("int4", "int8", "fp8a", "fp8b")


# =============================================================================
# QuantWeight: codes + scales round trips
# =============================================================================

@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("k", [64, 97])
def test_dequantize_matches_per_channel_fake_quant_bitwise(fmt, k):
    """dequantize_weight(quantize_weight(w)) must equal the per-output-
    channel fake-quant of w BITWISE — this is what makes resident and
    fake-quant serving byte-identical."""
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(k, 48).astype(np.float32) * 3.0)
    qw = F.quantize_weight(w, fmt)
    assert qw.fmt == fmt and qw.k == k
    np.testing.assert_array_equal(np.asarray(F.dequantize_weight(qw)),
                                  np.asarray(_maybe_quant_weight(w, fmt)))


def test_int4_residency_packs_two_per_byte_and_roundtrips_bit_exact():
    rng = np.random.RandomState(1)
    for k in (32, 33):
        w = jnp.asarray(rng.randn(k, 16).astype(np.float32))
        qw = F.quantize_weight(w, "int4")
        assert qw.codes.shape == ((k + 1) // 2, 16)
        assert qw.codes.dtype == jnp.int8
        assert qw.bytes_per_param == 0.5
        # unpack -> repack is the identity on the stored bytes
        unpacked = F.unpack_int4(jnp.swapaxes(qw.codes, -1, -2), k=k)
        repacked = jnp.swapaxes(F.pack_int4(unpacked & 0xF), -1, -2)
        np.testing.assert_array_equal(np.asarray(repacked),
                                      np.asarray(qw.codes))


def test_quant_weight_is_a_pytree_with_static_aux():
    w = jnp.ones((8, 4), jnp.float32)
    qw = F.quantize_weight(w, "int8")
    leaves, treedef = jax.tree.flatten(qw)
    assert len(leaves) == 2                       # codes + scale only
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert rebuilt.fmt == "int8" and rebuilt.k == 8
    # leading-axis slicing (what lax.scan does to stacked layer params)
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), qw)
    sliced = jax.tree.map(lambda a: a[0], stacked)
    assert isinstance(sliced, F.QuantWeight) and sliced.k == 8


def test_rejects_non_resident_formats():
    w = jnp.ones((8, 4), jnp.float32)
    with pytest.raises(ValueError):
        F.quantize_weight(w, "bf16")
    with pytest.raises(ValueError):
        T.quantize_params({"w": w}, "fp16")


# =============================================================================
# api.ops.matmul_codes dispatch
# =============================================================================

@pytest.mark.parametrize("fmt", FORMATS)
def test_matmul_codes_ref_byte_identical_to_fake_quant_dense(fmt):
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(3, 5, 96).astype(np.float32))
    w = jnp.asarray(rng.randn(96, 64).astype(np.float32))
    qw = F.quantize_weight(w, fmt)
    got = api.ops.matmul_codes(x, qw, backend="ref")
    want = jnp.einsum("...d,df->...f", x, _maybe_quant_weight(w, fmt),
                      preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("k", [256, 131])
def test_matmul_codes_pallas_bit_identical_to_on_the_fly_kernel(fmt, k):
    """Skipping the weight half of the quantize-operands stage is purely a
    residency optimization: the Pallas kernel result on stored codes must be
    bit-identical to quantizing the dense weight on the fly (incl. odd K,
    where the int4 phantom nibble meets the zero-padded activations)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(16, k).astype(np.float32))
    w = jnp.asarray(rng.randn(k, 40).astype(np.float32))
    qw = F.quantize_weight(w, fmt)
    got = api.ops.matmul_codes(x, qw, backend="pallas", interpret=True)
    want = api.ops.matmul(x, w, format=fmt, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fmt", ["int8", "int4"])
@pytest.mark.parametrize("k", [256, 201])
def test_matmul_codes_pallas_resident_matches_ref_oracle(fmt, k):
    """The integer kernel path on STORED codes (int8, and int4 unpacked from
    its two-per-byte layout in VMEM) is bit-identical to the decode-then-f32
    oracle on the same codes — an oracle that shares no unpack or dot code
    with the kernel."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(24, k).astype(np.float32))
    w = jnp.asarray(rng.randn(k, 136).astype(np.float32))
    qw = F.quantize_weight(w, fmt)
    xq, xs = F.quantize_scaled(x, F.REGISTRY[fmt], axis=1, pow2=True)
    wcodes = (F.unpack_int4(qw.codes.T, k=k).T if fmt == "int4"
              else qw.codes)
    want = aio_matmul_ref(xq, wcodes, xs, qw.scale.reshape(1, -1), mode=fmt)
    got = aio_matmul_resident(xq, qw, xs, bm=128, bn=128, bk=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matmul_codes_rejects_mismatched_k():
    qw = F.quantize_weight(jnp.ones((8, 4), jnp.float32), "int8")
    with pytest.raises(ValueError, match="resident weight K"):
        api.ops.matmul_codes(jnp.ones((2, 7), jnp.float32), qw)


# =============================================================================
# quantize_params pass + model forward/decode
# =============================================================================

def test_quantize_params_coverage_and_accounting():
    cfg = get_smoke("llama2_7b")
    params = init_params(jax.random.key(0), cfg)
    qparams = quantize_params(params, "int4")
    assert resident_format(params) is None
    assert resident_format(qparams) == "int4"
    seg = qparams["segments"][0]
    blk = seg[next(iter(seg))]
    assert isinstance(blk["attn"]["q"]["w"], F.QuantWeight)   # stacked codes
    assert blk["attn"]["q"]["w"].codes.ndim == 3
    assert isinstance(blk["mlp"]["down"]["w"], F.QuantWeight)
    # outside fake-quant coverage -> stays dense (byte-identity requires it)
    assert not isinstance(qparams["lm_head"]["w"], F.QuantWeight)
    assert "table" in qparams["embed"]            # embeddings untouched
    # qkv biases survive conversion next to the codes (qwen2 smoke has them)
    cfg_b = get_smoke("qwen2_1p5b")
    qp_b = quantize_params(init_params(jax.random.key(0), cfg_b), "int8")
    seg_b = qp_b["segments"][0]
    blk_b = seg_b[next(iter(seg_b))]
    assert isinstance(blk_b["attn"]["q"]["w"], F.QuantWeight)
    assert "b" in blk_b["attn"]["q"]


@pytest.mark.parametrize("arch", ["llama2_7b", "qwen2_1p5b"])
@pytest.mark.parametrize("fmt", ["int8", "fp8a"])
def test_forward_byte_identical_resident_vs_fake_quant(arch, fmt):
    cfg = get_smoke(arch)
    cfg_fq = dataclasses.replace(cfg, quant=QuantPolicy(weights=fmt))
    cfg_res = dataclasses.replace(cfg, quant=QuantPolicy(weights=fmt,
                                                         resident=True))
    params = init_params(jax.random.key(0), cfg)
    qparams = quantize_params(params, fmt)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        1, cfg.vocab, (2, 9)), jnp.int32)
    lf, _ = jax.jit(lambda p, t: T.forward(p, t, cfg_fq))(params, toks)
    lr, _ = jax.jit(lambda p, t: T.forward(p, t, cfg_res))(qparams, toks)
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lr))


# =============================================================================
# Serving byte-identity: resident codes vs fake-quant reference engine
# =============================================================================

def _serve(cfg, params, reqs, **kw):
    eng = ServingEngine(cfg, params, slots=2, max_len=64, **kw)
    for rid, (p, m) in enumerate(reqs):
        eng.submit(Request(rid, p, max_new_tokens=m))
    done = eng.run_until_drained()
    return eng, {r.rid: r.out_tokens for r in done}


# llama2 smoke is dense MHA (n_kv == n_heads); qwen2 smoke is GQA (4q/2kv)
@pytest.mark.parametrize("arch", ["llama2_7b", "qwen2_1p5b"])
@pytest.mark.parametrize("fmt", ["int8", "fp8a"])
def test_greedy_serving_byte_identical(arch, fmt):
    cfg = get_smoke(arch)
    cfg = dataclasses.replace(cfg, quant=QuantPolicy(weights=fmt))
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(1, cfg.vocab, rng.randint(3, 10)).astype(np.int32),
             int(rng.randint(2, 7))) for _ in range(5)]
    ref_eng, ref_out = _serve(cfg, params, reqs)
    res_eng, res_out = _serve(cfg, params, reqs, weight_format=fmt)
    assert ref_eng.weight_route() == f"fake-quant-{fmt}"
    assert res_eng.weight_route() == f"resident-{fmt}"
    assert res_out == ref_out


def test_engine_rejects_non_resident_weight_format():
    cfg = get_smoke("llama2_7b")
    params = init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="bf16"):
        ServingEngine(cfg, params, slots=2, max_len=64, weight_format="bf16")


def test_engine_pins_residency_policy_onto_cfg():
    """Handing the engine a pre-quantized pytree (the serve launcher's
    donated load path) must pin cfg.quant to the matching resident policy so
    uncovered linears fall back to the SAME fake-quant plane."""
    cfg = get_smoke("llama2_7b")
    params = quantize_params(init_params(jax.random.key(0), cfg), "int8")
    eng = ServingEngine(cfg, params, slots=2, max_len=64)
    assert eng.cfg.quant.resident and eng.cfg.quant.weights == "int8"
    assert eng.weight_route() == "resident-int8"
