"""Per-kernel allclose tests vs pure-jnp oracles: shape/dtype/mode sweeps
(interpret=True executes the kernel bodies on CPU)."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro import api
from repro.kernels.aio_matmul import (aio_matmul, aio_matmul_codes,
                                      aio_matmul_ref, quantize_operands_ref)
from repro.kernels.aio_quant import aio_quant_ref, aio_quantize
from repro.kernels.depthwise import depthwise_conv, depthwise_ref
from repro.kernels.flash_attention import (chunked_attention,
                                           flash_attention_pallas, mha_ref)
from repro.kernels.grouped_matmul import (grouped_matmul, make_group_ids,
                                          morphable_multi_gemm)

RNG = np.random.RandomState(42)


def randn(*shape, scale=1.0):
    return jnp.asarray(RNG.randn(*shape).astype(np.float32) * scale)


# ======================================================================
# aio_matmul
# ======================================================================
@pytest.mark.parametrize("mode", ["bf16", "fp8a", "fp8b", "int8", "int4"])
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (160, 200, 130), (64, 512, 96),
                                   (96, 201, 136)])
def test_aio_matmul_modes_shapes(mode, shape):
    m, k, n = shape
    x, w = randn(m, k), randn(k, n)
    xq, wq, xs, ws = quantize_operands_ref(x, w, mode)
    ref = aio_matmul_ref(xq, wq, xs, ws, mode=mode)
    got = aio_matmul_codes(xq, wq, xs, ws, mode=mode)
    if mode in ("int8", "int4"):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("mode", ["int8", "fp8a"])
def test_aio_matmul_dispatch_paths_agree(mode):
    x, w = randn(130, 140), randn(140, 150)
    plain = aio_matmul(x, w, mode=mode, prefer_pallas=False)
    with api.policy(backend="pallas"):
        pall = aio_matmul(x, w, mode=mode)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(pall),
                               rtol=2e-5, atol=1e-4)


def test_aio_matmul_quant_error_bounded():
    """Quantized matmul must track the f32 result within format error."""
    x, w = randn(128, 256, scale=0.5), randn(256, 128, scale=0.5)
    exact = np.asarray(x) @ np.asarray(w)
    out8 = np.asarray(aio_matmul(x, w, mode="int8", prefer_pallas=False))
    rel = np.abs(out8 - exact).max() / np.abs(exact).max()
    assert rel < 0.05, rel
    out4 = np.asarray(aio_matmul(x, w, mode="int4", prefer_pallas=False))
    rel4 = np.abs(out4 - exact).max() / np.abs(exact).max()
    assert rel4 < 0.5, rel4
    assert rel < rel4   # more bits, less error


@settings(max_examples=10, deadline=None)
@given(st.sampled_from(["int8", "fp8a", "int4"]),
       st.integers(1, 3), st.integers(1, 4), st.integers(1, 3))
def test_property_aio_matmul_random_shapes(mode, mi, ki, ni):
    m, k, n = mi * 64 + 7, ki * 64, ni * 64 + 3
    x, w = randn(m, k), randn(k, n)
    xq, wq, xs, ws = quantize_operands_ref(x, w, mode)
    ref = aio_matmul_ref(xq, wq, xs, ws, mode=mode)
    got = aio_matmul_codes(xq, wq, xs, ws, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5 * float(jnp.abs(ref).max() + 1))


# ======================================================================
# aio_quant
# ======================================================================
@pytest.mark.parametrize("fmt", ["fp8a", "fp8b", "int8", "int4"])
@pytest.mark.parametrize("shape", [(128, 128), (200, 300), (64, 500)])
def test_aio_quant_bit_exact(fmt, shape):
    x = randn(*shape, scale=13.0)
    rc, rs = aio_quant_ref(x, fmt_name=fmt)
    with api.policy(backend="pallas"):
        pc, ps = aio_quantize(x, fmt_name=fmt)
    np.testing.assert_array_equal(np.asarray(rc).astype(np.uint8),
                                  np.asarray(pc).astype(np.uint8))
    np.testing.assert_array_equal(np.asarray(rs), np.asarray(ps))


def test_aio_quant_scale_is_pow2():
    x = randn(128, 128, scale=100.0)
    _, s = aio_quantize(x, fmt_name="fp8a", prefer_pallas=True)
    l2 = np.log2(np.asarray(s))
    np.testing.assert_array_equal(l2, np.round(l2))


# ======================================================================
# grouped_matmul
# ======================================================================
def test_grouped_matmul_vs_loop():
    x = randn(512, 200)
    w = randn(4, 200, 130)
    sizes = [128, 256, 0, 128]
    with api.policy(backend="pallas"):
        got = np.asarray(grouped_matmul(x, w, sizes))
    xs = np.asarray(x)
    ws = np.asarray(w)
    ref = np.concatenate([xs[:128] @ ws[0], xs[128:384] @ ws[1],
                          xs[384:] @ ws[3]])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)


def test_grouped_matmul_rejects_unaligned():
    with pytest.raises(ValueError):
        make_group_ids([100, 156], bm=128)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4), st.integers(1, 3), st.integers(1, 2))
def test_property_grouped_matmul(g, ki, ni):
    k, n = ki * 128, ni * 128
    sizes = [128 * RNG.randint(0, 3) for _ in range(g)]
    t = sum(sizes)
    if t == 0:
        sizes[0] = 128
        t = 128
    x = randn(t, k)
    w = randn(g, k, n)
    with api.policy(backend="pallas"):
        got = np.asarray(grouped_matmul(x, w, sizes))
    row = 0
    for gi, size in enumerate(sizes):
        if size == 0:
            continue
        ref = np.asarray(x)[row:row + size] @ np.asarray(w)[gi]
        np.testing.assert_allclose(got[row:row + size], ref, rtol=1e-5,
                                   atol=1e-4)
        row += size


def test_morphable_multi_gemm_tenants():
    """Fig 3 scenario: two NLP GEMMs share one launch; results exact,
    utilization reported."""
    tenants = [(randn(100, 64), randn(64, 96)),
               (randn(300, 120), randn(120, 50)),
               (randn(60, 256), randn(256, 256))]
    with api.policy(backend="pallas"):
        res, util = morphable_multi_gemm(tenants)
    for (x, w), r in zip(tenants, res):
        np.testing.assert_allclose(np.asarray(r),
                                   np.asarray(x) @ np.asarray(w),
                                   rtol=1e-5, atol=1e-4)
    assert 0 < util <= 1


# ======================================================================
# depthwise
# ======================================================================
@pytest.mark.parametrize("shape", [(2, 16, 20, 96, 3), (1, 8, 8, 130, 5),
                                   (2, 9, 7, 64, 3), (1, 14, 14, 256, 3)])
def test_depthwise_vs_lax(shape):
    n, h, w, c, kk = shape
    x = randn(n, h, w, c)
    f = randn(kk, kk, c)
    with api.policy(backend="pallas"):
        got = depthwise_conv(x, f)
    ref = depthwise_ref(x, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 2), st.integers(4, 12), st.integers(4, 12),
       st.sampled_from([32, 64, 130]), st.sampled_from([3, 5]))
def test_property_depthwise(n, h, w, c, kk):
    x = randn(n, h, w, c)
    f = randn(kk, kk, c)
    with api.policy(backend="pallas"):
        got = depthwise_conv(x, f)
    ref = depthwise_ref(x, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)


# ======================================================================
# flash attention
# ======================================================================
CASES = [
    dict(b=2, hq=4, hkv=2, lq=128, lk=128, d=64),
    dict(b=1, hq=8, hkv=2, lq=256, lk=300, d=64, causal=True),
    dict(b=1, hq=4, hkv=4, lq=128, lk=256, d=64, causal=True, window=100),
    dict(b=1, hq=4, hkv=2, lq=128, lk=256, d=64, causal=True, softcap=30.0),
    dict(b=1, hq=4, hkv=2, lq=128, lk=384, d=64, causal=True, offset=256),
    dict(b=1, hq=2, hkv=1, lq=128, lk=128, d=128, causal=True, window=64,
         softcap=50.0),
]


@pytest.mark.parametrize("case", CASES)
def test_flash_attention_vs_ref(case):
    case = dict(case)
    b, hq, hkv = case.pop("b"), case.pop("hq"), case.pop("hkv")
    lq, lk, d = case.pop("lq"), case.pop("lk"), case.pop("d")
    q = randn(b, hq, lq, d, scale=0.5)
    k = randn(b, hkv, lk, d, scale=0.5)
    v = randn(b, hkv, lk, d)
    ref = mha_ref(q, k, v, **case)
    got = flash_attention_pallas(q, k, v, interpret=True, **case)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    chk = chunked_attention(q, k, v, chunk=64, **case)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_attention_decode_single_token():
    """Decode: Lq=1 against a long cache — chunked path must agree."""
    q = randn(2, 8, 1, 64)
    k = randn(2, 4, 511, 64, scale=0.5)
    v = randn(2, 4, 511, 64)
    ref = mha_ref(q, k, v, causal=True, offset=510)
    chk = chunked_attention(q, k, v, causal=True, offset=510, chunk=128)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


@settings(max_examples=6, deadline=None)
@given(st.sampled_from([1, 2]), st.sampled_from([(4, 2), (8, 8), (6, 1)]),
       st.sampled_from([128, 256]), st.sampled_from([128, 200, 384]),
       st.booleans())
def test_property_flash_attention(b, heads, lq, lk, causal):
    hq, hkv = heads
    q = randn(b, hq, lq, 64, scale=0.5)
    k = randn(b, hkv, lk, 64, scale=0.5)
    v = randn(b, hkv, lk, 64)
    # causal with lq > lk would mask whole rows; keep lk >= lq then
    if causal and lk < lq:
        lk = lq
        k = randn(b, hkv, lk, 64, scale=0.5)
        v = randn(b, hkv, lk, 64)
    ref = mha_ref(q, k, v, causal=causal)
    got = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
