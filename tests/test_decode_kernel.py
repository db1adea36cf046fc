"""Flash-decode kernel parity suite: the Pallas decode kernel (interpret
mode) vs the mha_ref oracle over GQA ratios, window/softcap, mixed per-row
cache positions (incl. pos=0 and pos=max_len-1), the fused int8-KV path
(bit-exact vs dequant-then-dense), block-pruning accounting, the
api.ops.attention routing rules, and end-to-end serving byte-identity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs import get_smoke
from repro.kernels.flash_attention import (decode_block_visits,
                                           flash_decode_pallas,
                                           flash_decode_quant_pallas,
                                           mha_ref)
from repro.models import init_params
from repro.models.attention import _dq8, _q8
from repro.serving import Request, ServingEngine

RNG = np.random.RandomState(7)
MAX_LEN = 256


def randn(*shape, scale=1.0):
    return jnp.asarray(RNG.randn(*shape).astype(np.float32) * scale)


def qkv(b, hq, hkv, lq, lk, d):
    return (randn(b, hq, lq, d, scale=0.5), randn(b, hkv, lk, d, scale=0.5),
            randn(b, hkv, lk, d))


# mixed per-row positions: an empty cache row, short rows, a block-boundary
# row and the last valid slot of the cache
MIXED_POS = [0, 5, 128, MAX_LEN - 1]


def assert_close(got, ref):
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


# ============================================================ kernel parity
@pytest.mark.parametrize("group", [1, 2, 4])
def test_decode_gqa_vs_ref(group):
    hkv = 2
    q, k, v = qkv(4, hkv * group, hkv, 1, MAX_LEN, 64)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    ref = mha_ref(q, k, v, causal=True, offset=pos)
    got = flash_decode_pallas(q, k, v, pos=pos, interpret=True)
    assert_close(got, ref)


@pytest.mark.parametrize("window,softcap", [(None, None), (40, None),
                                            (None, 30.0), (40, 30.0)])
def test_decode_window_softcap_vs_ref(window, softcap):
    q, k, v = qkv(4, 8, 2, 1, MAX_LEN, 64)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    ref = mha_ref(q, k, v, causal=True, offset=pos, window=window,
                  softcap=softcap)
    got = flash_decode_pallas(q, k, v, pos=pos, interpret=True,
                              window=window, softcap=softcap)
    assert_close(got, ref)


@pytest.mark.parametrize("lq", [2, 3, 8])
def test_decode_short_query_packed_vs_ref(lq):
    """Short multi-token queries (the narrow prefill buckets) with the GQA
    group packed into the q tile — row b queries positions pos[b]+i."""
    q, k, v = qkv(3, 6, 3, lq, MAX_LEN, 64)
    pos = jnp.asarray([0, 77, MAX_LEN - lq], jnp.int32)
    ref = mha_ref(q, k, v, causal=True, offset=pos)
    got = flash_decode_pallas(q, k, v, pos=pos, interpret=True)
    assert_close(got, ref)


def test_decode_scalar_offset_broadcasts():
    q, k, v = qkv(2, 4, 2, 1, MAX_LEN, 64)
    ref = mha_ref(q, k, v, causal=True, offset=100)
    got = flash_decode_pallas(q, k, v, pos=100, interpret=True)
    assert_close(got, ref)


def test_decode_unaligned_cache_length():
    """Lk not a bkv multiple: the pad tail must stay invisible."""
    q, k, v = qkv(2, 4, 2, 1, 200, 64)
    pos = jnp.asarray([199, 64], jnp.int32)
    ref = mha_ref(q, k, v, causal=True, offset=pos)
    got = flash_decode_pallas(q, k, v, pos=pos, interpret=True, bkv=128)
    assert_close(got, ref)


# ============================================================== int8-KV path
def test_decode_int8_fused_bit_exact_vs_dequant():
    """The fused in-VMEM dequant must be BIT-IDENTICAL to materializing the
    dequantized cache and running the dense kernel (it rounds through the
    q dtype exactly like models.attention._dq8)."""
    q, k, v = qkv(4, 8, 2, 1, MAX_LEN, 64)
    kc, ks = _q8(k)
    vc, vs = _q8(v)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    for kw in (dict(), dict(window=40, softcap=30.0)):
        fused = flash_decode_quant_pallas(q, kc, ks, vc, vs, pos=pos,
                                          interpret=True, **kw)
        dense = flash_decode_pallas(q, _dq8(kc, ks, q.dtype),
                                    _dq8(vc, vs, q.dtype), pos=pos,
                                    interpret=True, **kw)
        assert jnp.array_equal(fused, dense), kw
        assert_close(fused, mha_ref(q, _dq8(kc, ks, q.dtype),
                                    _dq8(vc, vs, q.dtype), causal=True,
                                    offset=pos, **kw))


# ====================================================== in-place cache update
def written(cache, new, layer, pos, lens):
    """The stacked cache with row b's first lens[b] new rows, rounded to the
    cache dtype, at pos[b].. of `layer` — what an in-place launch returns."""
    out = np.asarray(cache).copy()
    new = np.asarray(jnp.asarray(new).astype(cache.dtype))
    for b, (p, n) in enumerate(zip(np.asarray(pos), np.asarray(lens))):
        out[layer, b, :, p:p + n] = new[b, :, :n]
    return out


@pytest.mark.parametrize("lk,pos,lens,window,softcap", [
    # an empty row, a row sitting the step out, a block's first slot, the
    # cache's last slot
    (MAX_LEN, [0, 5, 128, MAX_LEN - 1], [1, 0, 1, 1], None, None),
    (MAX_LEN, [0, 5, 128, MAX_LEN - 1], [1, 1, 0, 1], 40, 30.0),
    # an unaligned cache: the last block is partial
    (200, [199, 64, 127, 0], [1, 1, 1, 0], None, None),
])
def test_decode_in_place_update(lk, pos, lens, window, softcap):
    """The in-place launch returns the stacked cache with each row's new
    token at pos[b] of its layer — other layers, rows sitting the step out
    and every other position untouched — and attention outputs bit-equal
    to writing the cache first, casting it to f32 and running the kernel."""
    n, layer = 3, 1
    q, _, _ = qkv(4, 8, 2, 1, lk, 64)
    k = randn(n, 4, 2, lk, 64, scale=0.5).astype(jnp.bfloat16)
    v = randn(n, 4, 2, lk, 64).astype(jnp.bfloat16)
    k_new, v_new = randn(4, 2, 1, 64, scale=0.5), randn(4, 2, 1, 64)
    pos, lens = jnp.asarray(pos, jnp.int32), jnp.asarray(lens, jnp.int32)
    out, k2, v2 = flash_decode_pallas(
        q, k, v, pos=pos, lengths=lens, window=window, softcap=softcap,
        k_new=k_new, v_new=v_new, layer=layer, interpret=True)
    want_k = written(k, k_new, layer, pos, lens)
    want_v = written(v, v_new, layer, pos, lens)
    np.testing.assert_array_equal(np.asarray(k2), want_k)
    np.testing.assert_array_equal(np.asarray(v2), want_v)
    dense = flash_decode_pallas(
        q, jnp.asarray(want_k[layer]).astype(jnp.float32),
        jnp.asarray(want_v[layer]).astype(jnp.float32), pos=pos,
        window=window, softcap=softcap, interpret=True)
    assert jnp.array_equal(out, dense)


# ============================================================ block pruning
def test_decode_block_pruning_visits():
    """The kernel must VISIT only the KV blocks inside each row's causal
    frontier — work scales with resident context, not max_len."""
    b, hkv, bkv = 4, 2, 64
    q, k, v = qkv(b, 4, hkv, 1, MAX_LEN, 64)
    pos = jnp.asarray([0, 63, 64, MAX_LEN - 1], jnp.int32)
    out, vis = flash_decode_pallas(q, k, v, pos=pos, interpret=True, bkv=bkv,
                                   debug_visits=True)
    vis = np.asarray(vis).reshape(b, hkv, -1)
    nk = MAX_LEN // bkv
    # per-row expectation: blocks 0..pos//bkv inclusive, identical per kv-head
    expect_rows = (np.asarray(pos) // bkv) + 1
    for row in range(b):
        for h in range(hkv):
            got_blocks = int(vis[row, h].sum())
            assert got_blocks == int(expect_rows[row]), (row, h)
    visited, total = decode_block_visits(pos, 1, MAX_LEN, bkv)
    assert visited == int(vis.sum()) // hkv
    assert int(vis.sum()) < b * hkv * nk          # pruning actually happened
    # pruned output still exact
    assert_close(out, mha_ref(q, k, v, causal=True, offset=pos))


def test_decode_window_prunes_old_blocks():
    """Sliding window adds a LOWER bound: a full-residency row visits only
    the window's blocks, so local-layer decode work scales with the window,
    not with how long the row has been resident."""
    b, hkv, bkv, window = 3, 2, 64, 80
    q, k, v = qkv(b, 4, hkv, 1, MAX_LEN, 64)
    pos = jnp.asarray([0, 130, MAX_LEN - 1], jnp.int32)
    out, vis = flash_decode_pallas(q, k, v, pos=pos, interpret=True, bkv=bkv,
                                   window=window, debug_visits=True)
    vis = np.asarray(vis).reshape(b, hkv, -1)
    first = np.maximum(np.asarray(pos) - (window - 1), 0) // bkv
    last = np.asarray(pos) // bkv
    for row in range(b):
        got = np.nonzero(vis[row, 0])[0]
        np.testing.assert_array_equal(
            got, np.arange(first[row], last[row] + 1), f"row={row}")
    visited, total = decode_block_visits(pos, 1, MAX_LEN, bkv, window=window)
    assert visited == int(vis.sum()) // hkv < total
    # the pos=MAX_LEN-1 row visits only ceil-ish window/bkv blocks
    assert int(vis[2, 0].sum()) <= window // bkv + 1
    assert_close(out, mha_ref(q, k, v, causal=True, offset=pos,
                              window=window))


# ================================================================== routing
def test_attention_route_rules():
    pallas = api.ExecutionPolicy(backend="pallas")
    route = api.ops.attention_route
    # cache-shaped decode (short Lq, causal, cache longer than query or a
    # per-row offset vector) hits the decode kernel — dense or quantized
    for kw in (dict(offset_ndim=1), dict(lk=512, offset_ndim=0),
               dict(offset_ndim=1, quantized=True)):
        assert route(lq=1, policy=pallas, **kw) == "pallas-decode", kw
    # multi-token vector-offset chunks (the engine's chunked admission
    # prefill) go to the varlen prefill kernel — dense or quantized
    assert route(lq=8, lk=512, policy=pallas,
                 offset_ndim=1) == "pallas-prefill"
    assert route(lq=256, policy=pallas, offset_ndim=1) == "pallas-prefill"
    assert route(lq=32, policy=pallas, offset_ndim=1,
                 quantized=True) == "pallas-prefill"
    # legacy scalar-offset short queries over a longer cache keep the
    # decode kernel's packed-group route
    assert route(lq=8, lk=512, policy=pallas) == "pallas-decode"
    # plain short SELF-attention (lk == lq, scalar offset) stays on the
    # differentiable ref path — the decode kernel has no VJP
    assert route(lq=4, lk=4, policy=pallas) == "ref"
    # long aligned prefill keeps the prefill flash kernel
    assert route(lq=256, policy=pallas) == "pallas"
    # unaligned / quantized scalar-offset prefill falls back to ref
    assert route(lq=100, policy=pallas) == "ref"
    assert route(lq=256, policy=pallas, quantized=True) == "ref"
    # non-causal never hits the decode kernel
    assert route(lq=1, lk=512, policy=pallas, causal=False) == "ref"
    # ref / default backends never route to kernels
    assert route(lq=1, lk=512, backend="ref") == "ref"
    assert route(lq=1, lk=512) == "ref"


def test_short_self_attention_stays_differentiable_under_pallas():
    """Regression: a tiny training forward (lq == lk <= 8) under
    backend='pallas' must keep taking grads — it routes to ref, not to the
    VJP-less decode kernel."""
    q, k, v = qkv(1, 4, 2, 4, 4, 32)

    def loss(q):
        return api.ops.attention(q, k, v, causal=True, backend="pallas",
                                 interpret=True).sum()

    g = jax.grad(loss)(q)
    assert g.shape == q.shape and bool(jnp.isfinite(g).all())


def test_api_attention_decode_dispatch_matches_ref():
    """api.ops.attention under backend='pallas' must dispatch decode shapes
    to the kernel and agree with the ref backend — dense and int8-KV."""
    q, k, v = qkv(4, 8, 4, 1, MAX_LEN, 64)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    ref = api.ops.attention(q, k, v, offset=pos, backend="ref")
    got = api.ops.attention(q, k, v, offset=pos, backend="pallas",
                            interpret=True)
    assert_close(got, ref)

    kc, ks = _q8(k)
    vc, vs = _q8(v)
    refq = api.ops.attention(q, kc, vc, offset=pos, k_scale=ks, v_scale=vs,
                             backend="ref")
    gotq = api.ops.attention(q, kc, vc, offset=pos, k_scale=ks, v_scale=vs,
                             backend="pallas", interpret=True)
    assert_close(gotq, refq)
    # the ref impl's dequant matches the old materialize-then-attend exactly
    np.testing.assert_array_equal(
        np.asarray(refq),
        np.asarray(api.ops.attention(q, _dq8(kc, ks, q.dtype),
                                     _dq8(vc, vs, q.dtype), offset=pos,
                                     backend="ref")))


# ==================================================== serving byte-identity
DECODE_POLICY = api.ExecutionPolicy(backend="pallas", interpret=True)


def _serve(cfg, params, spec, policy, slots=2, max_len=64):
    eng = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                        policy=policy)
    for rid, (p, m) in enumerate(spec):
        eng.submit(Request(rid, p, max_new_tokens=m))
    done = {r.rid: r.out_tokens for r in eng.run_until_drained()}
    return [done[i] for i in range(len(spec))], eng


@pytest.mark.parametrize("arch", ["qwen2_1p5b", "gemma2_27b"])
def test_serving_decode_kernel_byte_identical(arch):
    """Greedy serving with the decode kernel enabled must emit byte-identical
    tokens to the ref engine. gemma2 exercises sliding window (its smoke
    window of 16 is crossed), softcap and sandwich norms."""
    cfg = get_smoke(arch)
    params = init_params(jax.random.key(11), cfg)
    rng = np.random.RandomState(11)
    spec = [(rng.randint(1, cfg.vocab, l).astype(np.int32), m)
            for l, m in zip([3, 20, 5, 18], [6, 4, 8, 5])]
    want, ref_eng = _serve(cfg, params, spec, None)
    got, pal_eng = _serve(cfg, params, spec, DECODE_POLICY)
    assert pal_eng.decode_route() == "pallas-decode"
    assert ref_eng.decode_route() == "ref"
    assert got == want


def test_serving_decode_kernel_int8_kv_byte_identical():
    """The fused int8-KV decode path end to end: QuantKVCache codes+scales
    reach the kernel unmaterialized, outputs byte-identical to ref."""
    cfg = dataclasses.replace(get_smoke("qwen2_1p5b"), kv_quant=True)
    params = init_params(jax.random.key(12), cfg)
    rng = np.random.RandomState(12)
    spec = [(rng.randint(1, cfg.vocab, l).astype(np.int32), m)
            for l, m in zip([4, 13, 7], [5, 3, 6])]
    want, _ = _serve(cfg, params, spec, None)
    got, eng = _serve(cfg, params, spec, DECODE_POLICY)
    assert eng.decode_route() == "pallas-decode"
    assert got == want
