"""Varlen flash-prefill kernel parity suite: the Pallas prefill kernel
(interpret mode) vs the mha_ref oracle over GQA ratios, window/softcap,
mixed per-row (position, length) pairs incl. zero-length rows, the fused
int8-KV path (bit-exact vs dequant-then-dense), q-block/KV-block pruning
accounting, the pallas-prefill routing rules, and end-to-end CHUNKED
admission: chunked greedy serving byte-identical to one-shot admission
across dense/GQA/window/softcap/int8-KV engines, incl. admit-while-decoding
traffic, plus warmup() and the chunk-call stats accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs import get_smoke
from repro.kernels.flash_attention import (flash_prefill_pallas,
                                           flash_prefill_quant_pallas,
                                           mha_ref, prefill_block_visits)
from repro.kernels.flash_attention.prefill import (_write_blocks,
                                                   update_prefill_index_maps)
from repro.models import init_params
from repro.models.attention import _dq8, _q8
from repro.serving import Request, ServingEngine

RNG = np.random.RandomState(17)
MAX_LEN = 256
LQ = 20                                   # chunk width under test


def randn(*shape, scale=1.0):
    return jnp.asarray(RNG.randn(*shape).astype(np.float32) * scale)


def qkv(b, hq, hkv, lq, lk, d):
    return (randn(b, hq, lq, d, scale=0.5), randn(b, hkv, lk, d, scale=0.5),
            randn(b, hkv, lk, d))


# mixed per-row (cache position, valid chunk length): a fresh full chunk, a
# short tail chunk mid-cache, an idle row (lengths == 0), and a chunk ending
# exactly at the last cache slot
MIXED_POS = [0, 37, 128, MAX_LEN - LQ]
MIXED_LEN = [LQ, 5, 0, LQ]


def assert_valid_close(got, ref, lens):
    """Rows compare only over their valid chunk prefix; the pad tail of the
    kernel output must be exact zeros (deterministic, never consumed)."""
    got, ref = np.asarray(got), np.asarray(ref)
    for b, ln in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(got[b, :, :ln], ref[b, :, :ln],
                                   rtol=2e-5, atol=2e-5)
        assert not got[b, :, ln:].any(), f"row {b}: pad tail not zero"


# ============================================================ kernel parity
@pytest.mark.parametrize("group", [1, 2, 4])
def test_prefill_varlen_gqa_vs_ref(group):
    hkv = 2
    q, k, v = qkv(4, hkv * group, hkv, LQ, MAX_LEN, 64)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    lens = jnp.asarray(MIXED_LEN, jnp.int32)
    ref = mha_ref(q, k, v, causal=True, offset=pos)
    got = flash_prefill_pallas(q, k, v, pos=pos, lengths=lens, bq=8,
                               bkv=64, interpret=True)
    assert_valid_close(got, ref, lens)


@pytest.mark.parametrize("window,softcap", [(None, None), (40, None),
                                            (None, 30.0), (40, 30.0)])
def test_prefill_window_softcap_vs_ref(window, softcap):
    q, k, v = qkv(4, 8, 2, LQ, MAX_LEN, 64)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    lens = jnp.asarray(MIXED_LEN, jnp.int32)
    ref = mha_ref(q, k, v, causal=True, offset=pos, window=window,
                  softcap=softcap)
    got = flash_prefill_pallas(q, k, v, pos=pos, lengths=lens, bq=8, bkv=64,
                               interpret=True, window=window, softcap=softcap)
    assert_valid_close(got, ref, lens)


def test_prefill_default_lengths_fully_valid():
    """lengths=None means every chunk position is real — full parity, and a
    scalar pos broadcasts like the decode kernel's."""
    q, k, v = qkv(3, 6, 3, LQ, MAX_LEN, 64)
    ref = mha_ref(q, k, v, causal=True, offset=100)
    got = flash_prefill_pallas(q, k, v, pos=100, bq=8, bkv=64,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_prefill_unaligned_shapes():
    """Lk not a bkv multiple and Lq not a bq multiple: both pad tails must
    stay invisible."""
    q, k, v = qkv(2, 4, 2, 13, 200, 64)
    pos = jnp.asarray([187, 64], jnp.int32)
    lens = jnp.asarray([13, 7], jnp.int32)
    ref = mha_ref(q, k, v, causal=True, offset=pos)
    got = flash_prefill_pallas(q, k, v, pos=pos, lengths=lens, bq=8,
                               bkv=128, interpret=True)
    assert got.shape == q.shape
    assert_valid_close(got, ref, lens)


# ============================================================== int8-KV path
def test_prefill_int8_fused_bit_exact_vs_dequant():
    """The fused in-VMEM dequant must be BIT-IDENTICAL to materializing the
    dequantized cache and running the dense kernel (it rounds through the
    q dtype exactly like models.attention._dq8)."""
    q, k, v = qkv(4, 8, 2, LQ, MAX_LEN, 64)
    kc, ks = _q8(k)
    vc, vs = _q8(v)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    lens = jnp.asarray(MIXED_LEN, jnp.int32)
    for kw in (dict(), dict(window=40, softcap=30.0)):
        fused = flash_prefill_quant_pallas(q, kc, ks, vc, vs, pos=pos,
                                           lengths=lens, bq=8, bkv=64,
                                           interpret=True, **kw)
        dense = flash_prefill_pallas(q, _dq8(kc, ks, q.dtype),
                                     _dq8(vc, vs, q.dtype), pos=pos,
                                     lengths=lens, bq=8, bkv=64,
                                     interpret=True, **kw)
        assert jnp.array_equal(fused, dense), kw
        assert_valid_close(fused, mha_ref(q, _dq8(kc, ks, q.dtype),
                                          _dq8(vc, vs, q.dtype), causal=True,
                                          offset=pos, **kw), lens)


# ====================================================== in-place cache update
def written(cache, new, layer, pos, lens):
    """The stacked cache with row b's first lens[b] new rows, rounded to the
    cache dtype, at pos[b].. of `layer` — what an in-place launch returns."""
    out = np.asarray(cache).copy()
    new = np.asarray(jnp.asarray(new).astype(cache.dtype))
    for b, (p, n) in enumerate(zip(np.asarray(pos), np.asarray(lens))):
        out[layer, b, :, p:p + n] = new[b, :, :n]
    return out


@pytest.mark.parametrize("lk,lq,pos,lens,bq,bkv,window,softcap", [
    # a full chunk crossing a block boundary, a short chunk (pad rows), a
    # row sitting the step out, a chunk filling the cache's last block
    (MAX_LEN, 32, [0, 120, 60, MAX_LEN - 32], [32, 9, 0, 32], 32, 128, None,
     None),
    # several q-blocks per chunk; window and softcap
    (MAX_LEN, 32, [100, 0, 200, 126], [32, 32, 3, 0], 8, 128, 40, 30.0),
    # an unaligned cache: final partial chunks whose pad rows run past its
    # end, one crossing into the partial last block
    (300, LQ, [290, 250, 0, 120], [10, LQ, 0, 17], 32, 128, None, None),
    # a chunk longer than a KV block: up to five blocks written, one of
    # them the unaligned cache's partial last block
    (300, 64, [5, 240, 100, 17], [64, 60, 0, 33], 16, 16, 40, None),
])
def test_prefill_in_place_update(lk, lq, pos, lens, bq, bkv, window,
                                 softcap):
    """The in-place launch returns the stacked cache with each row's valid
    chunk rows at pos[b].. of its layer — other layers, pad rows, rows
    sitting the step out and everything past the cache's end untouched —
    and attention outputs bit-equal to writing the cache first, casting it
    to f32 and running the kernel."""
    n, layer = 2, 1
    q, _, _ = qkv(4, 8, 2, lq, lk, 64)
    k = randn(n, 4, 2, lk, 64, scale=0.5).astype(jnp.bfloat16)
    v = randn(n, 4, 2, lk, 64).astype(jnp.bfloat16)
    k_new, v_new = randn(4, 2, lq, 64, scale=0.5), randn(4, 2, lq, 64)
    pos, lens = jnp.asarray(pos, jnp.int32), jnp.asarray(lens, jnp.int32)
    out, k2, v2 = flash_prefill_pallas(
        q, k, v, pos=pos, lengths=lens, window=window, softcap=softcap,
        bq=bq, bkv=bkv, k_new=k_new, v_new=v_new, layer=layer,
        interpret=True)
    want_k = written(k, k_new, layer, pos, lens)
    want_v = written(v, v_new, layer, pos, lens)
    np.testing.assert_array_equal(np.asarray(k2), want_k)
    np.testing.assert_array_equal(np.asarray(v2), want_v)
    dense = flash_prefill_pallas(
        q, jnp.asarray(want_k[layer]).astype(jnp.float32),
        jnp.asarray(want_v[layer]).astype(jnp.float32), pos=pos,
        lengths=lens, window=window, softcap=softcap, bq=bq, bkv=bkv,
        interpret=True)
    assert jnp.array_equal(out, dense)


@pytest.mark.parametrize("lk,bq,bkv,window", [
    (384, 32, 128, None), (300, 8, 16, 40), (256, 32, 16, None)])
def test_prefill_in_place_write_schedule(lk, bq, bkv, window):
    """On the chip an output block starts unread in VMEM and is written
    back when the output index moves on; interpret mode reloads it every
    step and cannot see a fault there. So replay the in-place launch's
    output index over the grid: each block the chunk's rows fall in is
    reached in one run of steps, in ascending order, and merged exactly
    once inside its run, at a step whose K/V read is that very block."""
    nk = -(-lk // bkv)
    *_, kv_index, out_index = update_prefill_index_maps(
        bq=bq, bkv=bkv, nk=nk, hkv=1, rows=1, window=window)
    layer = np.zeros(1, np.int32)
    for lq in (bq, 4 * bq):
        for start in (0, bkv - 3, lk - lq, lk - 1):
            for ln in (0, 1, lq // 2 + 1, lq):
                pos, lens = np.array([start]), np.array([ln])
                seq, merged = [], []
                for iq in range(lq // bq):
                    for ik in range(nk):
                        blk = int(out_index(0, iq, ik, pos, lens, layer)[1])
                        seq.append(blk)
                        _, merge = _write_blocks(start, ln, iq, ik, bq=bq,
                                                 bkv=bkv, nk=nk)
                        if merge:
                            merged.append(blk)
                            assert blk == ik
                            assert int(kv_index(0, iq, ik, pos, lens,
                                                layer)[1]) == ik
                runs = [b for i, b in enumerate(seq)
                        if not i or b != seq[i - 1]]
                last = min((start + max(ln, 1) - 1) // bkv, nk - 1)
                want = list(range(min(start // bkv, nk - 1), last + 1))
                assert runs == want and merged == want, (lq, start, ln)


# ============================================================ block pruning
def test_prefill_block_pruning_visits():
    """The kernel must VISIT only each row's frontier blocks: q-blocks past
    the row's valid length are skipped outright, and each surviving q-block
    scans KV only up to its own causal frontier — work scales with REAL
    prompt tokens, not the chunk width x max_len."""
    b, hkv, bq, bkv = 4, 2, 8, 64
    q, k, v = qkv(b, 4, hkv, LQ, MAX_LEN, 64)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    lens = jnp.asarray(MIXED_LEN, jnp.int32)
    out, vis = flash_prefill_pallas(q, k, v, pos=pos, lengths=lens, bq=bq,
                                    bkv=bkv, interpret=True,
                                    debug_visits=True)
    vis = np.asarray(vis).reshape(b, hkv, -1)           # (B, Hkv, nq*nk)
    # per-row expectation straight from the frontier arithmetic, identical
    # across the row's kv-heads
    for row in range(b):
        exp_row, _ = prefill_block_visits(pos[row:row + 1],
                                          lens[row:row + 1], LQ, MAX_LEN,
                                          bq=bq, bkv=bkv)
        for h in range(hkv):
            assert int(vis[row, h].sum()) == exp_row, (row, h)
    visited, total = prefill_block_visits(pos, lens, LQ, MAX_LEN, bq=bq,
                                          bkv=bkv)
    assert visited == int(vis.sum()) // hkv
    assert int(vis.sum()) < total * hkv       # pruning actually happened
    # the idle row (lengths == 0) does zero block visits
    assert int(vis[2].sum()) == 0
    # pruned output still exact over the valid region
    assert_valid_close(out, mha_ref(q, k, v, causal=True, offset=pos), lens)


def test_prefill_window_prunes_old_blocks():
    """A sliding window adds a LOWER bound per q-block: a chunk landing deep
    in a long-resident row visits only the window's blocks."""
    b, hkv, bq, bkv, window = 2, 2, 8, 32, 40
    q, k, v = qkv(b, 4, hkv, LQ, MAX_LEN, 64)
    pos = jnp.asarray([MAX_LEN - LQ, 0], jnp.int32)
    lens = jnp.asarray([LQ, LQ], jnp.int32)
    out, vis = flash_prefill_pallas(q, k, v, pos=pos, lengths=lens, bq=bq,
                                    bkv=bkv, window=window, interpret=True,
                                    debug_visits=True)
    measured = int(np.asarray(vis).sum())
    visited, total = prefill_block_visits(pos, lens, LQ, MAX_LEN, bq=bq,
                                          bkv=bkv, window=window)
    no_win, _ = prefill_block_visits(pos, lens, LQ, MAX_LEN, bq=bq, bkv=bkv)
    assert measured == visited * hkv
    assert visited < no_win                   # the lower bound pruned blocks
    assert_valid_close(out, mha_ref(q, k, v, causal=True, offset=pos,
                                    window=window), lens)


# ================================================================== routing
def test_prefill_route_rules():
    pallas = api.ExecutionPolicy(backend="pallas")
    route = api.ops.attention_route
    # causal multi-token vector-offset chunks (what chunked admission
    # launches) hit the varlen prefill kernel — dense or quantized
    for lq in (2, 8, 32, 200):
        assert route(lq=lq, policy=pallas, offset_ndim=1) == "pallas-prefill"
    assert route(lq=32, policy=pallas, offset_ndim=1,
                 quantized=True) == "pallas-prefill"
    # single-token decode keeps the decode kernel
    assert route(lq=1, policy=pallas, offset_ndim=1) == "pallas-decode"
    # non-causal and ref/default backends never hit it
    assert route(lq=32, policy=pallas, offset_ndim=1, causal=False) == "ref"
    assert route(lq=32, offset_ndim=1, backend="ref") == "ref"
    assert route(lq=32, offset_ndim=1) == "ref"


def test_api_attention_prefill_dispatch_matches_ref():
    """api.ops.attention under backend='pallas' must dispatch varlen chunk
    shapes to the prefill kernel and agree with the ref backend on the valid
    region — dense and int8-KV."""
    q, k, v = qkv(4, 8, 4, LQ, MAX_LEN, 64)
    pos = jnp.asarray(MIXED_POS, jnp.int32)
    lens = jnp.asarray(MIXED_LEN, jnp.int32)
    ref = api.ops.attention(q, k, v, offset=pos, backend="ref")
    got = api.ops.attention(q, k, v, offset=pos, lengths=lens, bq=8,
                            backend="pallas", interpret=True)
    assert_valid_close(got, ref, lens)

    kc, ks = _q8(k)
    vc, vs = _q8(v)
    refq = api.ops.attention(q, kc, vc, offset=pos, k_scale=ks, v_scale=vs,
                             backend="ref")
    gotq = api.ops.attention(q, kc, vc, offset=pos, lengths=lens, bq=8,
                             k_scale=ks, v_scale=vs, backend="pallas",
                             interpret=True)
    assert_valid_close(gotq, refq, lens)


# ==================================================== chunked admission e2e
PALLAS_POLICY = api.ExecutionPolicy(backend="pallas", interpret=True)


def _serve(cfg, params, spec, policy, *, chunk, slots=2, max_len=64):
    eng = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                        policy=policy, prefill_chunk=chunk)
    for rid, (p, m) in enumerate(spec):
        eng.submit(Request(rid, p, max_new_tokens=m))
    done = {r.rid: r.out_tokens for r in eng.run_until_drained()}
    return [done[i] for i in range(len(spec))], eng


def _spec(cfg, lens, outs, seed):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, cfg.vocab, l).astype(np.int32), m)
            for l, m in zip(lens, outs)]


@pytest.mark.parametrize("arch,policy", [
    ("qwen2_1p5b", None),                    # dense GQA, ref path
    ("qwen2_1p5b", PALLAS_POLICY),           # dense GQA, varlen kernel
    ("gemma2_27b", PALLAS_POLICY),           # sliding window + softcap
])
def test_chunked_vs_oneshot_byte_identical(arch, policy):
    """Greedy outputs of chunked admission (chunk smaller than the prompts,
    not dividing them) must be byte-identical to one-shot admission (chunk
    covering every prompt in a single launch)."""
    cfg = get_smoke(arch)
    params = init_params(jax.random.key(21), cfg)
    spec = _spec(cfg, [3, 20, 5, 17], [6, 4, 8, 5], seed=21)
    want, one = _serve(cfg, params, spec, policy, chunk=32)
    got, chk = _serve(cfg, params, spec, policy, chunk=5)
    assert chk.stats.prefill_chunk_calls > one.stats.prefill_chunk_calls
    if policy is not None:
        assert chk.prefill_route() == "pallas-prefill"
        assert chk.decode_route() == "pallas-decode"
    assert got == want


def test_chunked_int8_kv_byte_identical():
    """The fused int8-KV prefill path end to end: QuantKVCache codes+scales
    reach the varlen kernel unmaterialized, chunked == one-shot == ref."""
    cfg = dataclasses.replace(get_smoke("qwen2_1p5b"), kv_quant=True)
    params = init_params(jax.random.key(22), cfg)
    spec = _spec(cfg, [4, 15, 7], [5, 3, 6], seed=22)
    want, _ = _serve(cfg, params, spec, None, chunk=32)
    got, eng = _serve(cfg, params, spec, PALLAS_POLICY, chunk=6)
    assert eng.prefill_route() == "pallas-prefill"
    assert got == want


@pytest.mark.parametrize("policy", [None, PALLAS_POLICY])
def test_admit_while_decoding_interleaved(policy):
    """A LONG prompt admitted while another slot is mid-generation: the
    resident slot must keep emitting DURING the admission (the head-of-line
    stall chunking removes), and both requests reproduce their solo
    outputs."""
    cfg = get_smoke("qwen2_1p5b")
    params = init_params(jax.random.key(23), cfg)
    rng = np.random.RandomState(23)
    short = rng.randint(1, cfg.vocab, 4).astype(np.int32)
    long_ = rng.randint(1, cfg.vocab, 40).astype(np.int32)

    def solo(p, m):
        out, _ = _serve(cfg, params, [(p, m)], policy, chunk=8, slots=1)
        return out[0]

    want_short, want_long = solo(short, 12), solo(long_, 4)

    eng = ServingEngine(cfg, params, slots=2, max_len=64, policy=policy,
                        prefill_chunk=8)
    eng.submit(Request(0, short, max_new_tokens=12))
    eng.step()                                # rid 0 admitted + first tokens
    generated_before = len(eng._slot_req[0].out_tokens)
    eng.submit(Request(1, long_, max_new_tokens=4))
    # the 40-token prompt needs 5 chunk launches; drive exactly that many
    # steps and watch rid 0 generate through every one of them
    for _ in range(5):
        eng.step()
    occ = eng.occupancy()
    assert occ[0] is not None and occ[1] is not None
    # rid 0 advanced one token per step DURING rid 1's admission
    assert occ[0]["generated"] == generated_before + 5
    # rid 1 finished admission on the last chunk launch (first token) and
    # joined the same step's decode launch (second token)
    assert occ[1]["generated"] == 2
    done = {r.rid: r.out_tokens for r in eng.run_until_drained()}
    assert done[0] == want_short and done[1] == want_long


def test_zamba2_merged_prefill_matches_solo():
    """Recurrent archs take the merged l=1 path: prefilling rows feed prompt
    tokens in the same launch decoding rows generate through — outputs stay
    byte-identical to solo serving."""
    cfg = get_smoke("zamba2_2p7b")
    params = init_params(jax.random.key(24), cfg)
    spec = _spec(cfg, [3, 12, 6], [4, 3, 5], seed=24)
    want = [_serve(cfg, params, [s], None, chunk=8, slots=1)[0][0]
            for s in spec]
    got, eng = _serve(cfg, params, spec, None, chunk=8)
    assert got == want
    # merged launches: no chunk-shaped calls, token steps counted instead
    assert eng.stats.prefill_chunk_calls == 0
    assert eng.stats.prefill_token_steps + eng.stats.decode_steps == \
        eng.stats.model_calls


# ============================================================ warmup + stats
def test_warmup_is_stateless_and_traces_once():
    """warmup() must leave every cache leaf bitwise intact, spend no stats,
    and pre-trace BOTH step shapes so serving adds no new compilations."""
    cfg = get_smoke("qwen2_1p5b")
    params = init_params(jax.random.key(25), cfg)
    eng = ServingEngine(cfg, params, slots=2, max_len=64, prefill_chunk=8)
    before = jax.tree.map(np.asarray, eng.caches)
    eng.warmup()
    after = jax.tree.map(np.asarray, eng.caches)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)
    assert eng.stats.model_calls == 0 and eng.stats.generated_tokens == 0
    n_traces = eng._step_fn._cache_size()
    assert n_traces == 2                      # chunk-shaped + decode-shaped
    # the fixed chunk shape means serving NEVER retraces: mixed prompt
    # lengths (the old pow2 ladder would have traced 3 widths here) reuse
    # the two warmed programs
    for rid, (p, m) in enumerate(_spec(cfg, [3, 9, 21], [3, 2, 2], seed=25)):
        eng.submit(Request(rid, p, max_new_tokens=m))
    eng.run_until_drained()
    assert eng._step_fn._cache_size() == n_traces
    # warmed engine output identical to an unwarmed twin
    twin = ServingEngine(cfg, params, slots=2, max_len=64, prefill_chunk=8)
    for rid, (p, m) in enumerate(_spec(cfg, [3, 9, 21], [3, 2, 2], seed=25)):
        twin.submit(Request(rid, p, max_new_tokens=m))
    twin.run_until_drained()
    assert {r.rid: r.out_tokens for r in eng.finished} == \
        {r.rid: r.out_tokens for r in twin.finished}


def test_prefill_chunk_calls_accounting():
    """EngineStats must count chunk launches distinctly: slots=1 serialises
    admissions, so the count is exactly sum(ceil(plen / chunk))."""
    cfg = get_smoke("qwen2_1p5b")
    params = init_params(jax.random.key(26), cfg)
    chunk = 4
    plens, outs = [5, 3, 9], [2, 1, 3]
    spec = _spec(cfg, plens, outs, seed=26)
    _, eng = _serve(cfg, params, spec, None, chunk=chunk, slots=1)
    expect = sum(-(-p // chunk) for p in plens)
    assert eng.stats.prefill_chunk_calls == expect
    assert eng.stats.prefill_tokens == sum(plens)
    assert eng.stats.model_calls == eng.stats.prefill_chunk_calls + \
        eng.stats.decode_steps
    assert eng.stats.generated_tokens == sum(outs)


def test_prefill_chunk_validation():
    cfg = get_smoke("qwen2_1p5b")
    params = init_params(jax.random.key(27), cfg)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(cfg, params, slots=1, max_len=32, prefill_chunk=0)
    # wider than the cache clamps (the default stays usable on small caches)
    eng = ServingEngine(cfg, params, slots=1, max_len=16, prefill_chunk=64)
    assert eng.prefill_chunk == 16
