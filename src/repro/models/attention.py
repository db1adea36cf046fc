"""GQA attention block with KV cache, covering the assigned archs' variants:
QKV bias (qwen2), logit softcap + sliding window + sandwich norms (gemma2),
cross attention (whisper decoder), and the AIO quantization policy."""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..api import ops as aio_ops
from ..core.formats import pow2_ceil
from .layers import QuantPolicy, linear, linear_init, rope

__all__ = ["KVCache", "PagedKVCache", "PagedQuantKVCache", "attn_init",
           "attn_apply", "cross_attn_apply", "init_kv_cache",
           "init_paged_kv_cache", "pool_block_values", "store_pool_blocks"]


class KVCache(NamedTuple):
    """Pre-allocated decode cache. k/v: (B, Hkv, L_max, D); pos: (B,) vector —
    every batch row ("slot" in the serving engine) sits at its own position,
    the substrate for continuous per-slot batching."""
    k: jax.Array
    v: jax.Array
    pos: jax.Array


class QuantKVCache(NamedTuple):
    """INT8 KV cache — the paper's format plane applied to cache residency.

    Codes are int8 with a per-(position, head) power-of-two scale (the
    bias-foldable kind): halves the decode memory term vs bf16. The
    dequantization happens at attention time (fused on real TPU).
    pos: (B,) per-row vector, like KVCache."""
    k_codes: jax.Array      # (B, Hkv, L, D) int8
    k_scale: jax.Array      # (B, Hkv, L, 1) f32, power-of-two
    v_codes: jax.Array
    v_scale: jax.Array
    pos: jax.Array


class PagedKVCache(NamedTuple):
    """Block-pool decode cache. Instead of a private (L_max, D) stripe per
    row, all rows share one pool of fixed-size KV blocks and each row maps
    logical block j -> physical block table[b, j]. Rows only pay for the
    context they actually hold, and identical prompt prefixes can alias the
    same physical blocks (copy-on-write sharing, managed host-side by the
    serving engine's allocator).

    k/v:   (P, Hkv, bs, D) pool — P physical blocks of bs positions
    table: (B, nblk) int32 — per-row logical->physical block map
    pos:   (B,) — per-row write frontier, same semantics as KVCache.pos
    """
    k: jax.Array
    v: jax.Array
    table: jax.Array
    pos: jax.Array


class PagedQuantKVCache(NamedTuple):
    """INT8 block-pool cache: PagedKVCache layout with QuantKVCache formats.
    codes (P, Hkv, bs, D) int8, scales (P, Hkv, bs, 1) f32 pow2."""
    k_codes: jax.Array
    k_scale: jax.Array
    v_codes: jax.Array
    v_scale: jax.Array
    table: jax.Array
    pos: jax.Array


def init_kv_cache(batch: int, n_kv: int, max_len: int, head_dim: int,
                  dtype=jnp.bfloat16, quantized: bool = False):
    if quantized:
        return QuantKVCache(
            k_codes=jnp.zeros((batch, n_kv, max_len, head_dim), jnp.int8),
            k_scale=jnp.ones((batch, n_kv, max_len, 1), jnp.float32),
            v_codes=jnp.zeros((batch, n_kv, max_len, head_dim), jnp.int8),
            v_scale=jnp.ones((batch, n_kv, max_len, 1), jnp.float32),
            pos=jnp.zeros((batch,), jnp.int32),
        )
    return KVCache(
        k=jnp.zeros((batch, n_kv, max_len, head_dim), dtype),
        v=jnp.zeros((batch, n_kv, max_len, head_dim), dtype),
        pos=jnp.zeros((batch,), jnp.int32),
    )


def init_paged_kv_cache(batch: int, n_kv: int, pool_blocks: int,
                        block_size: int, nblk: int, head_dim: int,
                        dtype=jnp.bfloat16, quantized: bool = False):
    """Block-pool cache init. The table starts as a striped identity map
    (row b's logical block j -> physical b*nblk + j, modulo the pool) so a
    freshly initialized paged cache behaves exactly like per-slot stripes
    until an allocator rewrites the tables."""
    ident = (jnp.arange(batch)[:, None] * nblk
             + jnp.arange(nblk)[None, :]) % pool_blocks
    table = ident.astype(jnp.int32)
    pos = jnp.zeros((batch,), jnp.int32)
    if quantized:
        return PagedQuantKVCache(
            k_codes=jnp.zeros((pool_blocks, n_kv, block_size, head_dim),
                              jnp.int8),
            k_scale=jnp.ones((pool_blocks, n_kv, block_size, 1), jnp.float32),
            v_codes=jnp.zeros((pool_blocks, n_kv, block_size, head_dim),
                              jnp.int8),
            v_scale=jnp.ones((pool_blocks, n_kv, block_size, 1), jnp.float32),
            table=table, pos=pos)
    return PagedKVCache(
        k=jnp.zeros((pool_blocks, n_kv, block_size, head_dim), dtype),
        v=jnp.zeros((pool_blocks, n_kv, block_size, head_dim), dtype),
        table=table, pos=pos)


def pool_block_values(cache, ids: jax.Array) -> dict:
    """Slice physical pool blocks `ids` ((C,) int32) out of one paged cache
    leaf: each pool array narrowed to C entries along its block axis. Works
    on the bare (P, H, bs, ...) layout and on the serving engine's stacked
    (n_layers, P, H, bs, ...) layout alike — the block axis is located from
    the trailing (H, bs, last) structure. `store_pool_blocks` is the exact
    inverse; together they are the device halves of KV block swap-out/in."""
    def take(a):
        return jnp.take(a, ids, axis=a.ndim - 4)

    if isinstance(cache, PagedKVCache):
        return {"k": take(cache.k), "v": take(cache.v)}
    if isinstance(cache, PagedQuantKVCache):
        return {"k_codes": take(cache.k_codes), "k_scale": take(cache.k_scale),
                "v_codes": take(cache.v_codes), "v_scale": take(cache.v_scale)}
    raise TypeError(f"not a paged cache leaf: {type(cache).__name__}")


def store_pool_blocks(cache, values: dict, dst: jax.Array):
    """Scatter `pool_block_values`-shaped block contents back into the pool
    at physical blocks `dst` ((C,) int32). Entries equal to the pool size
    are padding and are dropped, so a fixed-width dst traces once."""
    def put(a, vals):
        idx = (slice(None),) * (a.ndim - 4) + (dst,)
        return a.at[idx].set(jnp.asarray(vals, a.dtype), mode="drop")

    if isinstance(cache, PagedKVCache):
        return cache._replace(k=put(cache.k, values["k"]),
                              v=put(cache.v, values["v"]))
    if isinstance(cache, PagedQuantKVCache):
        return cache._replace(
            k_codes=put(cache.k_codes, values["k_codes"]),
            k_scale=put(cache.k_scale, values["k_scale"]),
            v_codes=put(cache.v_codes, values["v_codes"]),
            v_scale=put(cache.v_scale, values["v_scale"]))
    raise TypeError(f"not a paged cache leaf: {type(cache).__name__}")


def _q8(x: jax.Array):
    """Per-(b, h, position) row int8 quantization with a pow2 scale (the
    int8 caches' write path)."""
    with jax.named_scope("kv_cache"):
        amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8)
        scale = pow2_ceil(amax.astype(jnp.float32) / 127.0)
        codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                         -128, 127).astype(jnp.int8)
    return codes, scale


def _dq8(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (codes.astype(jnp.float32) * scale).astype(dtype)


def attn_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
              qkv_bias: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "q": linear_init(ks[0], d_model, n_heads * head_dim, qkv_bias, dtype),
        "k": linear_init(ks[1], d_model, n_kv * head_dim, qkv_bias, dtype),
        "v": linear_init(ks[2], d_model, n_kv * head_dim, qkv_bias, dtype),
        "o": linear_init(ks[3], n_heads * head_dim, d_model, False, dtype),
    }


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    b, l, _ = x.shape
    return x.reshape(b, l, n, -1).transpose(0, 2, 1, 3)     # (B, H, L, D)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, l, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, l, h * d)


def _row_update(buf: jax.Array, new: jax.Array, start: jax.Array) -> jax.Array:
    """Per-row cache write. buf: (B, H, L_max, ...); new: (B, H, l, ...);
    start: (B,) — row b's new tokens land at start[b]..start[b]+l-1."""
    l = new.shape[2]
    if l == 1:
        # decode hot path: start <= L_max - 1 always, the slice write fits
        return jax.vmap(
            lambda bb, nn, ss: jax.lax.dynamic_update_slice_in_dim(
                bb, nn, ss, axis=1))(buf, new.astype(buf.dtype), start)
    # A multi-token chunk's write window may overrun L_max on a row's FINAL
    # partial chunk (pad tail only — valid tokens always fit, the engine
    # guarantees start + lengths[b] <= L_max). dynamic_update_slice would
    # CLAMP the window start and shift valid tokens to wrong positions;
    # scatter with mode="drop" keeps them in place and drops the
    # out-of-range pad writes instead.
    idx = start[:, None] + jnp.arange(l)
    return jax.vmap(lambda bb, nn, ii: bb.at[:, ii].set(nn, mode="drop"))(
        buf, new.astype(buf.dtype), idx)


def _paged_update(pool: jax.Array, new: jax.Array, start: jax.Array,
                  table: jax.Array, lengths: Optional[jax.Array]) -> jax.Array:
    """Scatter a (B, H, l, ...) update into the (P, H, bs, ...) block pool.
    Token i of row b lands at physical block table[b, (start[b]+i)//bs],
    offset (start[b]+i)%bs. Positions past lengths[b] (right-pad) or past
    the table's reach scatter out of bounds and are dropped."""
    b, _, l = new.shape[:3]
    pool_blocks, _, bs = pool.shape[:3]
    nblk = table.shape[1]
    tok = start[:, None] + jnp.arange(l)                        # (B, l)
    lb = tok // bs
    phys = jnp.take_along_axis(table, jnp.clip(lb, 0, nblk - 1), axis=1)
    valid = lb < nblk
    if lengths is not None:
        valid &= jnp.arange(l)[None, :] < lengths[:, None]
    phys = jnp.where(valid, phys, pool_blocks)                  # OOB sentinel
    vals = new.astype(pool.dtype).transpose(0, 2, 1, 3)         # (B, l, H, .)
    return pool.at[phys, :, tok % bs].set(vals, mode="drop")


def attn_apply(p, x: jax.Array, *, n_heads: int, n_kv: int, causal: bool = True,
               window: Optional[int] = None, softcap: Optional[float] = None,
               rope_theta: float = 10000.0, positions: Optional[jax.Array] = None,
               cache: Optional[KVCache] = None,
               lengths: Optional[jax.Array] = None,
               policy: QuantPolicy = QuantPolicy()):
    """Self attention. Returns (out, new_cache). With a cache, x holds the new
    token(s) and attends to cache[:pos[b]] + x, per batch row.

    lengths: optional (B,) count of VALID new tokens per row (continuous
    batching: a right-padded batched prefill, or rows sitting a call out).
    Rows with lengths[b] == 0 keep their cache and position untouched; rows
    with 0 < lengths[b] < l advance by lengths[b], so the pad tail is never
    inside any row's causal frontier — pad keys are thereby masked out of all
    future attention, and each pad slot is overwritten before the frontier
    reaches it.
    """
    from .layers import _tp
    b, l, _ = x.shape
    q = _split_heads(_tp(linear(p["q"], x, policy), None, "model"), n_heads)
    k = _split_heads(_tp(linear(p["k"], x, policy), None, "model"), n_kv)
    v = _split_heads(_tp(linear(p["v"], x, policy), None, "model"), n_kv)

    if cache is not None:
        start = cache.pos
        uniform = start.ndim == 0               # legacy batch-global scalar
        if uniform:
            assert lengths is None, \
                "per-row lengths need a per-row (B,) cache position"
        if positions is None:
            positions = start + jnp.arange(l) if uniform \
                else start[:, None] + jnp.arange(l)          # (l,) | (B, l)
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
        new_pos = start + (l if lengths is None else lengths)
        keep_row = None if lengths is None else lengths > 0

        @jax.named_scope("kv_cache")
        def upd(buf, new):
            if uniform:
                # all rows at one position: a single contiguous slice write
                # lowers cheaper than the per-row scatter
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, new.astype(buf.dtype), start, axis=2)
            out = _row_update(buf, new, start)
            if keep_row is not None:
                out = jnp.where(keep_row[:, None, None, None], out, buf)
            return out

        if isinstance(cache, (PagedKVCache, PagedQuantKVCache)):
            assert not uniform, "paged caches carry per-row (B,) positions"

            @jax.named_scope("kv_cache")
            def pupd(pool, new):
                return _paged_update(pool, new, start, cache.table, lengths)

            if isinstance(cache, PagedQuantKVCache):
                kc, ks = _q8(k)
                vc, vs = _q8(v)
                new_cache = PagedQuantKVCache(
                    pupd(cache.k_codes, kc), pupd(cache.k_scale, ks),
                    pupd(cache.v_codes, vc), pupd(cache.v_scale, vs),
                    cache.table, new_pos)
                out = _cached_attn(q, new_cache.k_codes, new_cache.v_codes,
                                   start, l, causal, window, softcap,
                                   lengths=lengths,
                                   k_scale=new_cache.k_scale,
                                   v_scale=new_cache.v_scale,
                                   block_tables=cache.table)
            else:
                ck = pupd(cache.k, k)
                cv = pupd(cache.v, v)
                new_cache = PagedKVCache(ck, cv, cache.table, new_pos)
                out = _cached_attn(q, ck, cv, start, l, causal, window,
                                   softcap, lengths=lengths,
                                   block_tables=cache.table)
        elif isinstance(cache, QuantKVCache):
            kc, ks = _q8(k)
            vc, vs = _q8(v)
            new_cache = QuantKVCache(upd(cache.k_codes, kc),
                                     upd(cache.k_scale, ks),
                                     upd(cache.v_codes, vc),
                                     upd(cache.v_scale, vs), new_pos)
            # codes + scales go to attention UNMATERIALIZED: the decode /
            # prefill kernels dequantize block-by-block in VMEM, the ref
            # path at dispatch — either way no full-cache f32 copy in HBM
            out = _cached_attn(q, new_cache.k_codes, new_cache.v_codes,
                               start, l, causal, window, softcap,
                               lengths=lengths,
                               k_scale=new_cache.k_scale,
                               v_scale=new_cache.v_scale)
        else:
            ck = upd(cache.k, k)
            cv = upd(cache.v, v)
            new_cache = KVCache(ck, cv, new_pos)
            # attend over the full (static-length) cache; the per-row causal
            # mask at offset=start[b] kills each row's not-yet-written tail
            out = _cached_attn(q, ck, cv, start, l, causal, window, softcap,
                               lengths=lengths)
        out = _tp(_merge_heads(out), None, "model")
        return _tp(linear(p["o"], out, policy), "model", None), new_cache

    if positions is None:
        positions = jnp.arange(l)
    q = rope(q, positions, rope_theta)
    k = rope(k, positions, rope_theta)
    with jax.named_scope("attention"):
        out = aio_ops.attention(q, k, v, causal=causal, window=window,
                                softcap=softcap)
    out = _tp(_merge_heads(out), None, "model")
    return _tp(linear(p["o"], out, policy), "model", None), None


def _cached_attn(q, ck, cv, start, l, causal, window, softcap,
                 lengths=None, k_scale=None, v_scale=None, block_tables=None):
    """Decode-path attention: row b's query positions start[b]..start[b]+l-1
    over a cache of static length; the per-row offset lines the causal mask up
    and also masks the not-yet-written tail (kpos <= qpos < start[b]+l).
    lengths (B,) marks the valid query count of a right-padded chunk — the
    varlen prefill kernel prunes with it. With k_scale/v_scale, ck/cv are
    int8 codes (dequant happens at dispatch or inside the kernels)."""
    if k_scale is None:
        with jax.named_scope("kv_cache"):
            ck, cv = ck.astype(q.dtype), cv.astype(q.dtype)
    with jax.named_scope("attention"):
        return aio_ops.attention(q, ck, cv, causal=True, window=window,
                                 softcap=softcap, offset=start,
                                 lengths=lengths, k_scale=k_scale,
                                 v_scale=v_scale, block_tables=block_tables)


def cross_attn_apply(p, x: jax.Array, memory: jax.Array, *, n_heads: int,
                     n_kv: int, policy: QuantPolicy = QuantPolicy()):
    """Encoder-decoder cross attention (whisper): q from x, k/v from memory."""
    q = _split_heads(linear(p["q"], x, policy), n_heads)
    k = _split_heads(linear(p["k"], memory, policy), n_kv)
    v = _split_heads(linear(p["v"], memory, policy), n_kv)
    with jax.named_scope("attention"):
        out = aio_ops.attention(q, k, v, causal=False)
    return linear(p["o"], _merge_heads(out), policy)
