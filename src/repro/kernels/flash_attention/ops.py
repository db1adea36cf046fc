"""Attention: registry implementations + legacy shim.

"pallas" is the 128-aligned scalar-offset flash kernel (full-sequence
prefill); "pallas-prefill" is the VARLEN flash-prefill kernel (multi-token
right-padded chunks over a cache at per-row positions — scalar-prefetched
pos+lengths, q-block and KV-block pruning, fused int8-KV dequant);
"pallas-decode" is the flash-decode kernel (short Lq over a long per-row
cache, scalar-prefetched positions, block pruning, fused int8-KV dequant);
"ref" is the XLA path — one-shot scores for short contexts, chunked
online-softmax for long no-grad prefill (memory-bounded, so 32k-prefill
dry-runs reflect production footprints). `repro.api.ops.attention` owns the
dispatch, including shape eligibility (see `repro.api.ops.attention_route`).

Every impl accepts optional `k_scale`/`v_scale`: when given, k/v are int8
codes with per-position pow2 scales (the QuantKVCache layout) and the impl
dequantizes — in VMEM for the decode/prefill kernels, up front for the
others. Every impl also accepts `lengths` (per-row valid query counts for a
right-padded chunk): the varlen prefill kernel PRUNES with it; the others
ignore it (their outputs at invalid positions are garbage the engine never
consumes, and masking them would change nothing downstream).

The serving routes ("pallas-prefill", "pallas-decode", "ref") also take
`k_new`/`v_new` and `layer`: k/v are then the stacked per-slot caches of
every layer, the step's valid new rows are written into layer `layer`
before it is attended, and the impl returns (out, k, v). The kernels do
that write in place; the ref path scatters the rows and reads the layer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...api.policy import ExecutionPolicy
from ...api.registry import register
from .decode import (flash_decode_paged_pallas,
                     flash_decode_paged_quant_pallas, flash_decode_pallas,
                     flash_decode_quant_pallas)
from .kernel import flash_attention_pallas
from .prefill import (flash_prefill_paged_pallas,
                      flash_prefill_paged_quant_pallas, flash_prefill_pallas,
                      flash_prefill_quant_pallas)
from .ref import chunked_attention, mha_ref

__all__ = ["attention"]


def _dequant(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """QuantKVCache dequant, matching models.attention._q8's inverse: round
    through the compute dtype so ref results are unchanged by the move from
    materialize-in-HBM to dequant-at-dispatch."""
    return (codes.astype(jnp.float32) * scale).astype(dtype)


def _maybe_dequant(q, k, v, k_scale, v_scale):
    if k_scale is None:
        return k, v
    return _dequant(k, k_scale, q.dtype), _dequant(v, v_scale, q.dtype)


def _write_rows(cache: jax.Array, new: jax.Array, layer, pos: jax.Array,
                lengths: Optional[jax.Array]) -> jax.Array:
    """The ref path's cache write: row b's first lengths[b] new rows
    ((B, Hkv, l, D), all l when lengths is None), rounded to the cache
    dtype, land at pos[b].. of layer `layer` of the stacked (n_layers, B,
    Hkv, Lk, D) cache; pad rows and rows past Lk are dropped."""
    b, _, l, _ = new.shape
    i = jnp.arange(l)
    idx = pos[:, None] + i                                     # (B, l)
    if lengths is not None:
        idx = jnp.where(i < lengths[:, None], idx, cache.shape[3])
    vals = new.astype(cache.dtype).transpose(0, 2, 1, 3)       # (B, l, H, D)
    return cache.at[layer, jnp.arange(b)[:, None], :, idx].set(
        vals, mode="drop")


def _gather_pages(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Materialize a (P, Hkv, bs, last) block pool into per-row cache-shaped
    (B, Hkv, nblk*bs, last) via the (B, nblk) block table — the ref path's
    view of a paged cache. Positions past a row's frontier read whatever the
    mapped blocks hold; the causal/frontier mask removes them exactly, the
    same contract the per-slot cache tail relies on."""
    g = pool[table]                              # (B, nblk, Hkv, bs, last)
    b, nblk, h, bs, last = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, h, nblk * bs, last)


@register("attention", "pallas")
def _attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      scale: Optional[float] = None, offset=0,
                      lengths: Optional[jax.Array] = None,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None,
                      block_tables: Optional[jax.Array] = None,
                      policy: ExecutionPolicy) -> jax.Array:
    assert block_tables is None, \
        "the full-sequence kernel has no paged route (dispatch sends paged " \
        "cache-shaped calls to pallas-prefill/pallas-decode/ref)"
    k, v = _maybe_dequant(q, k, v, k_scale, v_scale)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale, offset=offset)


@register("attention", "pallas-prefill")
def _attention_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: Optional[int] = None,
                       softcap: Optional[float] = None,
                       scale: Optional[float] = None, offset=0,
                       lengths: Optional[jax.Array] = None,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None,
                       block_tables: Optional[jax.Array] = None,
                       k_new: Optional[jax.Array] = None,
                       v_new: Optional[jax.Array] = None, layer=None,
                       policy: ExecutionPolicy):
    assert causal, "the varlen prefill kernel is causal by construction"
    if k_new is not None:
        return flash_prefill_pallas(
            q, k, v, pos=offset, lengths=lengths, window=window,
            softcap=softcap, scale=scale, bq=policy.bq, bkv=policy.bkv,
            k_new=k_new, v_new=v_new, layer=layer)
    if block_tables is not None:
        if k_scale is not None:
            return flash_prefill_paged_quant_pallas(
                q, k, k_scale, v, v_scale, table=block_tables, pos=offset,
                lengths=lengths, window=window, softcap=softcap, scale=scale,
                bq=policy.bq)
        return flash_prefill_paged_pallas(
            q, k, v, table=block_tables, pos=offset, lengths=lengths,
            window=window, softcap=softcap, scale=scale, bq=policy.bq)
    if k_scale is not None:
        return flash_prefill_quant_pallas(
            q, k, k_scale, v, v_scale, pos=offset, lengths=lengths,
            window=window, softcap=softcap, scale=scale, bq=policy.bq,
            bkv=policy.bkv)
    return flash_prefill_pallas(q, k, v, pos=offset, lengths=lengths,
                                window=window, softcap=softcap, scale=scale,
                                bq=policy.bq, bkv=policy.bkv)


@register("attention", "pallas-decode")
def _attention_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      scale: Optional[float] = None, offset=0,
                      lengths: Optional[jax.Array] = None,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None,
                      block_tables: Optional[jax.Array] = None,
                      k_new: Optional[jax.Array] = None,
                      v_new: Optional[jax.Array] = None, layer=None,
                      policy: ExecutionPolicy):
    assert causal, "the decode kernel is causal by construction"
    if k_new is not None:
        return flash_decode_pallas(
            q, k, v, pos=offset, window=window, softcap=softcap, scale=scale,
            bkv=policy.bkv, k_new=k_new, v_new=v_new, layer=layer,
            lengths=lengths)
    if block_tables is not None:
        if k_scale is not None:
            return flash_decode_paged_quant_pallas(
                q, k, k_scale, v, v_scale, table=block_tables, pos=offset,
                window=window, softcap=softcap, scale=scale)
        return flash_decode_paged_pallas(
            q, k, v, table=block_tables, pos=offset, window=window,
            softcap=softcap, scale=scale)
    if k_scale is not None:
        return flash_decode_quant_pallas(
            q, k, k_scale, v, v_scale, pos=offset, window=window,
            softcap=softcap, scale=scale, bkv=policy.bkv)
    return flash_decode_pallas(q, k, v, pos=offset, window=window,
                               softcap=softcap, scale=scale, bkv=policy.bkv)


@register("attention", "ref")
def _attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: Optional[int] = None,
                   softcap: Optional[float] = None,
                   scale: Optional[float] = None, offset=0,
                   lengths: Optional[jax.Array] = None,
                   k_scale: Optional[jax.Array] = None,
                   v_scale: Optional[jax.Array] = None,
                   block_tables: Optional[jax.Array] = None,
                   k_new: Optional[jax.Array] = None,
                   v_new: Optional[jax.Array] = None, layer=None,
                   policy: ExecutionPolicy):
    if k_new is not None:
        k = _write_rows(k, k_new, layer, offset, lengths)
        v = _write_rows(v, v_new, layer, offset, lengths)
        out = _attention_ref(q, k[layer].astype(q.dtype),
                             v[layer].astype(q.dtype), causal=causal,
                             window=window, softcap=softcap, scale=scale,
                             offset=offset, policy=policy)
        return out, k, v
    if block_tables is not None:
        # gather codes AND scales through the table, then dequantize — the
        # same value order as dequantize-then-gather, without a f32 pool copy
        k = _gather_pages(k, block_tables)
        v = _gather_pages(v, block_tables)
        if k_scale is not None:
            k_scale = _gather_pages(k_scale, block_tables)
            v_scale = _gather_pages(v_scale, block_tables)
    k, v = _maybe_dequant(q, k, v, k_scale, v_scale)
    lq, lk = q.shape[2], k.shape[2]
    # One-shot scores up to 4k x 8k: under layer-level remat the score matrix
    # is transient, and autodiff through it is cheap. The chunked scan is for
    # LONG no-grad prefill only — under grad it would checkpoint every
    # chunk's probabilities (O(L^2) saved residuals, the exact blow-up flash
    # attention exists to avoid).
    if lq == 1 or lq * lk <= 4096 * 8192:
        return mha_ref(q, k, v, causal=causal, window=window, softcap=softcap,
                       scale=scale, offset=offset)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, offset=offset,
                             chunk=policy.chunk)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None, scale: Optional[float] = None,
              offset: int = 0, chunk: int = 1024,
              prefer_pallas: bool | None = None) -> jax.Array:
    """Deprecated: call `repro.api.ops.attention` (policy-driven) instead."""
    from ... import api
    return api.ops.attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        offset=offset, chunk=chunk,
        backend=api.ops.backend_from_prefer_pallas(prefer_pallas))
