"""Pallas flash-decode: short-query attention over a long per-row KV cache.

The serving engine's hottest loop is Lq=1 attention over a (B, Hkv, max_len,
D) cache where every batch row ("slot") sits at its own position — exactly
the shape the prefill flash kernel cannot take (it requires Lq % 128 == 0
and a scalar offset). This kernel is specialized for it:

  * grid (B*Hkv, nk) over KV blocks with the per-row cache position vector
    delivered via SCALAR PREFETCH, so the K/V BlockSpec index maps can see it
    before any DMA is issued;
  * per-row BLOCK PRUNING: blocks entirely beyond row b's causal frontier
    (`pos[b] + Lq - 1`) are skipped with `pl.when`, and their index maps
    clamp to the last needed block so the pipeline never fetches them from
    HBM — work scales with each row's RESIDENT context, not max_len;
  * the GQA head group is packed into the q tile: (group·Lq, D) instead of a
    degenerate (1, D) row, so the score matmul feeds the MXU a real operand
    and K/V tiles are read once per kv-head;
  * a fused INT8-KV variant takes `(codes, pow2 scale)` and dequantizes in
    VMEM — the full-cache dequant materialization in HBM disappears. The
    in-kernel dequant rounds through `cast_dtype` (the q dtype) so it is
    bit-identical to dequantize-then-dense-kernel;
  * an IN-PLACE variant (`k_new`/`v_new` given) takes the stacked bf16
    cache of every layer plus the step's new rows, reads layer `layer`
    through its index map (the layer is one more scalar prefetch), merges
    the new row into the frontier block in VMEM, attends over it, and
    writes that one block back through an output aliased to the cache.
    No whole-cache cast, select or copy is left around the launch.

Validated in interpret mode against ref.mha_ref (tests/test_decode_kernel.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import ceil_div, interpret_mode, pad_to
from .shared import NEG_INF as _NEG_INF
from .shared import as_row_vector, merge_rows, vmem_dequant, zero_tail

__all__ = ["flash_decode_pallas", "flash_decode_quant_pallas",
           "flash_decode_paged_pallas", "flash_decode_paged_quant_pallas",
           "decode_block_visits", "decode_index_maps",
           "paged_decode_index_maps", "update_decode_index_maps"]


def _block_bounds(start, lq: int, window: Optional[int], bkv: int):
    """KV-block range a row with cache position `start` actually needs:
    up to the causal frontier (start + lq - 1), and — with a sliding
    window — no earlier than the oldest in-window key of the first query
    (start - window + 1), so windowed decode work scales with the WINDOW,
    not the resident context. first <= last always (window >= 1)."""
    last = (start + lq - 1) // bkv
    if window is None:
        return 0, last
    return jnp.maximum(start - (window - 1), 0) // bkv, last


def _online_block(pos_ref, q_ref, load_k, load_v, o_ref, visits_ref, m_ref,
                  l_ref, acc_ref, *, scale: float, window: Optional[int],
                  softcap: Optional[float], lq: int, hkv: int, bkv: int,
                  nk: int, lk_real: int):
    """One (bh, ik) grid step of the online-softmax accumulation."""
    bh, ik = pl.program_id(0), pl.program_id(1)
    start = pos_ref[bh // hkv]
    first_blk, last_blk = _block_bounds(start, lq, window, bkv)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if visits_ref is not None:
            visits_ref[...] = jnp.zeros_like(visits_ref)

    @pl.when((ik >= first_blk) & (ik <= last_blk))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (group*lq, D)
        k = load_k()                                       # (bkv, D) f32
        v = load_v()
        gl = q.shape[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # packed row r = g*lq + i sits at query position start + i
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, (gl, bkv), 0) % lq
        kpos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (gl, bkv), 1)
        keep = (kpos < lk_real) & (kpos <= qpos)
        if window is not None:
            keep &= kpos > qpos - window
        s = jnp.where(keep, s, _NEG_INF)

        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_cur = jnp.maximum(m_prev[:, 0], s.max(-1))
        alpha = jnp.exp(m_prev[:, 0] - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        m_ref[...] = m_cur[:, None]
        l_ref[...] = (l_prev[:, 0] * alpha + p.sum(-1))[:, None]
        acc_ref[...] = acc_prev * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        if visits_ref is not None:
            visits_ref[0, ik] = 1

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _dense_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *rest, debug_visits,
                  **kw):
    visits_ref, (m_ref, l_ref, acc_ref) = \
        (rest[0], rest[1:]) if debug_visits else (None, rest)
    _online_block(pos_ref, q_ref,
                  lambda: k_ref[0].astype(jnp.float32),
                  lambda: v_ref[0].astype(jnp.float32),
                  o_ref, visits_ref, m_ref, l_ref, acc_ref, **kw)


def _frontier_block(start, bkv: int, nk: int):
    """The KV block that holds a row's new token at position `start`
    (clamped into the cache for a row idling at its end)."""
    return jnp.minimum(start // bkv, nk - 1)


def _update_kernel(pos_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, kn_ref,
                   vn_ref, o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref, *,
                   bkv: int, nk: int, lk_real: int, hkv: int, **kw):
    """In-place decode: the new row merges into the frontier block, which
    is written to the aliased cache output and attended from there."""
    ik = pl.program_id(1)
    row = pl.program_id(0) // hkv
    start = pos_ref[row]
    here = ik == _frontier_block(start, bkv, nk)

    @pl.when(here)
    def _write():
        # lengths[row] == 0 merges nothing: the block goes back unchanged
        ko_ref[0] = merge_rows(k_ref[0], kn_ref[0], ik * bkv, start,
                               len_ref[row])
        vo_ref[0] = merge_rows(v_ref[0], vn_ref[0], ik * bkv, start,
                               len_ref[row])

    def load(ref, out_ref):
        def get():
            blk = jax.lax.cond(here, lambda: out_ref[0], lambda: ref[0])
            if lk_real % bkv:
                blk = zero_tail(blk, ik * bkv, lk_real)
            return blk.astype(jnp.float32)
        return get

    _online_block(pos_ref, q_ref, load(k_ref, ko_ref), load(v_ref, vo_ref),
                  o_ref, None, m_ref, l_ref, acc_ref, bkv=bkv, nk=nk,
                  lk_real=lk_real, hkv=hkv, **kw)


def _quant_kernel(pos_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, o_ref,
                  *rest, debug_visits, cast_dtype, **kw):
    visits_ref, (m_ref, l_ref, acc_ref) = \
        (rest[0], rest[1:]) if debug_visits else (None, rest)
    _online_block(pos_ref, q_ref,
                  lambda: vmem_dequant(kc_ref, ks_ref, cast_dtype),
                  lambda: vmem_dequant(vc_ref, vs_ref, cast_dtype),
                  o_ref, visits_ref, m_ref, l_ref, acc_ref, **kw)


def decode_index_maps(*, lq: int, hkv: int, bkv: int,
                      window: Optional[int]):
    """The q and K/V BlockSpec index maps of a decode launch.

    Module-level (not a `_launch` closure) so the launch assembly and the
    `repro.analysis` kernel-contract checker evaluate the SAME functions —
    the checker sweeps them out-of-trace over (shape x policy) cases and
    flags out-of-bounds block indices before any kernel runs.
    """
    def q_index(bh, ik, pos_ref):
        return (bh, 0, 0)

    def kv_index(bh, ik, pos_ref):
        # clamp pruned steps into [first, last]: the pipeline sees an index
        # it already fetched and skips the HBM fetch entirely
        first, last = _block_bounds(pos_ref[bh // hkv], lq, window, bkv)
        return (bh, jnp.clip(ik, first, last), 0)

    return q_index, kv_index


def update_decode_index_maps(*, hkv: int, bkv: int, nk: int, rows: int,
                             window: Optional[int]):
    """The index maps of an in-place decode launch (Lq = 1): q, the
    stacked cache's K/V read, the step's new rows, and the aliased cache
    output. The cache is laid out (n_layers*rows, Lk, D), so row-head bh of
    layer i is row i*rows + bh; the layer rides scalar prefetch after the
    positions and lengths. The read clamps exactly as `decode_index_maps`;
    the output stays on the frontier block for the whole KV loop, so each
    row-head writes that one block back once."""
    def q_index(bh, ik, pos_ref, len_ref, layer_ref):
        return (bh, 0, 0)

    def kv_index(bh, ik, pos_ref, len_ref, layer_ref):
        first, last = _block_bounds(pos_ref[bh // hkv], 1, window, bkv)
        return (layer_ref[0] * rows + bh, jnp.clip(ik, first, last), 0)

    def out_kv_index(bh, ik, pos_ref, len_ref, layer_ref):
        return (layer_ref[0] * rows + bh,
                _frontier_block(pos_ref[bh // hkv], bkv, nk), 0)

    return q_index, kv_index, out_kv_index


def paged_decode_index_maps(*, lq: int, hkv: int, bs: int,
                            window: Optional[int]):
    """Index maps of a PAGED decode launch: same per-row block pruning as
    `decode_index_maps`, then one extra indirection — logical KV block `lb`
    of row b lives at physical pool block `table[b, lb]`. The pool is laid
    out (P*Hkv, bs, D), so head h of physical block p is row p*hkv + h.
    The clamp runs BEFORE the table lookup, so only table entries a row
    actually owns (logical blocks up to its frontier) are ever read."""
    def q_index(bh, ik, pos_ref, tbl_ref):
        return (bh, 0, 0)

    def kv_index(bh, ik, pos_ref, tbl_ref):
        b = bh // hkv
        first, last = _block_bounds(pos_ref[b], lq, window, bs)
        lb = jnp.clip(ik, first, last)
        return (tbl_ref[b, lb] * hkv + bh % hkv, 0, 0)

    return q_index, kv_index


def _paged_launch(kernel, q, pool_arrays, pos, table, *, interpret, window,
                  softcap, scale):
    """pallas_call assembly for the paged variants. pool_arrays are
    (P, Hkv, bs, last) block pools; `table` (B, nblk) int32 is scalar-
    prefetched alongside `pos` so the K/V index maps can indirect."""
    b, hq, lq, d = q.shape
    hkv, bs = pool_arrays[0].shape[1:3]
    group = hq // hkv
    gl = group * lq
    nblk = table.shape[1]

    qr = q.reshape(b, hkv, gl, d).reshape(b * hkv, gl, d)
    kvr = [a.reshape(a.shape[0] * hkv, bs, a.shape[-1]) for a in pool_arrays]

    q_index, kv_index = paged_decode_index_maps(lq=lq, hkv=hkv, bs=bs,
                                                window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hkv, nblk),
        in_specs=[pl.BlockSpec((1, gl, d), q_index)] +
                 [pl.BlockSpec((1, bs, a.shape[-1]), kv_index)
                  for a in kvr],
        out_specs=[pl.BlockSpec((1, gl, d), q_index)],
        scratch_shapes=[
            pltpu.VMEM((gl, 1), jnp.float32),
            pltpu.VMEM((gl, 1), jnp.float32),
            pltpu.VMEM((gl, d), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        # every logical position a row can reach maps through its table, so
        # the only tail to mask is the causal frontier itself
        functools.partial(kernel, debug_visits=False, scale=scale,
                          window=window, softcap=softcap, lq=lq, hkv=hkv,
                          bkv=bs, nk=nblk, lk_real=nblk * bs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b * hkv, gl, d), q.dtype)],
        interpret=interpret,
    )(pos, table, qr, *kvr)
    return outs[0].reshape(b, hkv, group, lq, d).reshape(b, hq, lq, d)


def _paged_dense_kernel(pos_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                        **kw):
    # the table steers the index maps only; the body's logical-position math
    # (kpos = ik*bs + iota) is exactly the dense kernel's
    _dense_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *rest, **kw)


def _paged_quant_kernel(pos_ref, tbl_ref, q_ref, kc_ref, ks_ref, vc_ref,
                        vs_ref, o_ref, *rest, **kw):
    _quant_kernel(pos_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, o_ref,
                  *rest, **kw)


def flash_decode_paged_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                              table: jax.Array, pos,
                              window: Optional[int] = None,
                              softcap: Optional[float] = None,
                              scale: Optional[float] = None,
                              interpret: Optional[bool] = None):
    """Paged flash-decode. q: (B, Hq, Lq, D); k/v: (P, Hkv, bs, D) BLOCK
    POOLS shared by all rows; table: (B, nblk) int32 maps row b's logical
    block j to a physical pool block. Block size bs doubles as the launch's
    KV tile, so a paged launch at bs == bkv visits the same logical blocks
    with the same masks as the dense kernel — bit-identical outputs."""
    if interpret is None:
        interpret = interpret_mode()
    b = q.shape[0]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _paged_launch(_paged_dense_kernel, q, [k, v],
                         as_row_vector(pos, b), table.astype(jnp.int32),
                         interpret=interpret, window=window, softcap=softcap,
                         scale=scale)


def flash_decode_paged_quant_pallas(q: jax.Array, k_codes: jax.Array,
                                    k_scale: jax.Array, v_codes: jax.Array,
                                    v_scale: jax.Array, *, table: jax.Array,
                                    pos, window: Optional[int] = None,
                                    softcap: Optional[float] = None,
                                    scale: Optional[float] = None,
                                    interpret: Optional[bool] = None):
    """Paged int8-KV decode: codes (P, Hkv, bs, D) int8 + pow2 scales
    (P, Hkv, bs, 1) f32 pools, dequantized block-by-block in VMEM."""
    if interpret is None:
        interpret = interpret_mode()
    b = q.shape[0]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    kernel = functools.partial(_paged_quant_kernel, cast_dtype=q.dtype)
    return _paged_launch(kernel, q, [k_codes, k_scale, v_codes, v_scale],
                         as_row_vector(pos, b), table.astype(jnp.int32),
                         interpret=interpret, window=window, softcap=softcap,
                         scale=scale)


def _launch(kernel, q, kv_arrays, pos, *, bkv, interpret, debug_visits,
            window, softcap, scale, lk_real):
    """Shared pallas_call assembly for the dense and quantized variants.

    kv_arrays: (B, Hkv, Lk_padded, last) arrays sharing the KV index map
    (codes last=D, scales last=1)."""
    b, hq, lq, d = q.shape
    hkv = kv_arrays[0].shape[1]
    group = hq // hkv
    gl = group * lq
    lk = kv_arrays[0].shape[2]
    nk = lk // bkv

    # pack the GQA group into the q tile: head h = kv*group + g, so a plain
    # reshape groups each kv-head's queries contiguously
    qr = q.reshape(b, hkv, gl, d).reshape(b * hkv, gl, d)
    kvr = [a.reshape(b * hkv, lk, a.shape[-1]) for a in kv_arrays]

    q_index, kv_index = decode_index_maps(lq=lq, hkv=hkv, bkv=bkv,
                                          window=window)

    out_shape = [jax.ShapeDtypeStruct((b * hkv, gl, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, gl, d), q_index)]
    if debug_visits:
        out_shape.append(jax.ShapeDtypeStruct((b * hkv, nk), jnp.int32))
        out_specs.append(pl.BlockSpec((1, nk), lambda bh, ik, pos_ref:
                                      (bh, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, nk),
        in_specs=[pl.BlockSpec((1, gl, d), q_index)] +
                 [pl.BlockSpec((1, bkv, a.shape[-1]), kv_index)
                  for a in kvr],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((gl, 1), jnp.float32),
            pltpu.VMEM((gl, 1), jnp.float32),
            pltpu.VMEM((gl, d), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(kernel, debug_visits=debug_visits, scale=scale,
                          window=window, softcap=softcap, lq=lq, hkv=hkv,
                          bkv=bkv, nk=nk, lk_real=lk_real),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(pos, qr, *kvr)
    out = outs[0].reshape(b, hkv, group, lq, d).reshape(b, hq, lq, d)
    return (out, outs[1]) if debug_visits else out


def _update_launch(q, k, v, k_new, v_new, pos, lens, layer, *, bkv,
                   interpret, window, softcap, scale):
    """pallas_call assembly for the in-place variant: k/v are the stacked
    (n_layers, B, Hkv, Lk, D) caches, aliased to the second and third
    outputs."""
    b, hq, lq, d = q.shape
    n, _, hkv, lk, _ = k.shape
    assert lq == 1, "the in-place decode takes one new token per row"
    bkv = min(bkv, lk)          # the aliased cache cannot be padded
    group = hq // hkv
    rows = b * hkv
    nk = ceil_div(lk, bkv)

    qr = q.reshape(b, hkv, group, d).reshape(rows, group, d)
    kr, vr = (a.reshape(n * rows, lk, d) for a in (k, v))
    knr, vnr = (a.astype(k.dtype).reshape(rows, 1, d)
                for a in (k_new, v_new))

    q_index, kv_index, out_kv_index = update_decode_index_maps(
        hkv=hkv, bkv=bkv, nk=nk, rows=rows, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, nk),
        in_specs=[pl.BlockSpec((1, group, d), q_index),
                  pl.BlockSpec((1, bkv, d), kv_index),
                  pl.BlockSpec((1, bkv, d), kv_index),
                  pl.BlockSpec((1, 1, d), q_index),
                  pl.BlockSpec((1, 1, d), q_index)],
        out_specs=[pl.BlockSpec((1, group, d), q_index),
                   pl.BlockSpec((1, bkv, d), out_kv_index),
                   pl.BlockSpec((1, bkv, d), out_kv_index)],
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out, kr, vr = pl.pallas_call(
        functools.partial(_update_kernel, scale=scale, window=window,
                          softcap=softcap, lq=1, hkv=hkv, bkv=bkv, nk=nk,
                          lk_real=lk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, group, d), q.dtype),
                   jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                   jax.ShapeDtypeStruct(vr.shape, vr.dtype)],
        # operands: pos, lengths, layer, q, k, v, k_new, v_new
        input_output_aliases={4: 1, 5: 2},
        interpret=interpret,
    )(pos, lens, layer, qr, kr, vr, knr, vnr)
    return (out.reshape(b, hkv, group, 1, d).reshape(b, hq, 1, d),
            kr.reshape(k.shape), vr.reshape(v.shape))


def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        pos, window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None, bkv: int = 128,
                        interpret: Optional[bool] = None,
                        debug_visits: bool = False,
                        k_new: Optional[jax.Array] = None,
                        v_new: Optional[jax.Array] = None, layer=0,
                        lengths=None):
    """q: (B, Hq, Lq, D) short query; k, v: (B, Hkv, Lk, D) cache.

    pos: per-row (B,) cache position (or a scalar, broadcast): row b's
    queries sit at absolute positions pos[b]..pos[b]+Lq-1 and attend causally
    — keys beyond the frontier (the not-yet-written cache tail) are never
    visited, not merely masked.

    debug_visits=True additionally returns an (B*Hkv, nk) int32 map of KV
    blocks whose compute actually ran — the block-pruning evidence used by
    tests and benchmarks (interpret/debug use).

    In place: with k_new/v_new (B, Hkv, 1, D), k and v are the stacked
    (n_layers, B, Hkv, Lk, D) caches. Row b's new token is rounded to the
    cache dtype, written at position pos[b] of layer `layer` unless
    lengths[b] == 0 (None: every row writes), and attended with the rest of
    the row's context. Returns (out, k, v), k and v updated in place.
    """
    if interpret is None:
        interpret = interpret_mode()
    b = q.shape[0]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if k_new is not None:
        assert not debug_visits, "the in-place launch has no visit map"
        return _update_launch(
            q, k, v, k_new, v_new, as_row_vector(pos, b),
            as_row_vector(lengths, b, fill=1),
            jnp.asarray(layer, jnp.int32).reshape(1), bkv=bkv,
            interpret=interpret, window=window, softcap=softcap, scale=scale)
    lk_real = k.shape[2]
    k, v = pad_to(k, bkv, 2), pad_to(v, bkv, 2)
    return _launch(_dense_kernel, q, [k, v], as_row_vector(pos, b),
                   bkv=bkv, interpret=interpret, debug_visits=debug_visits,
                   window=window, softcap=softcap, scale=scale,
                   lk_real=lk_real)


def flash_decode_quant_pallas(q: jax.Array, k_codes: jax.Array,
                              k_scale: jax.Array, v_codes: jax.Array,
                              v_scale: jax.Array, *, pos,
                              window: Optional[int] = None,
                              softcap: Optional[float] = None,
                              scale: Optional[float] = None, bkv: int = 128,
                              interpret: Optional[bool] = None,
                              debug_visits: bool = False):
    """Fused int8-KV decode: codes (B, Hkv, Lk, D) int8 + per-position pow2
    scales (B, Hkv, Lk, 1) f32, dequantized block-by-block in VMEM."""
    if interpret is None:
        interpret = interpret_mode()
    b = q.shape[0]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    lk_real = k_codes.shape[2]
    arrays = [pad_to(a, bkv, 2)
              for a in (k_codes, k_scale, v_codes, v_scale)]
    kernel = functools.partial(_quant_kernel, cast_dtype=q.dtype)
    return _launch(kernel, q, arrays, as_row_vector(pos, b), bkv=bkv,
                   interpret=interpret, debug_visits=debug_visits,
                   window=window, softcap=softcap, scale=scale,
                   lk_real=lk_real)


def decode_block_visits(pos, lq: int, lk: int, bkv: int = 128,
                        window: Optional[int] = None):
    """Expected (visited, total) KV-block counts per kv-head row for a decode
    launch — what `debug_visits` measures, available without running it."""
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    nk = -(-max(lk, 1) // bkv)
    first, last = _block_bounds(pos, lq, window, bkv)
    visited = jnp.minimum(last, nk - 1) - first + 1
    return int(visited.sum()), int(pos.shape[0] * nk)
