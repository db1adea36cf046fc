"""Pallas varlen flash-prefill: batched variable-length prompt attention
over a cache-shaped K/V.

Admission prefill is the other half of every request's latency: the engine
feeds each admitted slot a fixed-width chunk of prompt tokens (right-padded)
whose queries sit at that row's own cache position. Until this kernel, those
launches fell back to the ref path (vector per-row offsets had no
Pallas-eligible route) and did O(width x max_len) f32 score work per row
regardless of how many tokens were real. This kernel is specialized for the
chunk shape:

  * grid (B*Hkv, nq, nk) over q-blocks x KV-blocks with the per-row cache
    position AND valid-length vectors delivered via SCALAR PREFETCH, so
    every BlockSpec index map can see them before any DMA is issued;
  * Q-BLOCK PRUNING: q-blocks entirely past a row's valid token count
    (`lengths[b]`) are skipped with `pl.when` and their index maps clamp to
    the last needed block — a row with 3 real tokens in a 64-wide chunk does
    one q-block of work, not ceil(64/bq);
  * KV-BLOCK PRUNING per (row, q-block): blocks beyond the q-block's causal
    frontier (`pos[b] + min((iq+1)*bq, lengths[b]) - 1`) are skipped, and a
    sliding window adds a LOWER bound, so work scales with each row's REAL
    prompt tokens and resident context, not the chunk width x max_len;
  * the GQA head group is packed into the q tile — (group, bq, D) reshaped
    to a (group*bq, D) MXU operand — so K/V tiles are read once per kv-head;
  * a fused INT8-KV variant takes `(codes, pow2 scale)` and dequantizes in
    VMEM, rounding through `cast_dtype` (the q dtype) so it is bit-identical
    to dequantize-then-dense-kernel;
  * an IN-PLACE variant (`k_new`/`v_new` given) takes the stacked bf16
    cache of every layer plus the chunk's new rows, reads layer `layer`
    through its index map, merges the valid new rows into the KV blocks
    that hold them in VMEM, attends over the merged blocks, and writes
    those blocks (at most two for a chunk no longer than a block) back
    through outputs aliased to the cache.

Rows' invalid (right-pad) query positions return ZEROS — deterministic and
never consumed (the engine gathers each row's last VALID position).

Validated in interpret mode against ref.mha_ref (tests/test_prefill_kernel).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import interpret_mode, pad_to
from .shared import NEG_INF as _NEG_INF
from .shared import as_row_vector, merge_rows, vmem_dequant, zero_tail

__all__ = ["flash_prefill_pallas", "flash_prefill_quant_pallas",
           "flash_prefill_paged_pallas", "flash_prefill_paged_quant_pallas",
           "prefill_block_visits", "prefill_index_maps",
           "paged_prefill_index_maps", "update_prefill_index_maps"]


def _q_last_block(ln, bq: int):
    """Last q-block index a row with `ln` valid tokens needs (>= 0)."""
    return jnp.maximum((ln + bq - 1) // bq - 1, 0)


def _kv_bounds(start, ln, iq, *, bq: int, bkv: int, nk: int,
               window: Optional[int]):
    """KV-block range q-block `iq` of a row at cache position `start` with
    `ln` valid tokens actually needs. The upper bound is the q-block's causal
    frontier (its last VALID query position); a sliding window adds a lower
    bound from its first query. Clamped so first <= last always — pruned
    steps clip into this range and re-see an already-fetched block."""
    qlo = iq * bq
    qhi = jnp.maximum(jnp.minimum(qlo + bq, ln) - 1, 0)
    last = jnp.minimum((start + qhi) // bkv, nk - 1)
    if window is None:
        return jnp.zeros_like(last), last
    first = jnp.maximum(start + qlo - (window - 1), 0) // bkv
    return jnp.minimum(first, last), last


def _online_block(pos_ref, len_ref, q_ref, load_k, load_v, o_ref, visits_ref,
                  m_ref, l_ref, acc_ref, *, scale: float,
                  window: Optional[int], softcap: Optional[float], bq: int,
                  group: int, hkv: int, bkv: int, nk: int, lk_real: int):
    """One (bh, iq, ik) grid step of the online-softmax accumulation."""
    bh, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start = pos_ref[bh // hkv]
    ln = len_ref[bh // hkv]
    qlo = iq * bq
    first_blk, last_blk = _kv_bounds(start, ln, iq, bq=bq, bkv=bkv, nk=nk,
                                     window=window)
    gl = group * bq

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if visits_ref is not None:
            visits_ref[...] = jnp.zeros_like(visits_ref)

    @pl.when((qlo < ln) & (ik >= first_blk) & (ik <= last_blk))
    def _compute():
        q = q_ref[0].reshape(gl, q_ref.shape[-1]).astype(jnp.float32)
        k = load_k()                                       # (bkv, D) f32
        v = load_v()
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # packed row r = g*bq + i is query i of the block: position
        # start + qlo + i, valid while qlo + i < ln
        qrel = qlo + jax.lax.broadcasted_iota(jnp.int32, (gl, bkv), 0) % bq
        kpos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (gl, bkv), 1)
        keep = (kpos < lk_real) & (qrel < ln) & (kpos <= start + qrel)
        if window is not None:
            keep &= kpos > start + qrel - window
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
            visits_ref[0, 0, ik] = 1

    @pl.when(ik == nk - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        # invalid (pad) query rows return deterministic zeros; fully-pruned
        # q-blocks are already zero (acc never accumulated)
        qrel = qlo + jax.lax.broadcasted_iota(jnp.int32, (gl, 1), 0) % bq
        out = jnp.where(qrel < ln, out, 0.0)
        o_ref[0] = out.reshape(group, bq, out.shape[-1]).astype(o_ref.dtype)


def _dense_kernel(pos_ref, len_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                  debug_visits, **kw):
    visits_ref, (m_ref, l_ref, acc_ref) = \
        (rest[0], rest[1:]) if debug_visits else (None, rest)
    _online_block(pos_ref, len_ref, q_ref,
                  lambda: k_ref[0].astype(jnp.float32),
                  lambda: v_ref[0].astype(jnp.float32),
                  o_ref, visits_ref, m_ref, l_ref, acc_ref, **kw)


def _write_blocks(start, ln, iq, ik, *, bq: int, bkv: int, nk: int):
    """Where an in-place launch writes at grid step (iq, ik) of a row at
    cache position `start` with `ln` valid new tokens: (the output's KV
    block, whether the step merges into it). The new rows span blocks
    first..last (one block, unchanged, when ln == 0), however many. Block j
    is merged at step (iq, j) of the first q-block iq holding one of its
    new rows; that q-block's KV range reads block j there (it starts at or
    below the block of its first row and ends at the block of its last).
    The output moves forward through first..last, reaching each block at
    its merge step, so each block is one contiguous run of grid steps and
    is written back once."""
    n = jnp.maximum(ln, 1)
    iqc = jnp.minimum(iq, _q_last_block(ln, bq))

    def blk(r):                                 # block of the chunk's row r
        return jnp.minimum((start + r) // bkv, nk - 1)

    # the block of the row before q-block iqc (row 0's for q-block 0),
    # merged already unless iqc == 0, and the block of its last row
    prev = blk(jnp.maximum(iqc * bq - 1, 0))
    hi = blk(jnp.minimum((iqc + 1) * bq, n) - 1)
    lo = jnp.where(iqc == 0, prev, prev + 1)
    block = jnp.where(iq > iqc, hi, jnp.clip(ik, prev, hi))
    merge = (iq == iqc) & (ik >= lo) & (ik <= hi)
    return block, merge


def _update_kernel(pos_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, kn_ref,
                   vn_ref, o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref, *,
                   bq: int, bkv: int, nk: int, lk_real: int, hkv: int, **kw):
    """In-place prefill: the chunk's valid rows merge into the blocks that
    hold them, which are written to the aliased cache outputs; the
    attention reads every block it visits with the chunk merged in."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    row = pl.program_id(0) // hkv
    start, ln = pos_ref[row], len_ref[row]
    _, merge = _write_blocks(start, ln, iq, ik, bq=bq, bkv=bkv, nk=nk)

    @pl.when(merge)
    def _write():
        ko_ref[0] = merge_rows(k_ref[0], kn_ref[0], ik * bkv, start, ln)
        vo_ref[0] = merge_rows(v_ref[0], vn_ref[0], ik * bkv, start, ln)

    # a visited block is block ik (the compute runs inside [first, last])
    hit = (ik * bkv < start + ln) & (start < (ik + 1) * bkv)

    def load(ref, new_ref):
        def get():
            blk = jax.lax.cond(
                hit, lambda: merge_rows(ref[0], new_ref[0], ik * bkv, start,
                                        ln),
                lambda: ref[0])
            if lk_real % bkv:
                blk = zero_tail(blk, ik * bkv, lk_real)
            return blk.astype(jnp.float32)
        return get

    _online_block(pos_ref, len_ref, q_ref, load(k_ref, kn_ref),
                  load(v_ref, vn_ref), o_ref, None, m_ref, l_ref, acc_ref,
                  bq=bq, bkv=bkv, nk=nk, lk_real=lk_real, hkv=hkv, **kw)


def _quant_kernel(pos_ref, len_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                  o_ref, *rest, debug_visits, cast_dtype, **kw):
    visits_ref, (m_ref, l_ref, acc_ref) = \
        (rest[0], rest[1:]) if debug_visits else (None, rest)
    _online_block(pos_ref, len_ref, q_ref,
                  lambda: vmem_dequant(kc_ref, ks_ref, cast_dtype),
                  lambda: vmem_dequant(vc_ref, vs_ref, cast_dtype),
                  o_ref, visits_ref, m_ref, l_ref, acc_ref, **kw)


def prefill_index_maps(*, bq: int, bkv: int, nk: int, hkv: int,
                       window: Optional[int]):
    """The q and K/V BlockSpec index maps of a varlen prefill launch.

    Module-level (not a `_launch` closure) so the launch assembly and the
    `repro.analysis` kernel-contract checker evaluate the SAME functions —
    the checker sweeps them out-of-trace over (shape x policy) cases and
    flags out-of-bounds block indices before any kernel runs.
    """
    def q_index(bh, iq, ik, pos_ref, len_ref):
        # pruned q-blocks clamp to the last block the row needs: the
        # pipeline re-sees a fetched index and skips the HBM fetch
        return (bh, 0, jnp.minimum(iq, _q_last_block(len_ref[bh // hkv], bq)),
                0)

    def kv_index(bh, iq, ik, pos_ref, len_ref):
        i = bh // hkv
        first, last = _kv_bounds(pos_ref[i], len_ref[i], iq, bq=bq, bkv=bkv,
                                 nk=nk, window=window)
        return (bh, jnp.clip(ik, first, last), 0)

    return q_index, kv_index


def update_prefill_index_maps(*, bq: int, bkv: int, nk: int, hkv: int,
                              rows: int, window: Optional[int]):
    """The index maps of an in-place varlen-prefill launch: q (and the
    attention output, unclamped), the stacked cache's K/V read, the chunk's
    new rows (the whole padded chunk, every grid step), and the aliased
    cache output. The cache is laid out (n_layers*rows, Lk, D): row-head bh
    of layer i is row i*rows + bh, the layer riding scalar prefetch after
    the positions and lengths. The read clamps exactly as
    `prefill_index_maps`; the output walks `_write_blocks`."""
    def q_index(bh, iq, ik, pos_ref, len_ref, layer_ref):
        return (bh, 0, jnp.minimum(iq, _q_last_block(len_ref[bh // hkv], bq)),
                0)

    def o_index(bh, iq, ik, pos_ref, len_ref, layer_ref):
        return (bh, 0, iq, 0)

    def new_index(bh, iq, ik, pos_ref, len_ref, layer_ref):
        return (bh, 0, 0)

    def kv_index(bh, iq, ik, pos_ref, len_ref, layer_ref):
        i = bh // hkv
        first, last = _kv_bounds(pos_ref[i], len_ref[i], iq, bq=bq, bkv=bkv,
                                 nk=nk, window=window)
        return (layer_ref[0] * rows + bh, jnp.clip(ik, first, last), 0)

    def out_kv_index(bh, iq, ik, pos_ref, len_ref, layer_ref):
        i = bh // hkv
        block, _ = _write_blocks(pos_ref[i], len_ref[i], iq, ik, bq=bq,
                                 bkv=bkv, nk=nk)
        return (layer_ref[0] * rows + bh, block, 0)

    return q_index, o_index, new_index, kv_index, out_kv_index


def paged_prefill_index_maps(*, bq: int, bs: int, nblk: int, hkv: int,
                             window: Optional[int]):
    """Index maps of a PAGED varlen-prefill launch: the same per-(row,
    q-block) pruning as `prefill_index_maps`, then logical KV block `lb`
    indirects to physical pool block `table[b, lb]` (pool laid out
    (P*Hkv, bs, D); head h of block p is row p*hkv + h). The clamp runs
    before the lookup, so only owned table entries are read."""
    def q_index(bh, iq, ik, pos_ref, len_ref, tbl_ref):
        return (bh, 0, jnp.minimum(iq, _q_last_block(len_ref[bh // hkv], bq)),
                0)

    def kv_index(bh, iq, ik, pos_ref, len_ref, tbl_ref):
        i = bh // hkv
        first, last = _kv_bounds(pos_ref[i], len_ref[i], iq, bq=bq, bkv=bs,
                                 nk=nblk, window=window)
        return (tbl_ref[i, jnp.clip(ik, first, last)] * hkv + bh % hkv, 0, 0)

    return q_index, kv_index


def _paged_launch(kernel, q, pool_arrays, pos, lens, table, *, bq, interpret,
                  window, softcap, scale, lq_real):
    """pallas_call assembly for the paged variants. pool_arrays are
    (P, Hkv, bs, last) block pools; `table` (B, nblk) int32 rides scalar
    prefetch with pos/lengths so the K/V index maps can indirect."""
    b, hq, lq, d = q.shape
    hkv, bs = pool_arrays[0].shape[1:3]
    group = hq // hkv
    nblk = table.shape[1]
    nq = lq // bq

    qr = q.reshape(b, hkv, group, lq, d).reshape(b * hkv, group, lq, d)
    kvr = [a.reshape(a.shape[0] * hkv, bs, a.shape[-1]) for a in pool_arrays]

    q_index, kv_index = paged_prefill_index_maps(bq=bq, bs=bs, nblk=nblk,
                                                 hkv=hkv, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b * hkv, nq, nblk),
        in_specs=[pl.BlockSpec((1, group, bq, d), q_index)] +
                 [pl.BlockSpec((1, bs, a.shape[-1]), kv_index)
                  for a in kvr],
        out_specs=[pl.BlockSpec((1, group, bq, d),
                                lambda bh, iq, ik, pos_ref, len_ref, tbl_ref:
                                (bh, 0, iq, 0))],
        scratch_shapes=[
            pltpu.VMEM((group * bq, 1), jnp.float32),
            pltpu.VMEM((group * bq, 1), jnp.float32),
            pltpu.VMEM((group * bq, d), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(kernel, debug_visits=False, scale=scale,
                          window=window, softcap=softcap, bq=bq, group=group,
                          hkv=hkv, bkv=bs, nk=nblk, lk_real=nblk * bs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b * hkv, group, lq, d), q.dtype)],
        interpret=interpret,
    )(pos, lens, table, qr, *kvr)
    out = outs[0].reshape(b, hkv, group, lq, d).reshape(b, hq, lq, d)
    return out[:, :, :lq_real]                    # drop the bq-pad tail


def _paged_dense_kernel(pos_ref, len_ref, tbl_ref, q_ref, k_ref, v_ref,
                        o_ref, *rest, **kw):
    # the table steers the index maps only; the body's logical-position math
    # (kpos = ik*bs + iota) is exactly the dense kernel's
    _dense_kernel(pos_ref, len_ref, q_ref, k_ref, v_ref, o_ref, *rest, **kw)


def _paged_quant_kernel(pos_ref, len_ref, tbl_ref, q_ref, kc_ref, ks_ref,
                        vc_ref, vs_ref, o_ref, *rest, **kw):
    _quant_kernel(pos_ref, len_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                  o_ref, *rest, **kw)


def flash_prefill_paged_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                               table: jax.Array, pos, lengths=None,
                               window: Optional[int] = None,
                               softcap: Optional[float] = None,
                               scale: Optional[float] = None, bq: int = 32,
                               interpret: Optional[bool] = None):
    """Paged varlen prefill. q: (B, Hq, Lq, D) right-padded chunk; k/v:
    (P, Hkv, bs, D) BLOCK POOLS; table: (B, nblk) int32 block map. Block
    size bs doubles as the KV tile, so a paged launch at bs == bkv visits
    the same logical blocks with the same masks as the dense kernel."""
    lq_real = q.shape[2]
    q, pos, lens, bq, interpret = _prep(q, pos, lengths, bq, interpret)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _paged_launch(_paged_dense_kernel, q, [k, v], pos, lens,
                         table.astype(jnp.int32), bq=bq, interpret=interpret,
                         window=window, softcap=softcap, scale=scale,
                         lq_real=lq_real)


def flash_prefill_paged_quant_pallas(q: jax.Array, k_codes: jax.Array,
                                     k_scale: jax.Array, v_codes: jax.Array,
                                     v_scale: jax.Array, *, table: jax.Array,
                                     pos, lengths=None,
                                     window: Optional[int] = None,
                                     softcap: Optional[float] = None,
                                     scale: Optional[float] = None,
                                     bq: int = 32,
                                     interpret: Optional[bool] = None):
    """Paged int8-KV prefill: codes (P, Hkv, bs, D) int8 + pow2 scales
    (P, Hkv, bs, 1) f32 pools, dequantized block-by-block in VMEM."""
    lq_real = q.shape[2]
    q, pos, lens, bq, interpret = _prep(q, pos, lengths, bq, interpret)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    kernel = functools.partial(_paged_quant_kernel, cast_dtype=q.dtype)
    return _paged_launch(kernel, q, [k_codes, k_scale, v_codes, v_scale],
                         pos, lens, table.astype(jnp.int32), bq=bq,
                         interpret=interpret, window=window, softcap=softcap,
                         scale=scale, lq_real=lq_real)


def _launch(kernel, q, kv_arrays, pos, lens, *, bq, bkv, interpret,
            debug_visits, window, softcap, scale, lk_real, lq_real):
    """Shared pallas_call assembly for the dense and quantized variants.

    kv_arrays: (B, Hkv, Lk_padded, last) arrays sharing the KV index map
    (codes last=D, scales last=1)."""
    b, hq, lq, d = q.shape
    hkv = kv_arrays[0].shape[1]
    group = hq // hkv
    lk = kv_arrays[0].shape[2]
    nq, nk = lq // bq, lk // bkv

    # pack the GQA group into the q tile: head h = kv*group + g, so the
    # reshape groups each kv-head's query heads contiguously and a
    # (1, group, bq, d) block packs to a (group*bq, d) MXU operand
    qr = q.reshape(b, hkv, group, lq, d).reshape(b * hkv, group, lq, d)
    kvr = [a.reshape(b * hkv, lk, a.shape[-1]) for a in kv_arrays]

    q_index, kv_index = prefill_index_maps(bq=bq, bkv=bkv, nk=nk, hkv=hkv,
                                           window=window)

    out_shape = [jax.ShapeDtypeStruct((b * hkv, group, lq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, group, bq, d),
                              lambda bh, iq, ik, pos_ref, len_ref:
                              (bh, 0, iq, 0))]
    if debug_visits:
        out_shape.append(jax.ShapeDtypeStruct((b * hkv, nq, nk), jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1, nk),
                                      lambda bh, iq, ik, pos_ref, len_ref:
                                      (bh, iq, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hkv, nq, nk),
        in_specs=[pl.BlockSpec((1, group, bq, d), q_index)] +
                 [pl.BlockSpec((1, bkv, a.shape[-1]), kv_index)
                  for a in kvr],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((group * bq, 1), jnp.float32),
            pltpu.VMEM((group * bq, 1), jnp.float32),
            pltpu.VMEM((group * bq, d), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(kernel, debug_visits=debug_visits, scale=scale,
                          window=window, softcap=softcap, bq=bq, group=group,
                          hkv=hkv, bkv=bkv, nk=nk, lk_real=lk_real),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(pos, lens, qr, *kvr)
    out = outs[0].reshape(b, hkv, group, lq, d).reshape(b, hq, lq, d)
    out = out[:, :, :lq_real]                     # drop the bq-pad tail
    return (out, outs[1]) if debug_visits else out


def _update_launch(q, k, v, k_new, v_new, pos, lens, layer, *, bq, bkv,
                   interpret, window, softcap, scale, lq_real):
    """pallas_call assembly for the in-place variant: k/v are the stacked
    (n_layers, B, Hkv, Lk, D) caches, aliased to the second and third
    outputs; q and k_new/v_new are padded to the q tile."""
    b, hq, lq, d = q.shape
    n, _, hkv, lk, _ = k.shape
    bkv = min(bkv, lk)          # the aliased cache cannot be padded
    group = hq // hkv
    rows = b * hkv
    nq, nk = lq // bq, -(-lk // bkv)

    qr = q.reshape(b, hkv, group, lq, d).reshape(rows, group, lq, d)
    kr, vr = (a.reshape(n * rows, lk, d) for a in (k, v))
    knr, vnr = (pad_to(a.astype(k.dtype), bq, 2).reshape(rows, lq, d)
                for a in (k_new, v_new))

    q_index, o_index, new_index, kv_index, out_kv_index = \
        update_prefill_index_maps(bq=bq, bkv=bkv, nk=nk, hkv=hkv, rows=rows,
                                  window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, nq, nk),
        in_specs=[pl.BlockSpec((1, group, bq, d), q_index),
                  pl.BlockSpec((1, bkv, d), kv_index),
                  pl.BlockSpec((1, bkv, d), kv_index),
                  pl.BlockSpec((1, lq, d), new_index),
                  pl.BlockSpec((1, lq, d), new_index)],
        out_specs=[pl.BlockSpec((1, group, bq, d), o_index),
                   pl.BlockSpec((1, bkv, d), out_kv_index),
                   pl.BlockSpec((1, bkv, d), out_kv_index)],
        scratch_shapes=[
            pltpu.VMEM((group * bq, 1), jnp.float32),
            pltpu.VMEM((group * bq, 1), jnp.float32),
            pltpu.VMEM((group * bq, d), jnp.float32),
        ],
    )
    out, kr, vr = pl.pallas_call(
        functools.partial(_update_kernel, scale=scale, window=window,
                          softcap=softcap, bq=bq, group=group, hkv=hkv,
                          bkv=bkv, nk=nk, lk_real=lk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, group, lq, d), q.dtype),
                   jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                   jax.ShapeDtypeStruct(vr.shape, vr.dtype)],
        # operands: pos, lengths, layer, q, k, v, k_new, v_new
        input_output_aliases={4: 1, 5: 2},
        interpret=interpret,
    )(pos, lens, layer, qr, kr, vr, knr, vnr)
    out = out.reshape(b, hkv, group, lq, d).reshape(b, hq, lq, d)
    return out[:, :, :lq_real], kr.reshape(k.shape), vr.reshape(v.shape)


def _prep(q, pos, lengths, bq: int, interpret):
    """Resolve interpret/bq, pad Lq to a bq multiple, build (B,) vectors."""
    if interpret is None:
        interpret = interpret_mode()
    b, _, lq, _ = q.shape
    bq = max(1, min(bq, lq))
    return (pad_to(q, bq, 2), as_row_vector(pos, b),
            as_row_vector(lengths, b, fill=lq), bq, interpret)


def flash_prefill_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         pos, lengths=None, window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         scale: Optional[float] = None, bq: int = 32,
                         bkv: int = 128, interpret: Optional[bool] = None,
                         debug_visits: bool = False,
                         k_new: Optional[jax.Array] = None,
                         v_new: Optional[jax.Array] = None, layer=0):
    """q: (B, Hq, Lq, D) right-padded prompt chunk; k, v: (B, Hkv, Lk, D)
    cache (the chunk's keys already written at pos[b]..pos[b]+lengths[b]-1).

    pos: per-row (B,) cache position (or scalar, broadcast): row b's query i
    sits at absolute position pos[b] + i. lengths: per-row (B,) VALID query
    count (None = all Lq valid): rows attend causally only within their own
    prompt; queries at i >= lengths[b] return zeros and their q-blocks /
    KV-blocks are pruned, never fetched.

    debug_visits=True additionally returns a (B*Hkv, nq, nk) int32 map of
    (q-block, KV-block) pairs whose compute actually ran — the pruning
    evidence used by tests and benchmarks (interpret/debug use).

    In place: with k_new/v_new (B, Hkv, Lq, D), the chunk's keys and values
    are NOT yet in the cache: k and v are the stacked (n_layers, B, Hkv,
    Lk, D) caches, and row b's first lengths[b] new rows, rounded to the
    cache dtype, are written at pos[b].. of layer `layer` and attended.
    Pad rows, and rows of a row with lengths[b] == 0, are never written.
    Returns (out, k, v), k and v updated in place.
    """
    lq_real = q.shape[2]
    q, pos, lens, bq, interpret = _prep(q, pos, lengths, bq, interpret)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if k_new is not None:
        assert not debug_visits, "the in-place launch has no visit map"
        return _update_launch(
            q, k, v, k_new, v_new, pos, lens,
            jnp.asarray(layer, jnp.int32).reshape(1), bq=bq, bkv=bkv,
            interpret=interpret, window=window, softcap=softcap, scale=scale,
            lq_real=lq_real)
    lk_real = k.shape[2]
    k, v = pad_to(k, bkv, 2), pad_to(v, bkv, 2)
    return _launch(_dense_kernel, q, [k, v], pos, lens, bq=bq, bkv=bkv,
                   interpret=interpret, debug_visits=debug_visits,
                   window=window, softcap=softcap, scale=scale,
                   lk_real=lk_real, lq_real=lq_real)


def flash_prefill_quant_pallas(q: jax.Array, k_codes: jax.Array,
                               k_scale: jax.Array, v_codes: jax.Array,
                               v_scale: jax.Array, *, pos, lengths=None,
                               window: Optional[int] = None,
                               softcap: Optional[float] = None,
                               scale: Optional[float] = None, bq: int = 32,
                               bkv: int = 128,
                               interpret: Optional[bool] = None,
                               debug_visits: bool = False):
    """Fused int8-KV prefill: codes (B, Hkv, Lk, D) int8 + per-position pow2
    scales (B, Hkv, Lk, 1) f32, dequantized block-by-block in VMEM."""
    lq_real = q.shape[2]
    q, pos, lens, bq, interpret = _prep(q, pos, lengths, bq, interpret)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    lk_real = k_codes.shape[2]
    arrays = [pad_to(a, bkv, 2)
              for a in (k_codes, k_scale, v_codes, v_scale)]
    kernel = functools.partial(_quant_kernel, cast_dtype=q.dtype)
    return _launch(kernel, q, arrays, pos, lens, bq=bq, bkv=bkv,
                   interpret=interpret, debug_visits=debug_visits,
                   window=window, softcap=softcap, scale=scale,
                   lk_real=lk_real, lq_real=lq_real)


def prefill_block_visits(pos, lengths, lq: int, lk: int, *, bq: int = 32,
                         bkv: int = 128, window: Optional[int] = None):
    """Expected (visited, total) (q-block, KV-block) pair counts per kv-head
    row for a varlen prefill launch — what `debug_visits` measures, available
    without running it. `total` counts the unpruned grid (every row doing
    every q-block against every KV-block of the padded chunk/cache)."""
    import numpy as np
    pos = np.asarray(pos, np.int64).reshape(-1)
    lens = np.asarray(lengths, np.int64).reshape(-1)
    bq = max(1, min(bq, lq))
    nq = -(-max(lq, 1) // bq)
    nk = -(-max(lk, 1) // bkv)
    visited = 0
    for start, ln in zip(pos, lens):
        for iq in range(nq):
            qlo = iq * bq
            if qlo >= ln:
                continue
            qhi = min(qlo + bq, ln) - 1
            last = min((start + qhi) // bkv, nk - 1)
            first = 0 if window is None \
                else max(start + qlo - (window - 1), 0) // bkv
            visited += int(last - min(first, last) + 1)
    return visited, int(pos.shape[0] * nq * nk)
