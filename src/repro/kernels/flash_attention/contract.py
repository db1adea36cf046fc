"""Launch contracts for the three flash-attention pallas impls.

These reuse the REAL index-map factories (`flash_index_maps`,
`decode_index_maps`, `prefill_index_maps`, and the in-place launches'
`update_decode_index_maps` / `update_prefill_index_maps`) — the GQA head
mapping and the per-row block-pruning clamps are exactly the functions a
production launch installs, evaluated here out-of-trace over concrete
(pos, lengths, layer) vectors. The decode/prefill clamps are load-bearing:
an off-by-one in `_block_bounds` or `_kv_bounds` is an out-of-bounds DMA on
hardware, which is what the KC102 sweep exists to catch. The in-place
launches add the layer offset into the stacked cache and the output that
writes the frontier block(s) back; their KB4xx body proofs cover that
write.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ...api.policy import ExecutionPolicy
from ...api.registry import BlockContract, LaunchContract, register_contract
from ..common import ceil_div
from .decode import (decode_index_maps, flash_decode_paged_pallas,
                     flash_decode_paged_quant_pallas, flash_decode_pallas,
                     flash_decode_quant_pallas, paged_decode_index_maps,
                     update_decode_index_maps)
from .kernel import flash_attention_pallas, flash_index_maps
from .prefill import (flash_prefill_paged_pallas,
                      flash_prefill_paged_quant_pallas, flash_prefill_pallas,
                      flash_prefill_quant_pallas, paged_prefill_index_maps,
                      prefill_index_maps, update_prefill_index_maps)

__all__ = ["attention_contract", "decode_contract", "prefill_contract"]

_BF16 = 2


def _kv_blocks(b, hkv, lk_pad, bkv, d, kv_index, *, quant):
    """K/V operand blocks: dense (k, v) or quantized (codes + scale) x2."""
    if not quant:
        return [
            BlockContract("k", (b * hkv, lk_pad, d), (1, bkv, d), kv_index,
                          dtype_bytes=_BF16),
            BlockContract("v", (b * hkv, lk_pad, d), (1, bkv, d), kv_index,
                          dtype_bytes=_BF16),
        ]
    blocks = []
    for name in ("k", "v"):
        blocks.append(BlockContract(f"{name}_codes", (b * hkv, lk_pad, d),
                                    (1, bkv, d), kv_index, dtype_bytes=1,
                                    quant="int8"))
        blocks.append(BlockContract(f"{name}_scale", (b * hkv, lk_pad, 1),
                                    (1, bkv, 1), kv_index,
                                    scale_for=f"{name}_codes"))
    return blocks


# --------------------------------------------------------------------------
# attention / pallas — the full-sequence flash kernel (fixed 128x128 tiles)
# --------------------------------------------------------------------------

_FLASH_CASES = (
    {"b": 1, "hq": 4, "hkv": 2, "lq": 256, "lk": 300, "d": 64},
    {"b": 2, "hq": 2, "hkv": 2, "lq": 128, "lk": 128, "d": 128},
)


@register_contract("attention", "pallas", cases=_FLASH_CASES)
def attention_contract(case: dict, policy: ExecutionPolicy) -> LaunchContract:
    b, hq, hkv = case["b"], case["hq"], case["hkv"]
    lq, lk, d = case["lq"], case["lk"], case["d"]
    bq = bk = 128                     # the impl pins both (no policy fields)
    lk_pad = ceil_div(lk, bk) * bk
    q_index, kv_index = flash_index_maps(hq=hq, hkv=hkv)

    def body():
        return flash_attention_pallas(
            jnp.zeros((b, hq, lq, d), jnp.bfloat16),
            jnp.zeros((b, hkv, lk, d), jnp.bfloat16),
            jnp.zeros((b, hkv, lk, d), jnp.bfloat16))

    return LaunchContract(
        grid=(b * hq, lq // bq, lk_pad // bk),
        blocks=(
            BlockContract("q", (b * hq, lq, d), (1, bq, d), q_index,
                          dtype_bytes=_BF16),
            BlockContract("k", (b * hkv, lk_pad, d), (1, bk, d), kv_index,
                          dtype_bytes=_BF16),
            BlockContract("v", (b * hkv, lk_pad, d), (1, bk, d), kv_index,
                          dtype_bytes=_BF16),
            # the KV loop (grid dim 2) is the flash accumulation dim: every
            # KV block revisits the same (head, q-block) output tile
            BlockContract("out", (b * hq, lq, d), (1, bq, d), q_index,
                          dtype_bytes=_BF16, is_output=True, revisits=(2,)),
        ),
        scratch_bytes=(bq + bq + bq * d) * 4,    # m, l, acc
        body=body,
    )


# --------------------------------------------------------------------------
# attention / pallas-decode — per-row positions via scalar prefetch
# --------------------------------------------------------------------------

def _paged_table(b: int, nblk: int, pool: int) -> np.ndarray:
    """A deterministic scattered-but-valid block table: rows interleave the
    pool so the checker proves in-bounds for NON-identity maps too."""
    return np.asarray([[(i * nblk + j) * 7 % pool for j in range(nblk)]
                       for i in range(b)], np.int32)


_DECODE_CASES = (
    {"b": 3, "hq": 4, "hkv": 2, "lq": 1, "lk": 640, "d": 64,
     "pos": (0, 37, 639), "window": None, "quant": False},
    {"b": 3, "hq": 4, "hkv": 2, "lq": 1, "lk": 640, "d": 64,
     "pos": (0, 37, 639), "window": 64, "quant": False},
    {"b": 2, "hq": 8, "hkv": 2, "lq": 4, "lk": 512, "d": 64,
     "pos": (12, 500), "window": None, "quant": True},
    # paged: the pool is (P, Hkv, bs, D), the KV tile IS the block size, and
    # the index map indirects through the scalar-prefetched block table
    {"b": 3, "hq": 4, "hkv": 2, "lq": 1, "d": 64, "paged": True,
     "bs": 16, "nblk": 8, "pool": 26, "pos": (0, 37, 127), "window": None,
     "quant": False},
    {"b": 2, "hq": 8, "hkv": 2, "lq": 4, "d": 64, "paged": True,
     "bs": 16, "nblk": 8, "pool": 18, "pos": (12, 124), "window": None,
     "quant": True},
    # in place: a stacked 3-layer cache, layer 2 (the last) read and
    # written; a row sitting the step out (lengths 0) and the cache's end
    {"b": 3, "hq": 4, "hkv": 2, "lq": 1, "lk": 640, "d": 64, "inplace": True,
     "layers": 3, "layer": 2, "pos": (0, 37, 639), "lens": (1, 0, 1),
     "window": None, "quant": False},
    {"b": 3, "hq": 4, "hkv": 2, "lq": 1, "lk": 600, "d": 64, "inplace": True,
     "layers": 3, "layer": 0, "pos": (0, 130, 599), "lens": (1, 1, 1),
     "window": 64, "quant": False},
)


def _stacked_cache_blocks(rows, lk, bkv, d, kv_index, out_kv_index, *,
                          revisits):
    """The in-place launches' stacked-cache operands: K/V read through
    `kv_index`, and the aliased K/V outputs that write the frontier
    block(s) back through `out_kv_index` (both over the
    (n_layers*rows, Lk, D) cache; an unaligned Lk's last block is masked)."""
    tail = lk % bkv != 0
    shape = (rows, lk, d)
    return ([BlockContract(n, shape, (1, bkv, d), kv_index,
                           dtype_bytes=_BF16, masked_tail=tail)
             for n in ("k", "v")],
            [BlockContract(n, shape, (1, bkv, d), out_kv_index,
                           dtype_bytes=_BF16, masked_tail=tail,
                           is_output=True, revisits=revisits)
             for n in ("k_out", "v_out")])


@register_contract("attention", "pallas-decode", cases=_DECODE_CASES,
                   sweep_fields=("bkv",))
def decode_contract(case: dict, policy: ExecutionPolicy) -> LaunchContract:
    if case.get("paged"):
        return _paged_decode_contract(case)
    if case.get("inplace"):
        return _inplace_decode_contract(case, policy)
    b, hq, hkv = case["b"], case["hq"], case["hkv"]
    lq, lk, d = case["lq"], case["lk"], case["d"]
    bkv = policy.bkv
    gl = (hq // hkv) * lq                       # GQA group packed into q
    lk_pad = ceil_div(lk, bkv) * bkv
    pos = np.asarray(case["pos"], np.int32)
    q_index, kv_index = decode_index_maps(lq=lq, hkv=hkv, bkv=bkv,
                                          window=case["window"])
    blocks = [BlockContract("q", (b * hkv, gl, d), (1, gl, d), q_index,
                            dtype_bytes=_BF16)]
    blocks += _kv_blocks(b, hkv, lk_pad, bkv, d, kv_index,
                         quant=case["quant"])
    # the KV loop (grid dim 1) accumulates online-softmax state in scratch
    # and revisits the row's single output tile every block
    blocks.append(BlockContract("out", (b * hkv, gl, d), (1, gl, d), q_index,
                                dtype_bytes=_BF16, is_output=True,
                                revisits=(1,)))

    def body():
        q = jnp.zeros((b, hq, lq, d), jnp.bfloat16)
        if case["quant"]:
            codes = jnp.zeros((b, hkv, lk, d), jnp.int8)
            scl = jnp.zeros((b, hkv, lk, 1), jnp.float32)
            return flash_decode_quant_pallas(
                q, codes, scl, codes, scl, pos=jnp.asarray(pos),
                window=case["window"], bkv=bkv)
        kv = jnp.zeros((b, hkv, lk, d), jnp.bfloat16)
        return flash_decode_pallas(q, kv, kv, pos=jnp.asarray(pos),
                                   window=case["window"], bkv=bkv)

    return LaunchContract(
        grid=(b * hkv, lk_pad // bkv),
        blocks=tuple(blocks),
        num_scalar_prefetch=1,
        scalars=(pos,),
        scratch_bytes=(gl + gl + gl * d) * 4,
        body=body,
    )


def _inplace_decode_contract(case: dict,
                             policy: ExecutionPolicy) -> LaunchContract:
    """The in-place decode launch: grid (row-head, KV block) over layer
    `layer` of a stacked cache; the K/V outputs stay on each row's frontier
    block across the KV loop (grid dim 1) and write it back once."""
    b, hq, hkv = case["b"], case["hq"], case["hkv"]
    lk, d, n = case["lk"], case["d"], case["layers"]
    bkv = min(policy.bkv, lk)                   # _update_launch's rule
    group, rows, nk = hq // hkv, b * hkv, ceil_div(lk, bkv)
    pos = np.asarray(case["pos"], np.int32)
    lens = np.asarray(case["lens"], np.int32)
    layer = np.asarray([case["layer"]], np.int32)
    q_index, kv_index, out_kv_index = update_decode_index_maps(
        hkv=hkv, bkv=bkv, nk=nk, rows=rows, window=case["window"])
    kv_in, kv_out = _stacked_cache_blocks(n * rows, lk, bkv, d, kv_index,
                                          out_kv_index, revisits=(1,))
    blocks = [BlockContract("q", (rows, group, d), (1, group, d), q_index,
                            dtype_bytes=_BF16)]
    blocks += kv_in
    blocks += [BlockContract(nm, (rows, 1, d), (1, 1, d), q_index,
                             dtype_bytes=_BF16) for nm in ("k_new", "v_new")]
    blocks.append(BlockContract("out", (rows, group, d), (1, group, d),
                                q_index, dtype_bytes=_BF16, is_output=True,
                                revisits=(1,)))
    blocks += kv_out

    def body():
        q = jnp.zeros((b, hq, 1, d), jnp.bfloat16)
        kv = jnp.zeros((n, b, hkv, lk, d), jnp.bfloat16)
        new = jnp.zeros((b, hkv, 1, d), jnp.bfloat16)
        return flash_decode_pallas(q, kv, kv, pos=jnp.asarray(pos),
                                   window=case["window"], bkv=bkv,
                                   k_new=new, v_new=new,
                                   layer=jnp.asarray(case["layer"]),
                                   lengths=jnp.asarray(lens))

    return LaunchContract(
        grid=(rows, nk),
        blocks=tuple(blocks),
        num_scalar_prefetch=3,
        scalars=(pos, lens, layer),
        scratch_bytes=(group + group + group * d) * 4,
        body=body,
    )


def _paged_decode_contract(case: dict) -> LaunchContract:
    """The paged decode launch: grid walks (row-head, logical block); the
    K/V operands are the (P*Hkv, bs, D)-reshaped pools and their index map
    indirects through the prefetched (B, nblk) table — the in-bounds proof
    must hold THROUGH the indirection (every table entry < P). The KV tile
    is pinned to the pool block size, not policy.bkv."""
    b, hq, hkv = case["b"], case["hq"], case["hkv"]
    lq, d = case["lq"], case["d"]
    bs, nblk, pool = case["bs"], case["nblk"], case["pool"]
    gl = (hq // hkv) * lq
    pos = np.asarray(case["pos"], np.int32)
    table = _paged_table(b, nblk, pool)
    q_index, kv_index = paged_decode_index_maps(lq=lq, hkv=hkv, bs=bs,
                                                window=case["window"])
    blocks = [BlockContract("q", (b * hkv, gl, d), (1, gl, d), q_index,
                            dtype_bytes=_BF16)]
    blocks += _kv_blocks(pool, hkv, bs, bs, d, kv_index, quant=case["quant"])
    blocks.append(BlockContract("out", (b * hkv, gl, d), (1, gl, d), q_index,
                                dtype_bytes=_BF16, is_output=True,
                                revisits=(1,)))

    def body():
        q = jnp.zeros((b, hq, lq, d), jnp.bfloat16)
        jt, jp = jnp.asarray(table), jnp.asarray(pos)
        if case["quant"]:
            codes = jnp.zeros((pool, hkv, bs, d), jnp.int8)
            scl = jnp.zeros((pool, hkv, bs, 1), jnp.float32)
            return flash_decode_paged_quant_pallas(
                q, codes, scl, codes, scl, table=jt, pos=jp,
                window=case["window"])
        kv = jnp.zeros((pool, hkv, bs, d), jnp.bfloat16)
        return flash_decode_paged_pallas(q, kv, kv, table=jt, pos=jp,
                                         window=case["window"])

    return LaunchContract(
        grid=(b * hkv, nblk),
        blocks=tuple(blocks),
        num_scalar_prefetch=2,
        scalars=(pos, table),
        scratch_bytes=(gl + gl + gl * d) * 4,
        body=body,
    )


# --------------------------------------------------------------------------
# attention / pallas-prefill — per-row positions AND lengths prefetched
# --------------------------------------------------------------------------

_PREFILL_CASES = (
    {"b": 3, "hq": 4, "hkv": 2, "lq": 64, "lk": 384, "d": 64,
     "pos": (0, 37, 256), "lens": (3, 64, 17), "window": None,
     "quant": False},
    {"b": 3, "hq": 4, "hkv": 2, "lq": 64, "lk": 384, "d": 64,
     "pos": (0, 37, 256), "lens": (3, 64, 17), "window": 64, "quant": False},
    {"b": 2, "hq": 8, "hkv": 2, "lq": 48, "lk": 256, "d": 64,
     "pos": (128, 0), "lens": (48, 1), "window": None, "quant": True},
    # paged: pool-shaped K/V, table-indirected index maps, KV tile == bs
    {"b": 3, "hq": 4, "hkv": 2, "lq": 32, "d": 64, "paged": True,
     "bs": 16, "nblk": 8, "pool": 26, "pos": (0, 37, 70),
     "lens": (3, 32, 17), "window": None, "quant": False},
    {"b": 2, "hq": 8, "hkv": 2, "lq": 48, "d": 64, "paged": True,
     "bs": 16, "nblk": 8, "pool": 18, "pos": (80, 0), "lens": (48, 1),
     "window": None, "quant": True},
    # in place over a stacked 3-layer cache: chunks that cross a block
    # boundary, fill the cache's last block, sit the step out, or end in
    # a final partial chunk
    {"b": 4, "hq": 4, "hkv": 2, "lq": 64, "lk": 384, "d": 64,
     "inplace": True, "layers": 3, "layer": 2, "pos": (0, 100, 320, 200),
     "lens": (64, 40, 64, 0), "window": None, "quant": False},
    {"b": 3, "hq": 4, "hkv": 2, "lq": 32, "lk": 300, "d": 64,
     "inplace": True, "layers": 2, "layer": 1, "pos": (250, 120, 0),
     "lens": (32, 9, 1), "window": 64, "quant": False},
)


@register_contract("attention", "pallas-prefill", cases=_PREFILL_CASES,
                   sweep_fields=("bq", "bkv"))
def prefill_contract(case: dict, policy: ExecutionPolicy) -> LaunchContract:
    if case.get("paged"):
        return _paged_prefill_contract(case, policy)
    if case.get("inplace"):
        return _inplace_prefill_contract(case, policy)
    b, hq, hkv = case["b"], case["hq"], case["hkv"]
    lq, lk, d = case["lq"], case["lk"], case["d"]
    bq = max(1, min(policy.bq, lq))             # _prep's resolution rule
    bkv = policy.bkv
    group = hq // hkv
    lq_pad = ceil_div(lq, bq) * bq
    lk_pad = ceil_div(lk, bkv) * bkv
    nk = lk_pad // bkv
    pos = np.asarray(case["pos"], np.int32)
    lens = np.asarray(case["lens"], np.int32)
    q_index, kv_index = prefill_index_maps(bq=bq, bkv=bkv, nk=nk, hkv=hkv,
                                           window=case["window"])
    blocks = [BlockContract("q", (b * hkv, group, lq_pad, d),
                            (1, group, bq, d), q_index, dtype_bytes=_BF16)]
    blocks += _kv_blocks(b, hkv, lk_pad, bkv, d, kv_index,
                         quant=case["quant"])
    # the KV loop (grid dim 2) revisits each (row, q-block) output tile —
    # the online-softmax accumulation dim
    blocks.append(BlockContract(
        "out", (b * hkv, group, lq_pad, d), (1, group, bq, d),
        lambda bh, iq, ik, pos_ref, len_ref: (bh, 0, iq, 0),
        dtype_bytes=_BF16, is_output=True, revisits=(2,)))

    def body():
        q = jnp.zeros((b, hq, lq, d), jnp.bfloat16)
        jpos, jlens = jnp.asarray(pos), jnp.asarray(lens)
        if case["quant"]:
            codes = jnp.zeros((b, hkv, lk, d), jnp.int8)
            scl = jnp.zeros((b, hkv, lk, 1), jnp.float32)
            return flash_prefill_quant_pallas(
                q, codes, scl, codes, scl, pos=jpos, lengths=jlens,
                window=case["window"], bq=policy.bq, bkv=bkv)
        kv = jnp.zeros((b, hkv, lk, d), jnp.bfloat16)
        return flash_prefill_pallas(q, kv, kv, pos=jpos, lengths=jlens,
                                    window=case["window"], bq=policy.bq,
                                    bkv=bkv)

    return LaunchContract(
        grid=(b * hkv, lq_pad // bq, nk),
        blocks=tuple(blocks),
        num_scalar_prefetch=2,
        scalars=(pos, lens),
        scratch_bytes=(group * bq * 2 + group * bq * d) * 4,
        body=body,
    )


def _inplace_prefill_contract(case: dict,
                              policy: ExecutionPolicy) -> LaunchContract:
    """The in-place varlen-prefill launch over layer `layer` of a stacked
    cache: the chunk's new rows are one whole block read at every grid
    step; the K/V outputs walk `_write_blocks` (the frontier block(s),
    revisited along the q-block and KV loops, grid dims 1 and 2)."""
    b, hq, hkv = case["b"], case["hq"], case["hkv"]
    lq, lk, d, n = case["lq"], case["lk"], case["d"], case["layers"]
    bq = max(1, min(policy.bq, lq))             # _prep's resolution rule
    bkv = min(policy.bkv, lk)                   # _update_launch's rule
    group, rows = hq // hkv, b * hkv
    lq_pad = ceil_div(lq, bq) * bq
    nk = ceil_div(lk, bkv)
    pos = np.asarray(case["pos"], np.int32)
    lens = np.asarray(case["lens"], np.int32)
    layer = np.asarray([case["layer"]], np.int32)
    q_index, o_index, new_index, kv_index, out_kv_index = \
        update_prefill_index_maps(bq=bq, bkv=bkv, nk=nk, hkv=hkv, rows=rows,
                                  window=case["window"])
    kv_in, kv_out = _stacked_cache_blocks(n * rows, lk, bkv, d, kv_index,
                                          out_kv_index, revisits=(1, 2))
    blocks = [BlockContract("q", (rows, group, lq_pad, d), (1, group, bq, d),
                            q_index, dtype_bytes=_BF16)]
    blocks += kv_in
    blocks += [BlockContract(nm, (rows, lq_pad, d), (1, lq_pad, d),
                             new_index, dtype_bytes=_BF16)
               for nm in ("k_new", "v_new")]
    blocks.append(BlockContract("out", (rows, group, lq_pad, d),
                                (1, group, bq, d), o_index, dtype_bytes=_BF16,
                                is_output=True, revisits=(2,)))
    blocks += kv_out

    def body():
        q = jnp.zeros((b, hq, lq, d), jnp.bfloat16)
        kv = jnp.zeros((n, b, hkv, lk, d), jnp.bfloat16)
        new = jnp.zeros((b, hkv, lq, d), jnp.bfloat16)
        return flash_prefill_pallas(q, kv, kv, pos=jnp.asarray(pos),
                                    lengths=jnp.asarray(lens),
                                    window=case["window"], bq=policy.bq,
                                    bkv=bkv, k_new=new, v_new=new,
                                    layer=jnp.asarray(case["layer"]))

    return LaunchContract(
        grid=(rows, lq_pad // bq, nk),
        blocks=tuple(blocks),
        num_scalar_prefetch=3,
        scalars=(pos, lens, layer),
        scratch_bytes=(group * bq * 2 + group * bq * d) * 4,
        body=body,
    )


def _paged_prefill_contract(case: dict,
                            policy: ExecutionPolicy) -> LaunchContract:
    """The paged varlen-prefill launch: same (row-head, q-block, KV-block)
    grid walk as the dense contract, K/V operands swapped for the
    (P*Hkv, bs, D) pools with table-indirected index maps. bq still comes
    from the policy; the KV tile is the pool block size."""
    b, hq, hkv = case["b"], case["hq"], case["hkv"]
    lq, d = case["lq"], case["d"]
    bs, nblk, pool = case["bs"], case["nblk"], case["pool"]
    bq = max(1, min(policy.bq, lq))             # _prep's resolution rule
    group = hq // hkv
    lq_pad = ceil_div(lq, bq) * bq
    pos = np.asarray(case["pos"], np.int32)
    lens = np.asarray(case["lens"], np.int32)
    table = _paged_table(b, nblk, pool)
    q_index, kv_index = paged_prefill_index_maps(bq=bq, bs=bs, nblk=nblk,
                                                 hkv=hkv,
                                                 window=case["window"])
    blocks = [BlockContract("q", (b * hkv, group, lq_pad, d),
                            (1, group, bq, d), q_index, dtype_bytes=_BF16)]
    blocks += _kv_blocks(pool, hkv, bs, bs, d, kv_index, quant=case["quant"])
    blocks.append(BlockContract(
        "out", (b * hkv, group, lq_pad, d), (1, group, bq, d),
        lambda bh, iq, ik, pos_ref, len_ref, tbl_ref: (bh, 0, iq, 0),
        dtype_bytes=_BF16, is_output=True, revisits=(2,)))

    def body():
        q = jnp.zeros((b, hq, lq, d), jnp.bfloat16)
        jp, jl = jnp.asarray(pos), jnp.asarray(lens)
        jt = jnp.asarray(table)
        if case["quant"]:
            codes = jnp.zeros((pool, hkv, bs, d), jnp.int8)
            scl = jnp.zeros((pool, hkv, bs, 1), jnp.float32)
            return flash_prefill_paged_quant_pallas(
                q, codes, scl, codes, scl, table=jt, pos=jp, lengths=jl,
                window=case["window"], bq=policy.bq)
        kv = jnp.zeros((pool, hkv, bs, d), jnp.bfloat16)
        return flash_prefill_paged_pallas(q, kv, kv, table=jt, pos=jp,
                                          lengths=jl, window=case["window"],
                                          bq=policy.bq)

    return LaunchContract(
        grid=(b * hkv, lq_pad // bq, nblk),
        blocks=tuple(blocks),
        num_scalar_prefetch=3,
        scalars=(pos, lens, table),
        scratch_bytes=(group * bq * 2 + group * bq * d) * 4,
        body=body,
    )
