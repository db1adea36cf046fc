"""Composable transformer/SSM/MoE model assembly.

A model is a list of SEGMENTS; each segment is a repeating UNIT of block
kinds scanned `count` times (lax.scan over stacked params keeps the HLO one
unit deep regardless of depth — essential for 80-layer dry-run compiles):

    dense archs   [("dense",) x N]
    gemma2        [("dense_local", "dense_global") x N/2]
    kimi-k2       [("dense",) x 1] + [("moe",) x N-1]
    zamba2        [("mamba",)*5 + ("shared_attn",) x N/6]   (shared params!)
    xlstm         [("mlstm",)*7 + ("slstm",) x N/8]
    whisper       encoder [("enc",) x Ne] + decoder [("encdec",) x Nd]

Block kinds own their cache type; decode threads stacked caches through the
same scan. Shared-attention params (zamba2) are closed over, not scanned —
one weight copy, per-invocation KV caches, exactly the published trick.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ssm
from ..api.policy import policy as exec_policy
from ..core import formats as F
from .attention import (KVCache, PagedKVCache, PagedQuantKVCache,
                        QuantKVCache, attn_apply, attn_init,
                        cross_attn_apply, init_kv_cache, init_paged_kv_cache,
                        pool_block_values, store_pool_blocks)
from .layers import (QuantPolicy, apply_norm, embedding, embedding_init,
                     linear, linear_init, mlp, mlp_init, norm_init)
from .moe import moe_apply, moe_init

__all__ = ["ModelConfig", "init_params", "forward", "loss_fn", "decode_step",
           "init_caches", "reset_slots", "scrub_slots", "set_block_tables",
           "copy_pool_blocks", "gather_pool_blocks", "write_pool_blocks",
           "param_count", "active_param_count",
           "quantize_params", "resident_format"]

# KV-bearing cache types (positional caches with a per-row write frontier)
_KV_TYPES = (KVCache, QuantKVCache, PagedKVCache, PagedQuantKVCache)
_PAGED_TYPES = (PagedKVCache, PagedQuantKVCache)


# =============================================================================
# Config
# =============================================================================

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    norm: str = "rmsnorm"
    mlp_kind: str = "swiglu"
    qkv_bias: bool = False
    post_norm: bool = False                  # gemma2 sandwich norms
    softcap_attn: Optional[float] = None
    softcap_final: Optional[float] = None
    sliding_window: Optional[int] = None
    local_global: bool = False               # alternate local/global attention
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    n_dense_layers: int = 0
    capacity_factor: float = 1.25
    # --- SSM / hybrid ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    attn_every: int = 0                      # zamba2 shared-attn period
    slstm_every: int = 0                     # xlstm slstm period
    # --- enc-dec / frontends ---
    encoder_layers: int = 0
    cross_attention: bool = False
    frontend: Optional[str] = None           # 'audio' | 'vision' (stub inputs)
    frontend_len: int = 0                    # frames / patches per sample
    max_seq: int = 8192                      # learned-pos table size (whisper)
    learned_pos: bool = False
    # --- capability flags / policies ---
    subquadratic: bool = False               # may run long_500k
    quant: QuantPolicy = QuantPolicy()
    remat: bool = True
    kv_quant: bool = False                   # int8 KV caches (format plane)
    # scan unroll factor for the layer loop. The dry-run lowers with full
    # unroll because XLA cost_analysis counts a while-loop body ONCE — an
    # unrolled module yields exact FLOP/byte/collective totals.
    scan_unroll: int = 1

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def segments(self) -> List[Tuple[Tuple[str, ...], int]]:
        if self.family == "audio":
            return [(("encdec",), self.n_layers)]
        if self.attn_every:                                  # zamba2
            assert self.n_layers % self.attn_every == 0
            unit = ("mamba",) * (self.attn_every - 1) + ("shared_attn",)
            return [(unit, self.n_layers // self.attn_every)]
        if self.slstm_every:                                 # xlstm
            assert self.n_layers % self.slstm_every == 0
            unit = ("mlstm",) * (self.slstm_every - 1) + ("slstm",)
            return [(unit, self.n_layers // self.slstm_every)]
        if self.local_global:                                # gemma2
            assert self.n_layers % 2 == 0
            return [(("dense_local", "dense_global"), self.n_layers // 2)]
        if self.n_experts:                                   # moe
            segs = []
            if self.n_dense_layers:
                segs.append((("dense",), self.n_dense_layers))
            segs.append((("moe",), self.n_layers - self.n_dense_layers))
            return segs
        return [(("dense",), self.n_layers)]

    def block_kinds(self) -> List[str]:
        kinds = []
        for unit, n in self.segments():
            kinds.extend(list(unit) * n)
        return kinds


# =============================================================================
# Block init / apply
# =============================================================================

def _block_init(key, kind: str, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    if kind in ("dense", "dense_local", "dense_global", "moe", "enc"):
        p = {"ln1": norm_init(cfg.norm, d),
             "attn": attn_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                               cfg.qkv_bias),
             "ln2": norm_init(cfg.norm, d)}
        if cfg.post_norm:
            p["pn1"] = norm_init(cfg.norm, d)
            p["pn2"] = norm_init(cfg.norm, d)
        if kind == "moe":
            p["moe"] = moe_init(ks[1], d, cfg.d_ff, cfg.n_experts,
                                cfg.n_shared_experts)
        else:
            ff = cfg.d_ff if cfg.d_ff else 4 * d
            p["mlp"] = mlp_init(ks[1], d, ff, cfg.mlp_kind)
        return p
    if kind == "shared_attn":
        return _block_init(key, "dense", cfg)
    if kind == "mamba":
        return {"ln": norm_init(cfg.norm, d),
                "mamba": ssm.mamba_init(ks[0], d, d_state=cfg.ssm_state,
                                        expand=cfg.ssm_expand,
                                        headdim=cfg.ssm_headdim)}
    if kind == "mlstm":
        return {"ln": norm_init(cfg.norm, d),
                "mlstm": ssm.mlstm_init(ks[0], d, n_heads=cfg.n_heads)}
    if kind == "slstm":
        return {"ln": norm_init(cfg.norm, d),
                "slstm": ssm.slstm_init(ks[0], d, n_heads=cfg.n_heads)}
    if kind == "encdec":
        return {"ln1": norm_init(cfg.norm, d),
                "attn": attn_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.hd),
                "lnx": norm_init(cfg.norm, d),
                "xattn": attn_init(ks[1], d, cfg.n_heads, cfg.n_kv_heads, cfg.hd),
                "ln2": norm_init(cfg.norm, d),
                "mlp": mlp_init(ks[2], d, cfg.d_ff, cfg.mlp_kind)}
    raise ValueError(kind)


def _select_rows(new_cache, old_cache, active: jax.Array):
    """Keep new_cache rows where active (B,) is True, old rows elsewhere —
    per-slot masking for recurrent states during a padded batched prefill."""
    def sel(n, o):
        a = active.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)
    return jax.tree.map(sel, new_cache, old_cache)


def _block_apply(kind: str, p, x: jax.Array, cfg: ModelConfig, *,
                 cache=None, memory: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None,
                 lengths: Optional[jax.Array] = None):
    """Returns (x, new_cache, aux_loss).

    lengths: (B,) valid-new-token counts for cached paths (see attn_apply);
    recurrent blocks freeze state rows where lengths == 0."""
    aux = jnp.zeros((), jnp.float32)
    pol = cfg.quant
    if kind in ("dense", "dense_local", "dense_global", "moe", "enc",
                "shared_attn"):
        window = None
        if kind == "dense_local" or (kind == "dense" and cfg.sliding_window
                                     and not cfg.local_global):
            window = cfg.sliding_window
        causal = kind != "enc"
        # manual TP+SP fast path (explicit collectives; see tp_block.py) for
        # eligible dense/moe blocks without caches/quant — §Perf iterations 3/4
        if kind in ("dense", "dense_local", "dense_global", "moe") and causal:
            from .tp_block import manual_dense_block, manual_tp_ok
            if manual_tp_ok(cfg, x, cache, pol, params=p) and (
                    kind != "moe" or cfg.n_experts):
                if kind == "moe":
                    x = manual_dense_block(
                        p, x, cfg, window=window, softcap=cfg.softcap_attn,
                        post_norm=cfg.post_norm, with_mlp=False)
                    h = apply_norm(cfg.norm, p["ln2"], x)
                    h, aux = moe_apply(
                        p["moe"], h, n_experts=cfg.n_experts, top_k=cfg.top_k,
                        capacity_factor=cfg.capacity_factor, policy=pol)
                    if cfg.post_norm:
                        h = apply_norm(cfg.norm, p["pn2"], h)
                    return x + h, None, aux
                return manual_dense_block(
                    p, x, cfg, window=window, softcap=cfg.softcap_attn,
                    post_norm=cfg.post_norm), None, aux
        h = apply_norm(cfg.norm, p["ln1"], x)
        h, new_cache = attn_apply(
            p["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            causal=causal, window=window, softcap=cfg.softcap_attn,
            rope_theta=cfg.rope_theta, positions=positions, cache=cache,
            lengths=lengths, policy=pol)
        if cfg.post_norm:
            h = apply_norm(cfg.norm, p["pn1"], h)
        x = x + h
        h = apply_norm(cfg.norm, p["ln2"], x)
        if kind == "moe":
            h, aux = moe_apply(
                p["moe"], h, n_experts=cfg.n_experts, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, policy=pol)
        else:
            ff_kind = cfg.mlp_kind
            h = mlp(p["mlp"], h, ff_kind, pol)
        if cfg.post_norm:
            h = apply_norm(cfg.norm, p["pn2"], h)
        return x + h, new_cache, aux
    if kind == "mamba":
        h = apply_norm(cfg.norm, p["ln"], x)
        if cache is None:
            h, _ = ssm.mamba_apply(p["mamba"], h, d_state=cfg.ssm_state,
                                   headdim=cfg.ssm_headdim)
            return x + h, None, aux
        h, new_cache = ssm.mamba_step(p["mamba"], h, cache,
                                      d_state=cfg.ssm_state,
                                      headdim=cfg.ssm_headdim)
        if lengths is not None:
            new_cache = _select_rows(new_cache, cache, lengths > 0)
        return x + h, new_cache, aux
    if kind == "mlstm":
        h = apply_norm(cfg.norm, p["ln"], x)
        if cache is None:
            h, _ = ssm.mlstm_apply(p["mlstm"], h, n_heads=cfg.n_heads)
            return x + h, None, aux
        h, new_cache = ssm.mlstm_step(p["mlstm"], h, cache, n_heads=cfg.n_heads)
        if lengths is not None:
            new_cache = _select_rows(new_cache, cache, lengths > 0)
        return x + h, new_cache, aux
    if kind == "slstm":
        h = apply_norm(cfg.norm, p["ln"], x)
        if cache is None:
            h, _ = ssm.slstm_apply(p["slstm"], h, n_heads=cfg.n_heads)
            return x + h, None, aux
        h, new_cache = ssm.slstm_step(p["slstm"], h, cache, n_heads=cfg.n_heads)
        if lengths is not None:
            new_cache = _select_rows(new_cache, cache, lengths > 0)
        return x + h, new_cache, aux
    if kind == "encdec":
        h = apply_norm(cfg.norm, p["ln1"], x)
        h, new_cache = attn_apply(
            p["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            causal=True, rope_theta=cfg.rope_theta, positions=positions,
            cache=cache, lengths=lengths, policy=pol)
        x = x + h
        h = apply_norm(cfg.norm, p["lnx"], x)
        h = cross_attn_apply(p["xattn"], h, memory, n_heads=cfg.n_heads,
                             n_kv=cfg.n_kv_heads, policy=pol)
        x = x + h
        h = apply_norm(cfg.norm, p["ln2"], x)
        return x + mlp(p["mlp"], h, cfg.mlp_kind, pol), new_cache, aux
    raise ValueError(kind)


# =============================================================================
# Caches
# =============================================================================

def _block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                 dtype=jnp.bfloat16, paged: Optional[Tuple[int, int]] = None):
    if kind in ("dense", "dense_global", "moe", "shared_attn", "encdec",
                "dense_local"):
        if paged is not None:
            pool_blocks, block_size = paged
            nblk = -(-max_len // block_size)
            return init_paged_kv_cache(batch, cfg.n_kv_heads, pool_blocks,
                                       block_size, nblk, cfg.hd, dtype,
                                       quantized=cfg.kv_quant)
        return init_kv_cache(batch, cfg.n_kv_heads, max_len, cfg.hd, dtype,
                             quantized=cfg.kv_quant)
    if kind == "mamba":
        return ssm.mamba_cache_init(batch, cfg.d_model, d_state=cfg.ssm_state,
                                    expand=cfg.ssm_expand,
                                    headdim=cfg.ssm_headdim, dtype=dtype)
    if kind == "mlstm":
        return ssm.mlstm_cache_init(batch, cfg.d_model, n_heads=cfg.n_heads,
                                    dtype=dtype)
    if kind == "slstm":
        return ssm.slstm_cache_init(batch, cfg.d_model)
    if kind == "enc":
        return None
    raise ValueError(kind)


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16, paged: Optional[Tuple[int, int]] = None):
    """Per-segment stacked caches mirroring the stacked-params layout.

    paged: optional (pool_blocks, block_size) — KV caches become block-pool
    PagedKVCache/PagedQuantKVCache trees (recurrent states are positionless
    and keep their per-row layout either way)."""
    caches = []
    for unit, n in cfg.segments():
        seg = {}
        for j, kind in enumerate(unit):
            c = _block_cache(kind, cfg, batch, max_len, dtype, paged=paged)
            seg[f"{j}_{kind}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), c) \
                if c is not None else None
        caches.append(seg)
    return caches


def reset_slots(caches, slot_mask: jax.Array,
                new_pos: Optional[jax.Array] = None):
    """Reset cache rows (slots) where slot_mask (B,) is True to their initial
    state, leaving other rows untouched — the slot-refill primitive for
    continuous batching. KV caches only rewind pos: stale K/V rows sit beyond
    the new causal frontier, so they are invisible to attention and each slot
    is overwritten before the frontier reaches it. Recurrent states are
    re-zeroed (slstm stabilizer m to its -inf-like init).

    new_pos: optional (B,) frontier to rewind TO instead of 0 — the paged
    engine admits a request with a shared prompt prefix by pointing the
    row's block table at the shared blocks and starting it at pos ==
    shared-token count.

    Cache leaves are the stacked (n_layers, B, ...) trees from init_caches.
    """
    cache_types = _KV_TYPES + (ssm.MambaCache, ssm.MLSTMCache, ssm.SLSTMCache)
    pos_to = 0 if new_pos is None else new_pos[None, :]

    def rows(a, value):
        m = slot_mask.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(m, jnp.asarray(value, a.dtype), a)

    def reset(c):
        if isinstance(c, _KV_TYPES):
            return c._replace(pos=jnp.where(slot_mask[None, :], pos_to, c.pos))
        if isinstance(c, ssm.SLSTMCache):
            return ssm.SLSTMCache(c=rows(c.c, 0), n=rows(c.n, 0),
                                  m=rows(c.m, -1e30), h=rows(c.h, 0))
        return jax.tree.map(lambda a: rows(a, 0), c)

    return jax.tree.map(reset, caches,
                        is_leaf=lambda x: isinstance(x, cache_types))


def scrub_slots(caches, slot_mask: jax.Array):
    """`reset_slots` plus VALUE scrubbing: rows where slot_mask (B,) is True
    get their cache VALUES re-initialized (KV values and int8 codes to 0,
    quant scales to 1), not just their positions rewound.

    `reset_slots` leans on causal masking to make stale rows invisible,
    which is sound for FINITE stale values but not for non-finite ones: an
    additive attention mask turns `NaN + (-inf)` into NaN, so a poisoned
    K/V row could leak through the very mask that hides ordinary stale
    data. The serving engine's quarantine path scrubs the offending slot
    before it is ever reused; everything else keeps using the cheap
    `reset_slots`.
    """
    cache_types = _KV_TYPES + (ssm.MambaCache, ssm.MLSTMCache, ssm.SLSTMCache)

    def rows(a, value):
        m = slot_mask.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(m, jnp.asarray(value, a.dtype), a)

    def pos0(pos):
        return jnp.where(slot_mask[None, :], 0, pos)

    def paged_scrub(c):
        # Scrub every physical block REFERENCED by a scrubbed row — including
        # blocks shared with other rows (a poisoned NaN in a shared block
        # must not survive into another tenant's attention; the engine
        # quarantines + replays the co-sharing rows it finds host-side).
        n, _, nblk = c.table.shape
        pool = (c.k if isinstance(c, PagedKVCache) else c.k_codes).shape[1]
        lay = jnp.broadcast_to(jnp.arange(n)[:, None, None], c.table.shape)
        hit = jnp.broadcast_to(slot_mask[None, :, None], c.table.shape)
        bmask = jnp.zeros((n, pool), bool).at[
            lay.reshape(-1), c.table.reshape(-1)].max(hit.reshape(-1))

        def blocks(a, value):
            m = bmask.reshape(bmask.shape + (1,) * (a.ndim - 2))
            return jnp.where(m, jnp.asarray(value, a.dtype), a)

        if isinstance(c, PagedKVCache):
            return c._replace(k=blocks(c.k, 0), v=blocks(c.v, 0),
                              pos=pos0(c.pos))
        return c._replace(k_codes=blocks(c.k_codes, 0),
                          k_scale=blocks(c.k_scale, 1),
                          v_codes=blocks(c.v_codes, 0),
                          v_scale=blocks(c.v_scale, 1),
                          pos=pos0(c.pos))

    def scrub(c):
        if isinstance(c, _PAGED_TYPES):
            return paged_scrub(c)
        if isinstance(c, KVCache):
            return KVCache(k=rows(c.k, 0), v=rows(c.v, 0), pos=pos0(c.pos))
        if isinstance(c, QuantKVCache):
            return QuantKVCache(k_codes=rows(c.k_codes, 0),
                                k_scale=rows(c.k_scale, 1),
                                v_codes=rows(c.v_codes, 0),
                                v_scale=rows(c.v_scale, 1),
                                pos=pos0(c.pos))
        if isinstance(c, ssm.SLSTMCache):
            return ssm.SLSTMCache(c=rows(c.c, 0), n=rows(c.n, 0),
                                  m=rows(c.m, -1e30), h=rows(c.h, 0))
        return jax.tree.map(lambda a: rows(a, 0), c)

    return jax.tree.map(scrub, caches,
                        is_leaf=lambda x: isinstance(x, cache_types))


def set_block_tables(caches, table: jax.Array):
    """Install a host-computed (B, nblk) block table into every paged cache
    leaf (the allocator keeps one logical table; each layer's pool gets the
    same map, broadcast over the stacked leading axis)."""
    def st(c):
        if isinstance(c, _PAGED_TYPES):
            n = c.table.shape[0]
            t = jnp.broadcast_to(table.astype(jnp.int32)[None],
                                 (n,) + table.shape)
            return c._replace(table=t)
        return c

    return jax.tree.map(st, caches,
                        is_leaf=lambda x: isinstance(x, _PAGED_TYPES))


def copy_pool_blocks(caches, src: jax.Array, dst: jax.Array):
    """Copy physical pool blocks src[i] -> dst[i] in every paged cache leaf —
    the device half of copy-on-write (fork a shared block before a row
    writes into it). src/dst are fixed-width (C,) int32; entries equal to
    the pool size are padding (the read clamps, the write drops)."""
    def mv(a):                                   # (n, P, H, bs, ...)
        pool = a.shape[1]
        vals = jnp.take(a, jnp.clip(src, 0, pool - 1), axis=1)
        return a.at[:, dst].set(vals, mode="drop")

    def cp(c):
        if isinstance(c, PagedKVCache):
            return c._replace(k=mv(c.k), v=mv(c.v))
        if isinstance(c, PagedQuantKVCache):
            return c._replace(k_codes=mv(c.k_codes), k_scale=mv(c.k_scale),
                              v_codes=mv(c.v_codes), v_scale=mv(c.v_scale))
        return c

    return jax.tree.map(cp, caches,
                        is_leaf=lambda x: isinstance(x, _PAGED_TYPES))


def gather_pool_blocks(caches, ids: jax.Array):
    """Read physical pool blocks `ids` ((C,) int32) out of every paged cache
    leaf. Returns a tree shaped like `caches` with each paged leaf replaced
    by its dict of (n_layers, C, H, bs, ...) block values (non-paged leaves
    become None); `write_pool_blocks` is the exact inverse. This is the
    device half of KV swap-out: the serving engine runs it at the
    scheduler boundary — never inside the jitted step (HL206) — and moves
    the result to host memory."""
    def gather(c):
        if isinstance(c, _PAGED_TYPES):
            return pool_block_values(c, ids)
        return None

    return jax.tree.map(gather, caches,
                        is_leaf=lambda x: isinstance(x, _PAGED_TYPES))


def write_pool_blocks(caches, values, dst: jax.Array):
    """Scatter `gather_pool_blocks`-shaped block values back into the pool
    at physical blocks `dst` ((C,) int32; entries equal to the pool size are
    padding and are dropped, so a sentinel-padded fixed-width dst traces
    once — same convention as `copy_pool_blocks`). The device half of KV
    swap-in: restored bytes are exactly the gathered bytes, so a preempted
    row resumes byte-identically."""
    def put(c, vals):
        if isinstance(c, _PAGED_TYPES):
            return store_pool_blocks(c, vals, dst)
        return c

    return jax.tree.map(put, caches, values,
                        is_leaf=lambda x: isinstance(x, _PAGED_TYPES))


# =============================================================================
# Params
# =============================================================================

def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embedding_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = linear_init(keys[1], cfg.d_model, cfg.vocab,
                                        dtype=dtype)
    if cfg.learned_pos:
        params["pos"] = jax.random.normal(
            keys[2], (cfg.max_seq, cfg.d_model), dtype) * 0.01

    # encoder stack (audio family)
    if cfg.encoder_layers:
        ek = jax.random.split(keys[3], cfg.encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: _block_init(k, "enc", cfg))(ek)
        params["enc_norm"] = norm_init(cfg.norm, cfg.d_model)

    # main stack, segment by segment
    segs = []
    kidx = 4
    for unit, n in cfg.segments():
        seg = {}
        for j, kind in enumerate(unit):
            if kind == "shared_attn":
                # ONE weight copy reused across all n invocations (zamba2)
                seg[f"{j}_{kind}"] = _block_init(
                    jax.random.fold_in(keys[kidx % 8], j), kind, cfg)
            else:
                ks = jax.random.split(jax.random.fold_in(keys[kidx % 8], j), n)
                seg[f"{j}_{kind}"] = jax.vmap(
                    lambda k: _block_init(k, kind, cfg))(ks)
            kidx += 1
        segs.append(seg)
    params["segments"] = segs
    return params


# =============================================================================
# Weight residency — quantize the Linear weights ONCE, serve from codes.
# =============================================================================

# Param subtrees whose weights must stay dense. "router"/"mamba"/"mlstm"/
# "slstm"/"lm_head" linears never receive the model's QuantPolicy (raw-einsum
# consumers or policy-less call sites), so the fake-quant reference path
# leaves them dense — residency mirrors that coverage exactly. "moe" stays
# dense wholesale because the expert-parallel shard_map path addresses its
# weights by raw pytree structure; the shared expert's linears fall back to
# the fake-quant plane under `QuantPolicy.weights`, which is the SAME math —
# so resident and fake-quant serving stay byte-identical everywhere.
_RESIDENT_SKIP = ("router", "mamba", "mlstm", "slstm", "lm_head", "moe")


def quantize_params(params, fmt: str, *, skip=_RESIDENT_SKIP):
    """Convert each policy-covered Linear's `w` into a `formats.QuantWeight`
    (int4 packed two-per-byte along K, int8/fp8 codes; per-output-channel
    pow2 scales). The pass is jit-able and donation-friendly: untouched
    leaves (embeddings, norms, biases, recurrent/router weights) alias the
    input buffers, so `jax.jit(..., donate_argnums=(0,))` frees the dense
    f32 weights as the codes are built — HBM never holds both pytrees.

    Works on the stacked per-layer layout `init_params` produces: a stacked
    (n_layers, K, N) weight becomes stacked (n_layers, K', N) codes whose
    leading axis `lax.scan` slices exactly like the dense leaves;
    `forward`/`decode_step` accept the converted pytree unchanged.
    """
    if fmt not in F.RESIDENT_FORMATS:
        raise ValueError(f"resident weight format {fmt!r} not in "
                         f"{F.RESIDENT_FORMATS}")

    def walk(node, path):
        if isinstance(node, dict):
            if any(s in path for s in skip):
                return node
            if "w" in node and not isinstance(node["w"], F.QuantWeight) \
                    and getattr(node["w"], "ndim", 0) >= 2:
                out = dict(node)
                out["w"] = F.quantize_weight(node["w"], fmt)
                return out
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, path) for v in node)
        return node

    return walk(params, ())


def resident_format(params) -> Optional[str]:
    """The residency format of a param pytree (None when weights are dense)."""
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, F.QuantWeight)):
        if isinstance(leaf, F.QuantWeight):
            return leaf.fmt
    return None


# =============================================================================
# Forward
# =============================================================================

def _run_segment(seg_params, unit: Tuple[str, ...], n: int, x: jax.Array,
                 cfg: ModelConfig, memory=None, positions=None,
                 seg_caches=None, lengths=None):
    """Scan the unit n times; returns (x, new_caches, aux)."""
    scanned = {k: v for k, v in seg_params.items()
               if not k.endswith("shared_attn")}
    shared = {k: v for k, v in seg_params.items()
              if k.endswith("shared_attn")}
    caches = seg_caches or {}

    def body(carry, xs):
        h, aux = carry
        layer_params, layer_caches = xs
        new_caches = {}
        for j, kind in enumerate(unit):
            key = f"{j}_{kind}"
            p = shared[key] if key in shared else layer_params[key]
            c = layer_caches.get(key) if layer_caches else None
            h, nc, a = _block_apply(kind, p, h, cfg, cache=c, memory=memory,
                                    positions=positions, lengths=lengths)
            aux = aux + a
            if nc is not None:
                new_caches[key] = nc
        return (h, aux), new_caches

    if cfg.remat and seg_caches is None:
        body = jax.checkpoint(body)

    xs_caches = {k: v for k, v in caches.items() if v is not None}
    unroll = min(cfg.scan_unroll, n) if cfg.scan_unroll > 1 else 1
    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        ({k: v for k, v in scanned.items()}, xs_caches), unroll=unroll)
    return x, new_caches, aux


def _positions(cfg: ModelConfig, b: int, l: int, offset=0):
    return jnp.arange(l) + offset


def forward(params, tokens: jax.Array, cfg: ModelConfig, *,
            prefix_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None):
    """Full-sequence forward. tokens: (B, L) -> logits (B, L, V).

    prefix_embeds: VLM patch embeddings prepended to the token stream.
    frames: audio-family encoder inputs (B, T_enc, d_model) from the stub
    frontend. Returns (logits, aux_loss).
    """
    b, l = tokens.shape
    x = embedding(params["embed"], tokens)
    memory = None

    if cfg.family == "audio":
        assert frames is not None
        mem = frames + _sinusoid(frames.shape[1], cfg.d_model, frames.dtype)
        for i in range(cfg.encoder_layers):
            p_i = jax.tree.map(lambda a: a[i], params["encoder"])
            mem, _, _ = _block_apply("enc", p_i, mem, cfg)
        memory = apply_norm(cfg.norm, params["enc_norm"], mem)

    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)

    if cfg.learned_pos:
        x = x + params["pos"][:x.shape[1]]
    if cfg.name.startswith("gemma"):
        x = x * math.sqrt(cfg.d_model)       # gemma2 embedding scaling

    # sequence-parallel residual stream: sequence sharded over "model"
    # between blocks (no-op without a mesh / when L doesn't divide)
    from .layers import _tp
    x = _tp(x, "model", None)
    aux_total = jnp.zeros((), jnp.float32)
    for (unit, n), seg in zip(cfg.segments(), params["segments"]):
        x, _, aux = _run_segment(seg, unit, n, x, cfg, memory=memory)
        aux_total = aux_total + aux

    if prefix_embeds is not None:
        x = x[:, prefix_embeds.shape[1]:]
    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = _unembed(params, x, cfg)
    return logits, aux_total


@jax.named_scope("unembed")
def _unembed(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bld,vd->blv", x, params["embed"]["table"],
                            preferred_element_type=jnp.float32)
    else:
        logits = linear(params["lm_head"], x).astype(jnp.float32)
    if cfg.softcap_final:
        logits = cfg.softcap_final * jnp.tanh(logits / cfg.softcap_final)
    return logits


def _sinusoid(length: int, d: int, dtype):
    pos = jnp.arange(length)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / (10000.0 ** (dim / (d // 2)))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def loss_fn(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            aux_weight: float = 0.01):
    """Causal-LM cross entropy (+ MoE aux). batch: tokens, labels[, frames,
    patch_embeds]. labels = -100 masks a position out.

    The objective always traces the XLA reference ops: the Pallas kernels
    are forward-only (no VJP), and on a TPU backend `backend="auto"` would
    otherwise route 128-aligned attention to one."""
    with exec_policy(backend="ref"):
        logits, aux = forward(params, batch["tokens"], cfg,
                              prefix_embeds=batch.get("patch_embeds"),
                              frames=batch.get("frames"))
    labels = batch["labels"]
    mask = labels >= 0
    labels_safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels_safe[..., None], -1)[..., 0]
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return loss + aux_weight * aux, {"loss": loss, "aux": aux}


# =============================================================================
# Decode
# =============================================================================

def decode_step(params, caches, token: jax.Array, cfg: ModelConfig, *,
                memory: Optional[jax.Array] = None,
                lengths: Optional[jax.Array] = None):
    """One decode step. token: (B, l) -> (logits (B, l, V), new caches).
    l is usually 1; a one-shot batched prefill passes the whole (right-padded)
    prompt block with `lengths` (B,) marking each row's valid-token count —
    rows with lengths[b] == 0 keep caches and positions untouched.

    Caches carry per-row positions (KVCache.pos (B,)) / recurrent states;
    lowering this with a seq_len-sized cache is what the decode_32k/long_500k
    dry-run cells measure.
    """
    b, l = token.shape
    x = embedding(params["embed"], token)
    if cfg.learned_pos:
        # per-row position from the first attn cache (slots sit at their own
        # positions under continuous batching); clip guards rows idling past
        # the table — their logits are never consumed
        pos = _first_pos(caches)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (b,))
        idx = jnp.clip(pos[:, None] + jnp.arange(l),
                       0, params["pos"].shape[0] - 1)
        x = x + jnp.take(params["pos"], idx, axis=0)
    new_caches = []
    for (unit, n), seg, seg_c in zip(cfg.segments(), params["segments"], caches):
        x, nc, _ = _run_segment(seg, unit, n, x, cfg, memory=memory,
                                seg_caches=seg_c, lengths=lengths)
        new_caches.append(nc)
    x = apply_norm(cfg.norm, params["final_norm"], x)
    return _unembed(params, x, cfg), new_caches


def _first_pos(caches):
    """Position of the first KV cache: (B,) per-row vector from the stacked
    (n, B) leaf, or a scalar from a legacy (n,) batch-global stack."""
    for seg in caches:
        for v in seg.values():
            if isinstance(v, _KV_TYPES):
                return v.pos[0] if v.pos.ndim else v.pos
    return jnp.zeros((), jnp.int32)


# =============================================================================
# Accounting (roofline MODEL_FLOPS)
# =============================================================================

def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def active_param_count(params, cfg: ModelConfig) -> int:
    """MoE: only top_k of n_experts participate per token."""
    total = param_count(params)
    if not cfg.n_experts:
        return total
    expert_leaves = 0
    for seg in params["segments"]:
        for key, blk in seg.items():
            if "moe" in key and isinstance(blk, dict) and "moe" in blk:
                for nm in ("gate", "up", "down"):
                    expert_leaves += blk["moe"][nm].size
    inactive = expert_leaves * (1 - cfg.top_k / cfg.n_experts)
    return int(total - inactive)
