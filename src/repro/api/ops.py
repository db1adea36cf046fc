"""The public op surface: one call, many formats, one policy object.

Every op resolves the active ExecutionPolicy (innermost `repro.api.policy`
context, overridden by any per-call keywords), maps it to a registry
implementation key, and dispatches. Resolution happens eagerly in Python —
the chosen implementation sees a concrete, hashable policy it can treat as a
static jit argument, so at THIS layer backend/format changes always retrace
instead of reusing a stale compiled path. The policy is reduced to the
fields each op actually consumes before it becomes a jit key, so unrelated
overrides (e.g. attention's `chunk`) never recompile matmuls.

Caveat (inherited from any Python-level config): a CALLER-level `jax.jit`
around code that calls these ops bakes the ambient policy in at its own
trace time — the caller's cache key cannot see the thread-local. Pin one
policy per traced program (as ServingEngine does via its `policy=`
argument) or pass `policy=` explicitly so it participates in your own
static args.

    from repro import api

    y = api.ops.matmul(x, w)                          # default policy
    with api.policy(format="int8", backend="ref"):
        y = api.ops.matmul(x, w)                      # int8 reference path
        a = api.ops.attention(q, k, v)                # same policy object
    y = api.ops.matmul(x, w, format="int4")           # per-call override
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .policy import ExecutionPolicy, current_policy
from .registry import registry

__all__ = ["matmul", "matmul_codes", "attention", "attention_route",
           "depthwise_conv", "grouped_matmul", "quantize",
           "morphable_multi_gemm", "backend_from_prefer_pallas"]


def backend_from_prefer_pallas(prefer_pallas: Optional[bool]) -> Optional[str]:
    """Map the legacy tri-state kwarg onto a backend override (None = defer)."""
    if prefer_pallas is None:
        return None
    return "pallas" if prefer_pallas else "ref"


def _resolve(policy: Optional[ExecutionPolicy], **overrides) -> ExecutionPolicy:
    base = policy if policy is not None else current_policy()
    return base.override(**overrides)


# Fields each op's implementations actually consume. Dispatch reduces the
# resolved policy to these before calling the impl, so two policies that
# differ only in fields an op never reads share one jit cache entry.
_OP_FIELDS = {
    "matmul": ("format", "bm", "bn", "bk", "out_dtype", "interpret"),
    # the format plane comes from the QuantWeight itself, not the policy
    "matmul_codes": ("bm", "bn", "bk", "out_dtype", "interpret"),
    "quantize": ("format", "bm", "bn", "interpret"),
    "depthwise_conv": ("bh", "bc", "interpret"),
    "grouped_matmul": ("bm", "bn", "bk", "out_dtype", "interpret"),
    "attention": ("chunk", "bkv", "bq", "interpret"),
}


def _canonical(pol: ExecutionPolicy, op_name: str) -> ExecutionPolicy:
    fields = _OP_FIELDS.get(op_name)
    if fields is None:
        return pol
    return ExecutionPolicy(**{f: getattr(pol, f) for f in fields})


def _interpret_ctx(pol: ExecutionPolicy):
    if pol.interpret is None:
        return contextlib.nullcontext()
    from ..kernels import common            # deferred: kernels import the api
    return common.interpret_override(pol.interpret)


def _dispatch(op_name: str, impl: str, pol: ExecutionPolicy, *args, **kwargs):
    fn = registry.lookup(op_name, impl)
    with _interpret_ctx(pol):
        return fn(*args, policy=_canonical(pol, op_name), **kwargs)


# =============================================================================
# Ops
# =============================================================================

def matmul(x: jax.Array, w: jax.Array, *, format: Optional[str] = None,
           backend: Optional[str] = None, out_dtype: Any = None,
           bm: Optional[int] = None, bn: Optional[int] = None,
           bk: Optional[int] = None, interpret: Optional[bool] = None,
           policy: Optional[ExecutionPolicy] = None) -> jax.Array:
    """Quantize (M,K) x (K,N) operands to the policy format and multiply."""
    pol = _resolve(policy, format=format, backend=backend, out_dtype=out_dtype,
                   bm=bm, bn=bn, bk=bk, interpret=interpret)
    return _dispatch("matmul", pol.impl(), pol, x, w)


def matmul_codes(x: jax.Array, wq, *, backend: Optional[str] = None,
                 out_dtype: Any = None, bm: Optional[int] = None,
                 bn: Optional[int] = None, bk: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 policy: Optional[ExecutionPolicy] = None) -> jax.Array:
    """Matmul against a RESIDENT quantized weight (`formats.QuantWeight`).

    x: (..., K) activations; wq: pre-packed weight codes + per-output-channel
    pow2 scales, quantized ONCE by `transformer.quantize_params`. The
    quantize-operands stage of `matmul` is skipped for the weight side — the
    pallas impl unpacks int4 / decodes fp8 tiles in VMEM and folds the scales
    into the tile write; the ref impl dequantizes at dispatch (byte-identical
    to the per-channel fake-quant dense path). The weight's format rides in
    `wq.fmt`, so the policy's `format` field is ignored here.
    """
    if x.shape[-1] != wq.k:
        raise ValueError(f"activation K {x.shape[-1]} != resident weight K "
                         f"{wq.k} (format {wq.fmt!r})")
    pol = _resolve(policy, backend=backend, out_dtype=out_dtype,
                   bm=bm, bn=bn, bk=bk, interpret=interpret)
    return _dispatch("matmul_codes", pol.impl(), pol, x, wq)


# Longest query the flash-decode kernel takes on the legacy scalar-offset
# cache-shaped route; vector-offset multi-token chunks go to the varlen
# prefill kernel instead (see attention_route).
DECODE_MAX_LQ = 8


def attention_route(*, lq: int, lk: Optional[int] = None, causal: bool = True,
                    offset_ndim: int = 0, quantized: bool = False,
                    backend: Optional[str] = None,
                    policy: Optional[ExecutionPolicy] = None) -> str:
    """Which attention impl a call with this shape dispatches to.

    This IS the dispatch rule `attention` uses (not a parallel re-statement):
    under a pallas backend, causal attention OVER A CACHE routes to the
    serving kernels — multi-token (Lq > 1) per-row-offset chunks (the
    engine's chunked admission prefill, dense or int8 KV) to
    "pallas-prefill", and single-token decode steps (plus legacy
    scalar-offset short queries) to "pallas-decode"; 128-aligned
    scalar-offset full-sequence prefill routes to the "pallas" flash kernel;
    everything else (and every shape under backend="ref", or "auto" off a
    TPU backend) falls back to "ref". Cache-shaped means lk > lq or a
    per-row offset vector (which only caches produce): the serving kernels
    are forward-only (no VJP), and plain short self-attention (lk == lq,
    scalar offset — e.g. a tiny training forward) must stay on the
    differentiable ref path. Exposed so serving benchmarks/engines can
    report the path their decode steps and prefill chunks take.
    """
    pol = _resolve(policy, backend=backend)
    if pol.use_pallas():
        cache_shaped = offset_ndim == 1 or (lk is not None and lk > lq)
        if causal and cache_shaped:
            if offset_ndim == 1 and lq > 1:
                return "pallas-prefill"
            if lq <= DECODE_MAX_LQ:
                return "pallas-decode"
        if not quantized and lq % 128 == 0 and offset_ndim == 0:
            return "pallas"
    return "ref"


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
              window: Optional[int] = None, softcap: Optional[float] = None,
              scale: Optional[float] = None, offset=0,
              lengths: Optional[jax.Array] = None,
              k_scale: Optional[jax.Array] = None,
              v_scale: Optional[jax.Array] = None,
              chunk: Optional[int] = None, bkv: Optional[int] = None,
              bq: Optional[int] = None, backend: Optional[str] = None,
              interpret: Optional[bool] = None,
              block_tables: Optional[jax.Array] = None,
              k_new: Optional[jax.Array] = None,
              v_new: Optional[jax.Array] = None, layer=None,
              policy: Optional[ExecutionPolicy] = None):
    """GQA attention. q: (B,Hq,Lq,D); k,v: (B,Hkv,Lk,D).

    offset: scalar or per-row (B,) cache position (continuous batching:
    every row sits at its own position). lengths: per-row (B,) VALID query
    count of a right-padded multi-token chunk (None = all valid) — the
    varlen prefill kernel prunes q-blocks and KV-blocks with it so work
    scales with real prompt tokens; the other impls ignore it (outputs at
    invalid positions are never consumed). k_scale/v_scale: when given, k/v
    are int8 codes with per-position pow2 scales (QuantKVCache layout) —
    dequantized inside the decode/prefill kernels' VMEM on the pallas
    routes, or up front on the others. block_tables: when given, k/v (and
    scales) are (P, Hkv, bs, .) BLOCK POOLS and block_tables is the
    (B, nblk) int32 per-row map — the serving kernels indirect through it
    via scalar prefetch, the ref path gathers `pool[table]`. See
    `attention_route` for which shapes hit "pallas" (full-sequence flash),
    "pallas-prefill" (varlen chunk prefill), "pallas-decode"
    (flash-decode), or "ref".

    k_new/v_new (B,Hkv,Lq,D): the cache is written as well as read. k and
    v are then the stacked (n_layers,B,Hkv,Lk,D) per-slot caches, `layer`
    picks the one attended, and row b's first lengths[b] new rows (all Lq
    when lengths is None) are written at offset[b].. first, rounded to the
    cache dtype. Returns (out, k, v): the serving kernels update the cache
    in place, the ref path scatters the rows. offset is per-row here.
    """
    pol = _resolve(policy, backend=backend, chunk=chunk, bkv=bkv, bq=bq,
                   interpret=interpret)
    if k_new is not None:
        lk = k.shape[3]
        offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32),
                                  (q.shape[0],))
    elif block_tables is None:
        lk = k.shape[2]
    else:
        lk = block_tables.shape[1] * k.shape[2]
    impl = attention_route(lq=q.shape[2], lk=lk, causal=causal,
                           offset_ndim=jnp.ndim(offset),
                           quantized=k_scale is not None, policy=pol)
    if block_tables is not None and impl == "pallas":
        impl = "ref"    # no paged route on the full-sequence kernel
    update = {} if k_new is None else \
        {"k_new": k_new, "v_new": v_new, "layer": layer}
    return _dispatch("attention", impl, pol, q, k, v, causal=causal,
                     window=window, softcap=softcap, scale=scale,
                     offset=offset, lengths=lengths, k_scale=k_scale,
                     v_scale=v_scale, block_tables=block_tables, **update)


def depthwise_conv(x: jax.Array, filt: jax.Array, *, bh: Optional[int] = None,
                   bc: Optional[int] = None, backend: Optional[str] = None,
                   interpret: Optional[bool] = None,
                   policy: Optional[ExecutionPolicy] = None) -> jax.Array:
    """x: (N, H, W, C); filt: (kh, kw, C); stride-1 SAME depthwise conv."""
    pol = _resolve(policy, bh=bh, bc=bc, backend=backend, interpret=interpret)
    return _dispatch("depthwise_conv", pol.impl(), pol, x, filt)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: Sequence[int], *,
                   bm: Optional[int] = None, bn: Optional[int] = None,
                   bk: Optional[int] = None, out_dtype: Any = None,
                   backend: Optional[str] = None,
                   interpret: Optional[bool] = None,
                   policy: Optional[ExecutionPolicy] = None) -> jax.Array:
    """x (T,K) rows sorted by group; w (G,K,N); group_sizes sums to T."""
    pol = _resolve(policy, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                   backend=backend, interpret=interpret)
    return _dispatch("grouped_matmul", pol.impl(), pol, x, w,
                     tuple(group_sizes))


def quantize(x: jax.Array, *, format: Optional[str] = None,
             bm: Optional[int] = None, bn: Optional[int] = None,
             backend: Optional[str] = None, interpret: Optional[bool] = None,
             policy: Optional[ExecutionPolicy] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """x (M, N) -> (codes int8, per-row pow2 scale (M, 1))."""
    pol = _resolve(policy, format=format, bm=bm, bn=bn, backend=backend,
                   interpret=interpret)
    return _dispatch("quantize", pol.impl(), pol, x)


def morphable_multi_gemm(tenants, *, bm: Optional[int] = None,
                         bn: Optional[int] = None, bk: Optional[int] = None,
                         out_dtype: Any = None, backend: Optional[str] = None,
                         interpret: Optional[bool] = None,
                         policy: Optional[ExecutionPolicy] = None):
    """Run N unrelated tenant GEMMs in one grouped launch; returns
    (results, mac_utilization) — the software Fig 8/Fig 14 scenario."""
    pol = _resolve(policy, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                   backend=backend, interpret=interpret)
    from ..kernels.grouped_matmul.ops import multi_gemm_with_policy
    return multi_gemm_with_policy(tenants, pol)
