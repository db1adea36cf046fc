"""Functional NN layers (pure JAX, pytree params).

Every Linear can route through the AIO quantized-matmul plane (fake-quant in
training, code-domain in serving) — the paper's multi-format support as a
first-class model feature. With `QuantPolicy.resident` weights additionally
become a *residency* format: `quantize_params` (models/transformer.py)
converts each Linear's weight into a `formats.QuantWeight` (packed codes +
per-output-channel pow2 scales) once, and `linear` dispatches those through
`api.ops.matmul_codes` so no dense weight is materialized in HBM. Norm
variants cover the assigned archs: RMSNorm (llama-family), LayerNorm
(whisper), non-parametric LN (olmo-1b).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import formats as F
from ..dist.sharding import constrain, ctx_dp_axes

__all__ = ["QuantPolicy", "linear_init", "linear", "embedding_init", "embedding",
           "rmsnorm_init", "rmsnorm", "layernorm_init", "layernorm",
           "nonparam_layernorm", "rope", "mlp_init", "mlp", "norm_init",
           "apply_norm"]


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Which AIO format each tensor class runs in (paper Table II formats).

    resident: weights live as packed codes (`formats.QuantWeight`, built by
    `transformer.quantize_params`) instead of being fake-quantized from a
    dense f32 copy on every call — int4 residency is 8x less HBM weight
    traffic than f32. Linears whose params were not converted (e.g. a
    recurrent block outside the pass's coverage) still fall back to the
    fake-quant plane under `weights`, so greedy outputs stay byte-identical
    to the non-resident path.
    """
    activations: str = "none"      # none | bf16 | fp8a | fp8b | int8 | int4
    weights: str = "none"
    resident: bool = False

    @property
    def active(self) -> bool:
        return (self.activations != "none" or self.weights != "none"
                or self.resident)


def _maybe_quant(x: jax.Array, fmt_name: str) -> jax.Array:
    if fmt_name in ("none", "bf16"):
        return x
    # per-tensor pow2 scale: hardware folds it into the programmable bias
    fmt = F.REGISTRY[fmt_name]
    scale = F.pow2_scale(jax.lax.stop_gradient(x), fmt)
    return F.fake_quant(x / scale, fmt_name) * scale


def _maybe_quant_weight(w: jax.Array, fmt_name: str) -> jax.Array:
    """Weight fake-quant with PER-OUTPUT-CHANNEL pow2 scales (axis=-2 is the
    contraction axis of a (..., K, N) weight) — the same scale geometry the
    resident codes use, so `dequantize_weight(quantize_weight(w, f))` equals
    this bitwise and the two paths produce byte-identical logits."""
    if fmt_name in ("none", "bf16"):
        return w
    fmt = F.REGISTRY[fmt_name]
    scale = F.pow2_scale(jax.lax.stop_gradient(w), fmt, axis=-2)
    return F.fake_quant(w / scale, fmt_name) * scale


# ----------------------------------------------------------------- linear
def linear_init(key, d_in: int, d_out: int, bias: bool = False,
                dtype=jnp.float32, scale: Optional[float] = None):
    if scale is None:
        scale = d_in ** -0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x: jax.Array, policy: QuantPolicy = QuantPolicy()) -> jax.Array:
    w = p["w"]
    if isinstance(w, F.QuantWeight):
        # resident codes: the weight never exists dense — the matmul_codes
        # op decodes tiles in VMEM (pallas) or dequantizes at dispatch (ref)
        from ..api import ops as aio_ops        # deferred: api ships no models
        x = _maybe_quant(x, policy.activations)
        with jax.named_scope("linear"):
            y = aio_ops.matmul_codes(x, w).astype(x.dtype)
    else:
        if policy.active:
            x = _maybe_quant(x, policy.activations)
            with jax.named_scope("weight_cast"):
                w = _maybe_quant_weight(w, policy.weights)
        with jax.named_scope("linear"):
            y = jnp.einsum("...d,df->...f", x, w,
                           preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ----------------------------------------------------------------- embedding
def embedding_init(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embedding(p, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


# ----------------------------------------------------------------- norms
def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * p["g"]).astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(p, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"] + p["b"]).astype(x.dtype)


def nonparam_layernorm(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """OLMo's non-parametric LayerNorm: no learnable gain/bias."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def norm_init(kind: str, d: int, dtype=jnp.float32):
    if kind == "rmsnorm":
        return rmsnorm_init(d, dtype)
    if kind == "layernorm":
        return layernorm_init(d, dtype)
    if kind == "nonparam_ln":
        return {}
    raise ValueError(kind)


def apply_norm(kind: str, p, x: jax.Array) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(p, x)
    if kind == "layernorm":
        return layernorm(p, x)
    if kind == "nonparam_ln":
        return nonparam_layernorm(x)
    raise ValueError(kind)


# ----------------------------------------------------------------- rope
def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding. x: (..., L, D) with D even; positions: (L,) or (B, L)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., L, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast over head dim: x (..., H, L, D) vs ang (..., L, half)
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- MLP
def mlp_init(key, d_model: int, d_ff: int, kind: str = "swiglu",
             dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"gate": linear_init(ks[0], d_model, d_ff, dtype=dtype),
                "up": linear_init(ks[1], d_model, d_ff, dtype=dtype),
                "down": linear_init(ks[2], d_ff, d_model, dtype=dtype)}
    if kind == "gelu":
        return {"fc1": linear_init(ks[0], d_model, d_ff, bias=True, dtype=dtype),
                "fc2": linear_init(ks[1], d_ff, d_model, bias=True, dtype=dtype)}
    raise ValueError(kind)


def _tp(x, *spec):
    """Megatron-style TP constraint against the ambient mesh (no-op without
    one). Keeping the residual stream model-replicated and the ff/head dim
    model-sharded turns GSPMD's per-linear activation all-reduces into ONE
    all-reduce per block — §Perf iteration 1."""
    dp = ctx_dp_axes()
    if not dp:
        return x
    full = (dp,) + spec if len(spec) == x.ndim - 1 else spec
    return constrain(x, *full)


def mlp(p, x: jax.Array, kind: str = "swiglu",
        policy: QuantPolicy = QuantPolicy()) -> jax.Array:
    # column-parallel up/gate (ff sharded), row-parallel down whose output
    # REDUCE-SCATTERS onto the sequence-sharded residual (sequence
    # parallelism, Korthikanti et al.) — one shared all-gather on entry, one
    # reduce-scatter on exit, both bf16, instead of per-linear f32 gathers.
    if kind == "swiglu":
        h = jax.nn.silu(_tp(linear(p["gate"], x, policy), None, "model")) * \
            _tp(linear(p["up"], x, policy), None, "model")
        return _tp(linear(p["down"], h, policy), "model", None)
    if kind == "geglu":
        h = jax.nn.gelu(_tp(linear(p["gate"], x, policy), None, "model")) * \
            _tp(linear(p["up"], x, policy), None, "model")
        return _tp(linear(p["down"], h, policy), "model", None)
    if kind == "gelu":
        h = jax.nn.gelu(_tp(linear(p["fc1"], x, policy), None, "model"))
        return _tp(linear(p["fc2"], h, policy), "model", None)
    raise ValueError(kind)
