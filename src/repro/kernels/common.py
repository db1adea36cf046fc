"""Shared helpers for the Pallas kernel layer.

The kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling, MXU-aligned
tiles); on a CPU backend they run with interpret=True and are validated
against the pure-jnp oracles in each kernel's ref.py. Backend choice lives in
`repro.api.ExecutionPolicy`: backend="auto" runs the kernels on a TPU
backend and the pure-JAX paths everywhere else (so the multi-pod dry-run on
the CPU backend lowers the XLA paths).
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

__all__ = ["ceil_div", "pad_to", "interpret_mode", "interpret_override",
           "decode_fp_code", "encode_fp_code", "MXU_LANE", "dtype_sublane"]

MXU_LANE = 128          # lane (minor-most) tile quantum on TPU


def dtype_sublane(dtype) -> int:
    """Sublane quantum for a dtype on TPU (8 for f32, 16 bf16, 32 int8/fp8)."""
    itemsize = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x: jax.Array, multiple: int, axis: int) -> jax.Array:
    """Zero-pad `axis` up to the next multiple."""
    size = x.shape[axis]
    target = ceil_div(size, multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------
# Interpret-mode override (thread-local so tests can flip it safely)
# ---------------------------------------------------------------------------
_state = threading.local()


def interpret_mode() -> bool:
    """interpret=True everywhere except a real TPU backend (unless an
    ExecutionPolicy.interpret override is installed)."""
    override = getattr(_state, "interpret", None)
    if override is not None:
        return override
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def interpret_override(value: bool):
    """Force interpret mode on/off while tracing (repro.api wires
    ExecutionPolicy.interpret through here; the policy rides the jit cache
    key, so the override stays consistent with retracing)."""
    prev = getattr(_state, "interpret", None)
    _state.interpret = value
    try:
        yield
    finally:
        _state.interpret = prev


# ---------------------------------------------------------------------------
# In-kernel fp code decode/encode (pure jnp -> usable inside Pallas bodies).
# These mirror core.formats decode/encode but avoid ldexp (exp2 vectorizes
# better on the VPU) — exact for the narrow formats involved.
# ---------------------------------------------------------------------------

def decode_fp_code(code: jax.Array, ebits: int, mbits: int, bias: int) -> jax.Array:
    code = code.astype(jnp.int32)
    m_mask = (1 << mbits) - 1
    m = code & m_mask
    e = (code >> mbits) & ((1 << ebits) - 1)
    s = (code >> (ebits + mbits)) & 1
    normal = e > 0
    sig = jnp.where(normal, (1 << mbits) + m, m).astype(jnp.float32)
    exp = (jnp.where(normal, e, 1) - bias - mbits).astype(jnp.float32)
    val = sig * jnp.exp2(exp)
    return jnp.where(s == 1, -val, val)


def encode_fp_code(x: jax.Array, ebits: int, mbits: int, bias: int) -> jax.Array:
    """RNE-encode f32 -> fp code (saturating). Mirrors formats.encode."""
    x = x.astype(jnp.float32)
    a = jnp.abs(x)
    sgn = jnp.signbit(x).astype(jnp.int32)
    emin = 1 - bias
    emax = (1 << ebits) - 1 - bias
    max_finite = (2.0 - 2.0 ** (-mbits)) * 2.0 ** emax
    _, e2 = jnp.frexp(jnp.maximum(a, 2.0 ** (emin - mbits)))
    ebit = e2 - 1
    eff = jnp.maximum(ebit, emin)
    step = (eff - mbits).astype(jnp.float32)
    q = jnp.round(a * jnp.exp2(-step)) * jnp.exp2(step)
    q = jnp.minimum(q, max_finite)
    # re-derive exponent after rounding (may cross a binade)
    _, e2q = jnp.frexp(jnp.maximum(q, 2.0 ** (emin - mbits)))
    ebq = jnp.maximum(e2q - 1, emin)
    is_normal = q >= 2.0 ** emin
    e_code = jnp.where(is_normal, ebq + bias, 0).astype(jnp.int32)
    m_norm = jnp.round(q * jnp.exp2(-(ebq - mbits).astype(jnp.float32))) - (1 << mbits)
    m_sub = jnp.round(q * jnp.exp2(jnp.float32(-(emin - mbits))))
    m_code = jnp.where(is_normal, m_norm, m_sub).astype(jnp.int32)
    code = (sgn << (ebits + mbits)) | (e_code << mbits) | m_code
    return jnp.where(a == 0, sgn << (ebits + mbits), code)
