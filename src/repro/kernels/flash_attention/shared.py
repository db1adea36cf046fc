"""Helpers shared by the serving attention kernels (decode + varlen
prefill): the masked-score sentinel, per-row scalar-vector normalization,
the in-VMEM QuantKVCache dequant rounding rule, and the in-VMEM merge of a
step's new rows into a cache block.

The dequant lives here so there is exactly ONE copy of the rounding
contract (codes * scale cast through the q dtype, matching
models.attention._dq8): both kernels' fused int8-KV paths assert
bit-identity against dequantize-then-dense, and a drift between two copies
would silently break one of them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["NEG_INF", "as_row_vector", "vmem_dequant", "merge_rows",
           "zero_tail"]

NEG_INF = -1e30


def as_row_vector(x, b: int, fill: int = 0) -> jnp.ndarray:
    """Normalize a per-row scalar argument: None -> `fill`, a scalar
    broadcasts, a (B,) vector passes through."""
    if x is None:
        x = fill
    x = jnp.asarray(x, jnp.int32)
    return jnp.broadcast_to(x.reshape(-1) if x.ndim else x, (b,))


def vmem_dequant(codes_ref, scale_ref, cast_dtype) -> jnp.ndarray:
    """Dequantize a QuantKVCache block inside the kernel, rounding through
    `cast_dtype` (the q dtype) so the fused path is bit-identical to
    dequantize-in-HBM-then-dense-kernel (models.attention._dq8's rule)."""
    return (codes_ref[0].astype(jnp.float32) * scale_ref[0]) \
        .astype(cast_dtype).astype(jnp.float32)


def merge_rows(blk: jnp.ndarray, new: jnp.ndarray, row0, start,
               n) -> jnp.ndarray:
    """The cache block `blk` (bkv, D), whose first row holds position `row0`,
    with the first `n` of the new rows `new` (l, D) written at positions
    start..start+n-1; rows outside that range keep their value. Exact: one
    new row is broadcast and selected; several are picked by a one-hot
    matmul (one product of 1.0 per output element, f32 accumulation)."""
    bkv, d = blk.shape
    l = new.shape[0]
    rel = row0 - start + jax.lax.broadcasted_iota(jnp.int32, (bkv, d), 0)
    hit = (rel >= 0) & (rel < n)
    if l == 1:
        picked = jnp.broadcast_to(new, (bkv, d))
    else:
        onehot = (row0 - start
                  + jax.lax.broadcasted_iota(jnp.int32, (bkv, l), 0)
                  == jax.lax.broadcasted_iota(jnp.int32, (bkv, l), 1))
        picked = jnp.dot(onehot.astype(new.dtype), new,
                         preferred_element_type=jnp.float32)
    return jnp.where(hit, picked.astype(jnp.float32),
                     blk.astype(jnp.float32)).astype(blk.dtype)


def zero_tail(blk: jnp.ndarray, row0, length: int) -> jnp.ndarray:
    """`blk` (bkv, D) with rows at positions >= `length` zeroed: the final
    block of a cache whose length is not a multiple of the block reads
    unspecified values past the end, which a masked score does not stop
    from reaching the p @ v product as NaN."""
    pos = row0 + jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    return jnp.where(pos < length, blk, jnp.zeros_like(blk))
