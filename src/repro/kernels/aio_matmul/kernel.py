"""Pallas multi-format matmul — the MAC-array plane of the all-in-one multiplier.

One kernel body, five operating modes (bf16 / fp8a / fp8b / int8 / int4),
mirroring Fig 7's mode gating:
  * bf16  — native MXU matmul (the 8b-significand path).
  * fp8a/fp8b — codes decoded to f32 in VMEM (VPU work), MXU matmul, f32 acc.
  * int8  — integer dot with int32 accumulation (CSM-only path, Fig 7-d).
  * int4  — codes packed 2-per-byte along K; unpacked in VMEM. Packing halves
    HBM traffic and doubles effective lanes — the software realization of the
    "4 results per multiplier" throughput morph (Table III 128x128 -> 256x256).

Scaling factors are applied on the final tile write as a per-row x per-col
outer product; power-of-two scales correspond to the paper's programmable
exponent bias (no extra multipliers on hardware).

BlockSpec tiling: (bm x bk) @ (bk x bn) with a VMEM accumulator, grid
(M/bm, N/bn, K/bk), K innermost so the accumulator lives across the K loop.
Tiles are MXU-aligned (multiples of 128 in lanes; sublane quantum per dtype).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import decode_fp_code, interpret_mode
from ...core.formats import REGISTRY

__all__ = ["aio_matmul_pallas", "matmul_index_maps", "MODES"]

MODES = ("bf16", "fp8a", "fp8b", "int8", "int4")


def matmul_index_maps():
    """BlockSpec index maps of an AIO matmul launch, grid = (i, j, k).

    Module-level so the launch assembly and the `repro.analysis` contract
    checker evaluate the SAME functions.
    """
    return {
        "x": lambda i, j, k: (i, k),
        "w": lambda i, j, k: (k, j),
        "xs": lambda i, j, k: (i, 0),
        "ws": lambda i, j, k: (0, j),
        "out": lambda i, j, k: (i, j),
    }


def _mm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, mode: str,
               nsteps: int, out_dtype):
    """Grid = (i, j, k); acc_ref is VMEM scratch carried over the k loop."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if mode == "bf16":
        x = x_ref[...]
        w = w_ref[...]
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    elif mode in ("fp8a", "fp8b"):
        fmt = REGISTRY[mode]
        x = decode_fp_code(x_ref[...], fmt.ebits, fmt.mbits, fmt.bias)
        w = decode_fp_code(w_ref[...], fmt.ebits, fmt.mbits, fmt.bias)
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    elif mode == "int8":
        # int8 x int8 -> int32 is the MXU's integer path; Mosaic refuses an
        # int32 x int32 dot, so the operands stay in their stored dtype
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.int32)
    elif mode == "int4":
        # packed along K: byte b holds K=2b (low nibble) and K=2b+1 (high);
        # dot(lo,lo) covers even K, dot(hi,hi) odd K — together the full
        # contraction, with half the HBM traffic (the 4x-results morph).
        xlo, xhi = unpack_nibbles(x_ref[...])
        wlo, whi = unpack_nibbles(w_ref[...])
        acc_ref[...] += jnp.dot(xlo, wlo, preferred_element_type=jnp.int32)
        acc_ref[...] += jnp.dot(xhi, whi, preferred_element_type=jnp.int32)
    else:  # pragma: no cover
        raise ValueError(mode)

    @pl.when(pl.program_id(2) == nsteps - 1)
    def _finish():
        acc = acc_ref[...].astype(jnp.float32)
        if xs_ref is not None:
            acc = acc * xs_ref[...] * ws_ref[...]
        o_ref[...] = acc.astype(out_dtype)


def unpack_nibbles(packed):
    """Packed int4 codes (int8 bytes, two nibbles each) -> (lo, hi) int8
    operands in [-8, 7]. The packed axis is K for both layouts (x packs its
    last axis, w its first), so the even/odd split keeps the two dots'
    shapes aligned. Sign extension runs in int32 (the VPU's native width)
    and narrows back to int8 for the MXU's int8 x int8 -> int32 path."""
    p32 = packed.astype(jnp.int32)
    lo = (p32 << 28) >> 28
    hi = p32 >> 4
    return lo.astype(jnp.int8), hi.astype(jnp.int8)


def aio_matmul_pallas(x, w, x_scale: Optional[jax.Array],
                      w_scale: Optional[jax.Array], *, mode: str,
                      out_dtype=jnp.float32, bm: int = 128, bn: int = 128,
                      bk: int = 128, interpret: Optional[bool] = None):
    """x:(M,K[,/2]) w:(K[,/2],N) in mode's code dtype; scales (M,1)/(1,N) f32.

    Shapes must be pre-padded to tile multiples by ops.py. int4 mode expects
    K pre-packed (two nibbles per byte) and bk counts *packed* bytes.
    """
    if mode not in MODES:
        raise ValueError(f"mode {mode} not in {MODES}")
    if interpret is None:
        interpret = interpret_mode()
    m, kx = x.shape
    kw, n = w.shape
    assert kx == kw, (x.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and kx % bk == 0, \
        f"unpadded shapes {x.shape}x{w.shape} for tiles ({bm},{bn},{bk})"
    grid = (m // bm, n // bn, kx // bk)

    has_scale = x_scale is not None
    if has_scale:
        assert w_scale is not None
        assert x_scale.shape == (m, 1) and w_scale.shape == (1, n)

    acc_dtype = jnp.int32 if mode in ("int8", "int4") else jnp.float32
    kernel = functools.partial(_mm_kernel, mode=mode, nsteps=grid[2],
                               out_dtype=out_dtype)
    maps = matmul_index_maps()
    in_specs = [
        pl.BlockSpec((bm, bk), maps["x"]),
        pl.BlockSpec((bk, bn), maps["w"]),
    ]
    args = [x, w]
    if has_scale:
        in_specs += [pl.BlockSpec((bm, 1), maps["xs"]),
                     pl.BlockSpec((1, bn), maps["ws"])]
        args += [x_scale, w_scale]
        body = kernel
    else:
        body = lambda xr, wr, o, a: kernel(xr, wr, None, None, o, a)  # noqa: E731

    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), maps["out"]),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(*args)
