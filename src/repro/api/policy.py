"""ExecutionPolicy: the one object that says how every op runs.

The paper's premise is a single substrate serving many data formats and
operation shapes; the software mirror is a single policy object carrying the
format plane (AIO format name), the backend plane (pallas kernels vs the
pure-jnp reference path), and the tiling geometry — declared once and obeyed
by every op dispatched through `repro.api.ops`.

Policies are frozen (hashable) so a resolved policy can ride through
`jax.jit(..., static_argnames=("policy",))` and participate in trace caching
correctly — the footgun the old hidden thread-local flag had when read at
trace time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp

__all__ = ["ExecutionPolicy", "policy", "current_policy", "default_policy",
           "policy_sweep", "TILE_FIELDS"]

_BACKENDS = ("auto", "pallas", "ref")
# Formats the matmul plane's kernels implement (core.formats.REGISTRY names).
_FORMATS = ("bf16", "fp8a", "fp8b", "int8", "int4", "fp16", "uint8", "uint4")


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """How ops dispatched through repro.api execute.

    format:    AIO number format for the quantized-matmul/quantize plane.
    backend:   "pallas" forces the Pallas kernels, "ref" the pure-jnp oracle,
               "auto" picks from the platform: the kernels on a TPU backend,
               the XLA reference path everywhere else.
    bm/bn/bk:  MXU tile sizes for matmul-family kernels.
    bh/bc:     height/channel tiles for the depthwise kernel.
    bkv:       KV-block length of the flash decode/prefill attention kernels.
    bq:        q-block length of the varlen flash-prefill kernel.
    chunk:     query-chunk length for the long-prefill attention path.
    out_dtype: accumulator/output dtype of matmul-family ops.
    interpret: force pallas interpret mode on (True) / off (False); None
               keeps the automatic rule (interpret everywhere but real TPU).
    """
    format: str = "bf16"
    backend: str = "auto"
    bm: int = 128
    bn: int = 128
    bk: int = 128
    bh: int = 8
    bc: int = 128
    bkv: int = 128
    bq: int = 32
    chunk: int = 1024
    out_dtype: Any = jnp.float32
    interpret: Optional[bool] = None

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {_BACKENDS}")
        if self.format not in _FORMATS:
            raise ValueError(f"format {self.format!r} not in {_FORMATS}")

    # ------------------------------------------------------------ resolution
    def use_pallas(self) -> bool:
        """Resolve the backend plane to a concrete pallas-or-not choice."""
        if self.backend == "pallas":
            return True
        if self.backend == "ref":
            return False
        return jax.default_backend() == "tpu"

    def impl(self) -> str:
        """Registry implementation key this policy selects."""
        return "pallas" if self.use_pallas() else "ref"

    def override(self, **overrides) -> "ExecutionPolicy":
        """A copy with the non-None overrides applied (per-call kwargs)."""
        effective = {k: v for k, v in overrides.items() if v is not None}
        return dataclasses.replace(self, **effective) if effective else self

    def demoted(self) -> "ExecutionPolicy":
        """The safe-route copy of this policy: backend re-pinned to "ref"
        (the pure-jnp oracle every pallas kernel is byte-identical to), all
        other planes untouched. The serving engine installs this when a
        kernel launch raises — the software analogue of reconfiguring the
        morphable array back to its safe dataflow — so every subsequent
        traced step dispatches down the reference route while formats,
        tiling and out_dtype stay exactly what the engine pinned."""
        return dataclasses.replace(self, backend="ref")


default_policy = ExecutionPolicy()

# The tiling-geometry plane of the policy: the fields kernels consume as
# BlockSpec block lengths. `repro.analysis` sweeps launch contracts over
# these; REPRESENTATIVE_TILES are the per-field values the sweep uses
# (the default plus the smaller tiles the serving/test configs exercise).
TILE_FIELDS = ("bm", "bn", "bk", "bh", "bc", "bkv", "bq")
REPRESENTATIVE_TILES = {
    "bm": (128, 64), "bn": (128, 64), "bk": (128, 64),
    "bh": (8, 4), "bc": (128, 64),
    "bkv": (128, 16), "bq": (32, 8),
}


def policy_sweep(fields, base: Optional[ExecutionPolicy] = None,
                 values: Optional[dict] = None):
    """Representative ExecutionPolicy grid over the named tile fields.

    Returns the cartesian product of per-field candidate values (from
    ``values`` or REPRESENTATIVE_TILES) applied on top of ``base`` (the
    default policy when omitted). The analyzer uses this to evaluate every
    kernel launch contract across the tiling geometries production code can
    install; tests pin the semantics.
    """
    import itertools
    base = base if base is not None else default_policy
    table = values if values is not None else REPRESENTATIVE_TILES
    fields = tuple(fields)
    for f in fields:
        if f not in TILE_FIELDS:
            raise ValueError(f"{f!r} is not a tile field {TILE_FIELDS}")
    grids = [table[f] for f in fields]
    return tuple(base.override(**dict(zip(fields, combo)))
                 for combo in itertools.product(*grids))


_state = threading.local()


def _stack():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


def current_policy() -> ExecutionPolicy:
    """The innermost installed policy (the default one outside any context)."""
    stack = _stack()
    return stack[-1] if stack else default_policy


@contextlib.contextmanager
def policy(base: Optional[ExecutionPolicy] = None,
           **overrides) -> Iterator[ExecutionPolicy]:
    """Install an ExecutionPolicy for every op inside the block.

        with repro.api.policy(format="int4", backend="ref"):
            y = repro.api.ops.matmul(x, w)        # int4, reference path

    Nests: unspecified fields inherit from the innermost enclosing policy.
    Pass an ExecutionPolicy positionally to install it verbatim (plus any
    keyword overrides on top of it).
    """
    installed = (base if base is not None else current_policy()).override(
        **overrides)
    stack = _stack()
    stack.append(installed)
    try:
        yield installed
    finally:
        stack.pop()
