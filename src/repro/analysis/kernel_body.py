"""Kernel-body checker: abstract interpretation of Pallas kernel jaxprs.

PR 6's kernel-contract checker proves the *launch geometry* (index maps,
grids, VMEM); this checker proves properties of the kernel *bodies*. Each
LaunchContract now carries a zero-arg ``body`` thunk that assembles the
real launch on dummy operands; ``jax.make_jaxpr`` traces it (nothing
executes), the single ``pallas_call`` equation is extracted, and an
interval + taint abstract interpretation runs over the kernel jaxpr:

  KB400  ref index not provably within the block shape      (error)
  KB401  guarded ref index: the pl.when predicate interval
         does not cover the out-of-range lanes              (error)
  KB410  two grid points write the same output block along
         a grid dim not declared in ``revisits=``           (error)
  KB411  declared revisit dim with grid > 1 never revisits  (warning)
  KB420  dequantized value reaches an output store without
         a scale multiply (unscaled dequant)                (error)
  KB421  quant/scale contract declaration inconsistent
         (unknown format, dangling scale_for, scale plane
         not broadcastable onto its codes block)            (error)
  KB430  contract declares no traceable kernel body         (warning)
  KB431  body trace failed or drifted from its contract
         (grid/block-shape/operand-count mismatch)          (error)

The interpreter maps every jaxpr value to an interval [lo, hi] plus a
quantization taint (clean / scale / codes / dequant). ``program_id(i)``
seeds [0, grid[i]-1]; scalar-prefetch loads seed the min/max of the
contract's concrete scalar vectors; ``pl.when`` predicates refine
intervals inside the guarded branch by walking the predicate's def chain.
Ref reads/writes (the ``get``/``swap``/``addupdate`` state primitives)
re-materialize their NDIndexer and every scalar/slice index must prove
0 <= idx < dim. The taint lattice catches the Jack-Unit dequant contract:
a load from a ``quant=``-marked ref is CODES, int->float conversion makes
it DEQUANT, a multiply against a ``scale_for=``-marked operand clears it,
and storing a still-DEQUANT (or raw CODES) value to an output is KB420.

The race detector (KB410/411) needs no jaxpr: it replays the contract's
output index maps over the (stratified-sampled) grid and compares every
grid point against the first point that produced each output block —
complete for pairwise dim-difference containment because difference sets
against a common point union.

Known limits: ``scan``/``while`` bodies are not entered (their outputs
become unbounded, which is sound — no registered kernel loops in-body),
and bitwise shifts are unbounded (int4 nibble unpacking stays sound
because unpacked values are never used as indices).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..api.policy import ExecutionPolicy, policy_sweep
from ..api.registry import BlockContract, KernelRegistry, LaunchContract
from ..api.registry import registry as default_registry
from .findings import Report
from .format_matrix import FORMAT_MATRIX

__all__ = ["check_body", "check_kernel_bodies", "CODES"]

CHECKER = "kernel-body"

CODES = {
    "KB400": ("error", "ref index not provably within the block shape"),
    "KB401": ("error", "guarded ref index: pl.when predicate does not "
                       "cover the out-of-range lanes"),
    "KB410": ("error", "two grid points write the same output block along "
                       "an undeclared (non-revisits) grid dim"),
    "KB411": ("warning", "declared revisits= dim with grid > 1 never "
                         "revisits an output block"),
    "KB420": ("error", "dequantized/code value stored to an output without "
                       "a scale multiply"),
    "KB421": ("error", "quant/scale declaration inconsistent (unknown "
                       "format, dangling scale_for, bad scale plane)"),
    "KB430": ("warning", "launch contract declares no traceable body"),
    "KB431": ("error", "kernel body trace failed or drifted from its "
                       "contract"),
}

INF = float("inf")

# Taint lattice, ordered by badness; join = max.
CLEAN, SCALE, CODES_T, DEQ = 0, 1, 2, 3
_TAINT_NAMES = {CLEAN: "clean", SCALE: "scale", CODES_T: "codes",
                DEQ: "dequant"}


@dataclasses.dataclass(frozen=True)
class AbsVal:
    """Interval + quantization taint for one jaxpr value."""
    lo: float = -INF
    hi: float = INF
    taint: int = CLEAN

    @property
    def is_top(self) -> bool:
        return self.lo == -INF and self.hi == INF


TOP = AbsVal()


def _join(*vals: AbsVal) -> AbsVal:
    if not vals:
        return TOP
    return AbsVal(min(v.lo for v in vals), max(v.hi for v in vals),
                  max(v.taint for v in vals))


def _taint_of(*vals: AbsVal) -> int:
    return max((v.taint for v in vals), default=CLEAN)


def _mul_taint(a: AbsVal, b: AbsVal) -> int:
    """A scale multiply CLEARS codes/dequant taint — the dequant contract."""
    pair = {a.taint, b.taint}
    if SCALE in pair and (CODES_T in pair or DEQ in pair):
        return CLEAN
    return _taint_of(a, b)


def _mul_iv(a: AbsVal, b: AbsVal, taint: int) -> AbsVal:
    def m(x, y):                       # 0 * inf -> 0, not nan
        if x == 0 or y == 0:
            return 0.0
        return x * y
    prods = [m(a.lo, b.lo), m(a.lo, b.hi), m(a.hi, b.lo), m(a.hi, b.hi)]
    return AbsVal(min(prods), max(prods), taint)


def _floordiv_iv(a: AbsVal, b: AbsVal, taint: int) -> AbsVal:
    """Exact interval floor-division (the `bh // hkv` prefetch-index case)."""
    if b.lo <= 0 <= b.hi or a.is_top or b.is_top:
        return AbsVal(taint=taint)
    cands = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            if math.isfinite(x) and math.isfinite(y):
                cands.append(math.floor(x / y))
            else:
                cands.append(math.copysign(INF, x / y if y else 1.0))
    return AbsVal(min(cands), max(cands), taint)


def _mod_iv(a: AbsVal, b: AbsVal, taint: int) -> AbsVal:
    if b.lo > 0 and math.isfinite(b.hi):
        # jnp.remainder follows the divisor's sign: result in [0, b)
        return AbsVal(0.0, b.hi - 1 if b.hi == int(b.hi) else b.hi, taint)
    return AbsVal(taint=taint)


@dataclasses.dataclass
class RefInfo:
    """One kernel ref operand: identity, shape, and mutable content taint."""
    name: str
    shape: Tuple[int, ...]
    kind: str                               # prefetch | input | output | scratch
    block: Optional[BlockContract] = None
    scalars: Optional[np.ndarray] = None    # concrete prefetch operand
    taint: int = CLEAN


def _is_literal(v) -> bool:
    """jax Literals carry .val and may be unhashable — never dict keys."""
    return hasattr(v, "val")


def _literal_val(v) -> Optional[float]:
    """Concrete scalar of a Literal/const var, else None."""
    if not _is_literal(v):
        return None
    try:
        arr = np.asarray(v.val)
    except Exception:  # noqa: BLE001 — opaque literal payload
        return None
    if arr.size == 1 and np.issubdtype(arr.dtype, np.number):
        return float(arr.reshape(()))
    return None


class _Env:
    """Var -> AbsVal and Var -> RefInfo scopes (shared mutable ref table)."""

    def __init__(self):
        self.vals: Dict[Any, AbsVal] = {}
        self.refs: Dict[Any, RefInfo] = {}

    def is_ref(self, v) -> bool:
        return not _is_literal(v) and v in self.refs

    def read(self, v) -> AbsVal:
        if _is_literal(v):
            lit = _literal_val(v)
            if lit is not None:
                return AbsVal(lit, lit)
            try:                            # non-scalar literal/const array
                arr = np.asarray(v.val)
                if arr.size and np.issubdtype(arr.dtype, np.number):
                    return AbsVal(float(arr.min()), float(arr.max()))
            except Exception:  # noqa: BLE001
                pass
            return TOP
        return self.vals.get(v, TOP)

    def child(self) -> "_Env":
        env = _Env()
        env.vals = dict(self.vals)
        env.refs = self.refs                # refs are shared, taint is global
        return env


def _dtype_bounds(aval) -> AbsVal:
    dt = getattr(aval, "dtype", None)
    if dt is not None and np.issubdtype(dt, np.integer):
        info = np.iinfo(dt)
        return AbsVal(float(info.min), float(info.max))
    if dt is not None and np.issubdtype(dt, np.bool_):
        return AbsVal(0.0, 1.0)
    return TOP


class _BodyInterp:
    """One pass over a kernel jaxpr for one (contract, grid) instance."""

    def __init__(self, rep: Report, where: str, grid: Tuple[int, ...]):
        self.rep = rep
        self.where = where
        self.grid = grid
        self.reported: set = set()          # (code, ref name) dedup

    # ------------------------------------------------------------- findings
    def _oob(self, ref: RefInfo, dim: int, iv: AbsVal, lo_ok: float,
             hi_ok: float, guarded: bool):
        code = "KB401" if guarded else "KB400"
        if (code, ref.name, dim) in self.reported:
            return
        self.reported.add((code, ref.name, dim))
        guard = ("the enclosing pl.when predicate does not restrict it to"
                 if guarded else "no pl.when guard restricts it to")
        self.rep.add(code, "error", CHECKER, self.where,
                     f"ref {ref.name!r} dim {dim}: index interval "
                     f"[{iv.lo:g}, {iv.hi:g}] not provably within "
                     f"[{lo_ok:g}, {hi_ok:g}] — {guard} the block")

    # ------------------------------------------------------------- indexing
    def _check_indexers(self, ref: RefInfo, tree, dyn_invars, env: _Env,
                        guarded: bool):
        """Re-materialize the NDIndexer pytree; prove every index in-bounds."""
        import jax

        try:
            indexers = jax.tree_util.tree_unflatten(tree, tuple(dyn_invars))
        except Exception:  # noqa: BLE001 — unknown layout, stay silent
            return
        if not isinstance(indexers, (tuple, list)):
            indexers = (indexers,)
        for indexer in indexers:
            indices = getattr(indexer, "indices", None)
            if indices is None:
                continue
            for dim, (idx, n) in enumerate(zip(indices, ref.shape)):
                start = getattr(idx, "start", None)
                if start is not None:       # a Slice(start, size, stride)
                    size = getattr(idx, "size", 1)
                    stride = getattr(idx, "stride", 1) or 1
                    siv = self._as_iv(start, env)
                    if not isinstance(size, int):
                        continue            # dynamic size: geometry unknown
                    last = AbsVal(siv.lo + (size - 1) * stride,
                                  siv.hi + (size - 1) * stride)
                    if siv.lo < 0 or last.hi > n - 1:
                        self._oob(ref, dim, AbsVal(siv.lo, last.hi), 0,
                                  n - 1, guarded)
                else:                       # scalar or array index
                    iv = self._as_iv(idx, env)
                    if iv.lo < 0 or iv.hi > n - 1:
                        self._oob(ref, dim, iv, 0, n - 1, guarded)

    def _as_iv(self, idx, env: _Env) -> AbsVal:
        if isinstance(idx, (int, np.integer)):
            return AbsVal(float(idx), float(idx))
        if isinstance(idx, np.ndarray):
            return AbsVal(float(idx.min()), float(idx.max()))
        return env.read(idx)

    def _load_interval(self, ref: RefInfo, tree, dyn_invars,
                       env: _Env) -> AbsVal:
        """Value interval of a ref read (concrete for prefetch operands)."""
        if ref.scalars is not None and ref.scalars.size:
            arr = np.asarray(ref.scalars)
            import jax
            try:
                indexers = jax.tree_util.tree_unflatten(tree,
                                                        tuple(dyn_invars))
                if not isinstance(indexers, (tuple, list)):
                    indexers = (indexers,)
                indices = getattr(indexers[0], "indices", ())
                if len(indices) == arr.ndim == 1:
                    iv = self._as_iv(indices[0], env)
                    if math.isfinite(iv.lo) and math.isfinite(iv.hi):
                        lo = max(0, int(iv.lo))
                        hi = min(arr.shape[0] - 1, int(iv.hi))
                        if lo <= hi:
                            sub = arr[lo:hi + 1]
                            return AbsVal(float(sub.min()), float(sub.max()),
                                          ref.taint)
            except Exception:  # noqa: BLE001 — fall back to the full range
                pass
            return AbsVal(float(arr.min()), float(arr.max()), ref.taint)
        return dataclasses.replace(TOP, taint=ref.taint)

    # --------------------------------------------------------- cond support
    def _refine_from_pred(self, pred_var, jaxpr, env: _Env) -> Dict[Any, AbsVal]:
        """Interval tightenings that hold inside the TRUE branch of pred."""
        defs = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                defs[ov] = eqn
        out: Dict[Any, AbsVal] = {}

        def cur(v) -> AbsVal:
            if _is_literal(v):
                return env.read(v)
            return out.get(v, env.read(v))

        def visit(v):
            if _is_literal(v):
                return
            eqn = defs.get(v)
            if eqn is None:
                return
            name = eqn.primitive.name
            if name == "convert_element_type":
                visit(eqn.invars[0])
                return
            if name == "and":
                visit(eqn.invars[0])
                visit(eqn.invars[1])
                return
            if name not in ("lt", "le", "gt", "ge", "eq"):
                return
            a, b = eqn.invars
            av = cur(a)
            bv = cur(b)
            # rewrite gt/ge as lt/le with swapped sides
            if name in ("gt", "ge"):
                a, b, av, bv = b, a, bv, av
                name = "lt" if name == "gt" else "le"
            if name == "eq":
                both = AbsVal(max(av.lo, bv.lo), min(av.hi, bv.hi),
                              av.taint)
                if both.lo <= both.hi:
                    for side, t in ((a, av.taint), (b, bv.taint)):
                        if not _is_literal(side):
                            out[side] = dataclasses.replace(both, taint=t)
                return
            gap = 1.0 if name == "lt" else 0.0       # a < b  <=>  a <= b-1
            if not _is_literal(a):
                out[a] = AbsVal(av.lo, min(av.hi, bv.hi - gap), av.taint)
            if not _is_literal(b):
                out[b] = AbsVal(max(bv.lo, av.lo + gap), bv.hi, bv.taint)

        visit(pred_var)
        return {v: iv for v, iv in out.items() if iv.lo <= iv.hi}

    # ------------------------------------------------------------ the walk
    def run(self, jaxpr, env: _Env, guarded: bool):
        # _enclosing tracks the jaxpr whose def chains a cond predicate
        # refinement must walk (predicates are defined as siblings of the
        # cond equation, not inside the branch)
        saved = getattr(self, "_enclosing", None)
        self._enclosing = jaxpr
        try:
            for eqn in jaxpr.eqns:
                self.eqn(eqn, env, guarded)
        finally:
            self._enclosing = saved

    def _bind(self, eqn, env: _Env, *vals: AbsVal):
        for ov, v in zip(eqn.outvars, vals):
            env.vals[ov] = v

    def eqn(self, eqn, env: _Env, guarded: bool):  # noqa: C901 — dispatch
        name = eqn.primitive.name
        iv = [env.read(v) for v in eqn.invars
              if not env.is_ref(v)]          # value operands only

        if name == "program_id":
            ax = eqn.params["axis"]
            self._bind(eqn, env, AbsVal(0.0, float(self.grid[ax] - 1)))
        elif name == "num_programs":
            ax = eqn.params["axis"]
            g = float(self.grid[ax])
            self._bind(eqn, env, AbsVal(g, g))

        elif name in ("get", "swap", "addupdate"):
            ref = env.refs.get(eqn.invars[0])
            ndyn = {"get": 1, "swap": 2, "addupdate": 2}[name]
            dyn = eqn.invars[ndyn:]
            if ref is not None:
                self._check_indexers(ref, eqn.params.get("tree"), dyn, env,
                                     guarded)
            if name == "get":
                out = (self._load_interval(ref, eqn.params.get("tree"), dyn,
                                           env) if ref is not None else TOP)
                if out.is_top and eqn.outvars:
                    out = dataclasses.replace(
                        _dtype_bounds(eqn.outvars[0].aval), taint=out.taint)
                self._bind(eqn, env, out)
            else:
                stored = env.read(eqn.invars[1])
                if ref is not None:
                    self._store(ref, stored)
                if name == "swap" and eqn.outvars:
                    self._bind(eqn, env, dataclasses.replace(
                        _dtype_bounds(eqn.outvars[0].aval), taint=ref.taint
                        if ref is not None else CLEAN))

        elif name == "cond":
            self._cond(eqn, env, guarded)
        elif name == "jit":
            self._jit(eqn, env, guarded)
        elif name in ("custom_jvp_call", "custom_vjp_call",
                      "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"):
            closed = (eqn.params.get("call_jaxpr")
                      or eqn.params.get("fun_jaxpr"))
            if closed is not None:
                self._inline(closed, eqn, env, guarded)
            else:
                self._bind(eqn, env, *[dataclasses.replace(
                    TOP, taint=_taint_of(*iv))] * len(eqn.outvars))

        elif name == "add":
            self._bind(eqn, env, AbsVal(iv[0].lo + iv[1].lo,
                                        iv[0].hi + iv[1].hi,
                                        _taint_of(*iv)))
        elif name == "sub":
            self._bind(eqn, env, AbsVal(iv[0].lo - iv[1].hi,
                                        iv[0].hi - iv[1].lo,
                                        _taint_of(*iv)))
        elif name == "mul":
            self._bind(eqn, env, _mul_iv(iv[0], iv[1],
                                         _mul_taint(iv[0], iv[1])))
        elif name == "div":
            # conservative: only the scale-clearing taint rule, interval TOP
            self._bind(eqn, env, AbsVal(taint=_mul_taint(iv[0], iv[1])))
        elif name == "rem":
            self._bind(eqn, env, _mod_iv(iv[0], iv[1], _taint_of(*iv)))
        elif name == "max":
            self._bind(eqn, env, AbsVal(max(iv[0].lo, iv[1].lo),
                                        max(iv[0].hi, iv[1].hi),
                                        _taint_of(*iv)))
        elif name == "min":
            self._bind(eqn, env, AbsVal(min(iv[0].lo, iv[1].lo),
                                        min(iv[0].hi, iv[1].hi),
                                        _taint_of(*iv)))
        elif name == "neg":
            self._bind(eqn, env, AbsVal(-iv[0].hi, -iv[0].lo, iv[0].taint))
        elif name == "sign":
            self._bind(eqn, env, AbsVal(-1.0, 1.0, iv[0].taint))
        elif name == "abs":
            lo = 0.0 if iv[0].lo <= 0 <= iv[0].hi else min(abs(iv[0].lo),
                                                           abs(iv[0].hi))
            self._bind(eqn, env, AbsVal(lo, max(abs(iv[0].lo), abs(iv[0].hi)),
                                        iv[0].taint))
        elif name == "clamp":
            # clamp(a, x, b) = max(a, min(x, b)) — monotone in all three
            lo = max(iv[0].lo, min(iv[1].lo, iv[2].lo))
            hi = max(iv[0].hi, min(iv[1].hi, iv[2].hi))
            self._bind(eqn, env, AbsVal(lo, max(lo, hi), _taint_of(*iv)))
        elif name in ("floor", "round", "ceil"):
            f = {"floor": math.floor, "ceil": math.ceil,
                 "round": round}[name]
            lo = f(iv[0].lo) if math.isfinite(iv[0].lo) else iv[0].lo
            hi = f(iv[0].hi) if math.isfinite(iv[0].hi) else iv[0].hi
            self._bind(eqn, env, AbsVal(float(lo), float(hi), iv[0].taint))

        elif name == "convert_element_type":
            new = eqn.params.get("new_dtype")
            taint = iv[0].taint
            if (taint == CODES_T and new is not None
                    and np.issubdtype(new, np.floating)):
                taint = DEQ                 # codes became float: needs a scale
            lo, hi = iv[0].lo, iv[0].hi
            if new is not None and np.issubdtype(new, np.integer):
                lo = math.floor(lo) if math.isfinite(lo) else lo
                hi = math.ceil(hi) if math.isfinite(hi) else hi
            self._bind(eqn, env, AbsVal(lo, hi, taint))

        elif name in ("lt", "le", "gt", "ge", "eq", "ne"):
            a, b = iv[0], iv[1]
            res = AbsVal(0.0, 1.0, _taint_of(a, b))
            if name == "lt" and a.hi < b.lo:
                res = AbsVal(1.0, 1.0)
            elif name == "lt" and a.lo >= b.hi:
                res = AbsVal(0.0, 0.0)
            elif name == "le" and a.hi <= b.lo:
                res = AbsVal(1.0, 1.0)
            elif name == "le" and a.lo > b.hi:
                res = AbsVal(0.0, 0.0)
            elif name == "ge" and a.lo >= b.hi:
                res = AbsVal(1.0, 1.0)
            elif name == "ge" and a.hi < b.lo:
                res = AbsVal(0.0, 0.0)
            elif name == "gt" and a.lo > b.hi:
                res = AbsVal(1.0, 1.0)
            elif name == "gt" and a.hi <= b.lo:
                res = AbsVal(0.0, 0.0)
            self._bind(eqn, env, res)
        elif name in ("and", "or", "not", "xor"):
            self._bind(eqn, env, AbsVal(0.0, 1.0, _taint_of(*iv)))

        elif name == "select_n":
            which, cases = iv[0], iv[1:]
            if which.lo == which.hi and 0 <= int(which.lo) < len(cases):
                self._bind(eqn, env, cases[int(which.lo)])
            else:
                self._bind(eqn, env, _join(*cases))
        elif name == "iota":
            dim = eqn.params.get("dimension", 0)
            shape = eqn.params.get("shape", (1,))
            self._bind(eqn, env, AbsVal(0.0, float(shape[dim] - 1)))
        elif name in ("broadcast_in_dim", "reshape", "squeeze", "transpose",
                      "slice", "rev", "expand_dims", "copy",
                      "stop_gradient", "reduce_precision"):
            self._bind(eqn, env, iv[0])
        elif name in ("concatenate", "pad"):
            self._bind(eqn, env, _join(*iv))
        elif name in ("reduce_max", "reduce_min", "reduce_or", "reduce_and"):
            self._bind(eqn, env, iv[0])
        elif name == "reduce_sum":
            axes = eqn.params.get("axes", ())
            shape = getattr(eqn.invars[0].aval, "shape", ())
            n = 1
            for a in axes:
                n *= shape[a] if a < len(shape) else 1
            self._bind(eqn, env, AbsVal(min(iv[0].lo * n, iv[0].lo),
                                        max(iv[0].hi * n, iv[0].hi),
                                        iv[0].taint))
        elif name == "dot_general":
            self._bind(eqn, env, AbsVal(taint=_mul_taint(iv[0], iv[1])))
        else:
            # unknown primitive: unbounded, taint joins through (sound for
            # KB400 — an unbounded index simply cannot be proven in-bounds)
            t = _taint_of(*iv)
            self._bind(eqn, env, *[AbsVal(taint=t)] * len(eqn.outvars))

    def _store(self, ref: RefInfo, stored: AbsVal):
        ref.taint = max(ref.taint, stored.taint)
        if ref.kind == "output" and stored.taint in (CODES_T, DEQ) \
                and not (ref.block is not None and ref.block.quant):
            key = ("KB420", ref.name)
            if key not in self.reported:
                self.reported.add(key)
                what = ("raw quantized codes" if stored.taint == CODES_T
                        else "a dequantized (int->float) value")
                self.rep.add("KB420", "error", CHECKER, self.where,
                             f"output {ref.name!r} stores {what} that was "
                             f"never multiplied by a scale_for= operand — "
                             f"unscaled dequant")

    # ------------------------------------------------- structured equations
    def _bind_sub(self, closed, operands, env: _Env) -> _Env:
        sub = env.child()
        jaxpr = getattr(closed, "jaxpr", closed)
        consts = getattr(closed, "consts", ())
        for cv, c in zip(jaxpr.constvars, consts):
            try:
                arr = np.asarray(c)
            except Exception:  # noqa: BLE001 — opaque const
                continue
            if arr.size and np.issubdtype(arr.dtype, np.number):
                sub.vals[cv] = AbsVal(float(arr.min()), float(arr.max()))
        for inv, op in zip(jaxpr.invars, operands):
            if env.is_ref(op):
                sub.refs[inv] = env.refs[op]
            else:
                sub.vals[inv] = env.read(op)
        return sub

    def _inline(self, closed, eqn, env: _Env, guarded: bool,
                refine: Optional[Dict[Any, AbsVal]] = None,
                operands: Optional[Sequence] = None):
        operands = eqn.invars if operands is None else operands
        if refine:
            env = env.child()
            env.vals.update(refine)
        sub = self._bind_sub(closed, operands, env)
        jaxpr = getattr(closed, "jaxpr", closed)
        self.run(jaxpr, sub, guarded)
        return [sub.read(ov) for ov in jaxpr.outvars]

    def _cond(self, eqn, env: _Env, guarded: bool):
        branches = eqn.params["branches"]
        idx_iv = env.read(eqn.invars[0])
        operands = eqn.invars[1:]
        constant = idx_iv.lo == idx_iv.hi and math.isfinite(idx_iv.lo)
        results: List[List[AbsVal]] = []
        for bi, closed in enumerate(branches):
            if constant and int(idx_iv.lo) != bi:
                continue
            refine = None
            inner_guarded = guarded
            if not constant:
                inner_guarded = True
                if bi == len(branches) - 1:       # the pl.when TRUE branch
                    refine = self._refine_from_pred(
                        eqn.invars[0], self._enclosing, env)
            results.append(self._inline(closed, eqn, env, inner_guarded,
                                        refine=refine, operands=operands))
        outs = []
        for i in range(len(eqn.outvars)):
            outs.append(_join(*[r[i] for r in results if i < len(r)]))
        self._bind(eqn, env, *outs)

    def _jit(self, eqn, env: _Env, guarded: bool):
        pname = eqn.params.get("name", "")
        closed = eqn.params.get("jaxpr")
        iv = [env.read(v) for v in eqn.invars if not env.is_ref(v)]
        if pname == "floor_divide" and len(iv) == 2:
            self._bind(eqn, env,
                       _floordiv_iv(iv[0], iv[1], _taint_of(*iv)))
        elif pname in ("remainder", "mod") and len(iv) == 2:
            self._bind(eqn, env, _mod_iv(iv[0], iv[1], _taint_of(*iv)))
        elif closed is not None:
            self._bind(eqn, env, *self._inline(closed, eqn, env, guarded))
        else:
            t = _taint_of(*iv)
            self._bind(eqn, env, *[AbsVal(taint=t)] * len(eqn.outvars))

    def interpret(self, jaxpr, env: _Env):
        self.run(jaxpr, env, False)


# ---------------------------------------------------------------------------
# Grid sampling (shared with kernel_contracts' KC105 replacement)
# ---------------------------------------------------------------------------

def stratified_grid_points(grid: Sequence[int], max_points: int):
    """All grid points, or a stratified sample that ALWAYS includes the
    first and last block along every grid dim (where the clamp bugs live).

    Returns (iterator of points, truncated: bool).
    """
    import itertools
    total = 1
    for g in grid:
        total *= g
    if total <= max_points:
        return itertools.product(*(range(g) for g in grid)), False
    counts = [max(1, g) for g in grid]
    while True:
        prod = 1
        for c in counts:
            prod *= c
        if prod <= max_points:
            break
        d = counts.index(max(counts))
        if counts[d] <= 2:
            break
        counts[d] = max(2, counts[d] // 2)
    axes = []
    for g, c in zip(grid, counts):
        if g <= c:
            axes.append(range(g))
        else:
            vals = np.unique(np.linspace(0, g - 1, c).round().astype(int))
            axes.append([int(v) for v in vals])
    return itertools.product(*axes), True


# ---------------------------------------------------------------------------
# KB410/411 — the grid write-race detector (contract-level, no jaxpr)
# ---------------------------------------------------------------------------

MAX_RACE_POINTS = 65536


def _check_races(lc: LaunchContract, where: str, rep: Report):
    outputs = [b for b in lc.blocks if b.is_output]
    points, truncated = stratified_grid_points(lc.grid, MAX_RACE_POINTS)
    first_hit: Dict[Tuple[str, Tuple[int, ...]], Tuple[int, ...]] = {}
    observed: Dict[str, set] = {b.name: set() for b in outputs}
    raced: set = set()
    for point in points:
        for b in outputs:
            if b.name in raced:
                continue
            try:
                idx = tuple(int(v) for v in b.index_map(*point, *lc.scalars))
            except Exception:  # noqa: BLE001 — KC101/KC105 territory
                raced.add(b.name)
                continue
            key = (b.name, idx)
            prev = first_hit.setdefault(key, point)
            if prev is point or prev == point:
                continue
            diff = [d for d in range(len(lc.grid)) if prev[d] != point[d]]
            bad = [d for d in diff if d not in b.revisits]
            if bad:
                raced.add(b.name)
                rep.add("KB410", "error", CHECKER, where,
                        f"output {b.name!r}: grid points {prev} and {point} "
                        f"both write block {idx}, differing along grid "
                        f"dim(s) {bad} which are not declared in revisits="
                        f"{tuple(b.revisits)} — a write race (declare the "
                        f"reduction dim, or fix the index map)")
            else:
                observed[b.name].update(diff)
    if truncated:
        return
    for b in outputs:
        if b.name in raced:
            continue
        stale = [d for d in b.revisits
                 if d < len(lc.grid) and lc.grid[d] > 1
                 and d not in observed[b.name]]
        if stale:
            rep.add("KB411", "warning", CHECKER, where,
                    f"output {b.name!r} declares revisits={tuple(b.revisits)} "
                    f"but no two grid points revisit a block along dim(s) "
                    f"{stale} (grid {tuple(lc.grid)}) — stale declaration")


# ---------------------------------------------------------------------------
# KB421 — static quant/scale declaration audit vs FORMAT_MATRIX
# ---------------------------------------------------------------------------

def _check_quant_decls(lc: LaunchContract, where: str, rep: Report):
    known = {c.name for c in FORMAT_MATRIX}
    by_name = {b.name: b for b in lc.blocks}
    scaled = {b.scale_for for b in lc.blocks if b.scale_for}
    for b in lc.blocks:
        if b.quant is not None and b.quant not in known:
            rep.add("KB421", "error", CHECKER, where,
                    f"block {b.name!r} declares quant={b.quant!r} which is "
                    f"not a FORMAT_MATRIX format "
                    f"({', '.join(sorted(known))})")
        if b.quant is not None and b.name not in scaled:
            rep.add("KB421", "error", CHECKER, where,
                    f"quantized block {b.name!r} has no scale operand: no "
                    f"block declares scale_for={b.name!r}")
        if b.scale_for is not None:
            codes = by_name.get(b.scale_for)
            if codes is None:
                rep.add("KB421", "error", CHECKER, where,
                        f"block {b.name!r} declares scale_for="
                        f"{b.scale_for!r} but no such block exists")
            elif codes.quant is None:
                rep.add("KB421", "error", CHECKER, where,
                        f"block {b.name!r} scales {b.scale_for!r} which "
                        f"declares no quant= format")
            elif len(b.block_shape) == len(codes.block_shape):
                for d, (s, c) in enumerate(zip(b.block_shape,
                                               codes.block_shape)):
                    if s != c and s != 1:
                        rep.add("KB421", "error", CHECKER, where,
                                f"scale {b.name!r} dim {d}: plane length "
                                f"{s} is neither 1 nor the codes block "
                                f"length {c} — scale axis mismatch vs "
                                f"{b.scale_for!r}")
                        break


# ---------------------------------------------------------------------------
# KB43x + the body walk — one LaunchContract end to end
# ---------------------------------------------------------------------------

def _pallas_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                _pallas_eqns(sub, out)
            elif isinstance(v, (tuple, list)):
                for item in v:
                    sub = getattr(item, "jaxpr", None)
                    if sub is not None and hasattr(sub, "eqns"):
                        _pallas_eqns(sub, out)
    return out


def check_body(lc: LaunchContract, where: str,
               report: Optional[Report] = None) -> Report:
    """All KB4xx checks for one concrete LaunchContract."""
    rep = report if report is not None else Report()

    _check_quant_decls(lc, where, rep)
    outputs = [b for b in lc.blocks if b.is_output]
    if outputs and any(b.is_output for b in
                       lc.blocks[:len(lc.blocks) - len(outputs)]):
        rep.add("KB431", "error", CHECKER, where,
                "is_output blocks must be a contiguous suffix of blocks "
                "(pallas_call orders inputs before outputs)")
        return rep
    _check_races(lc, where, rep)

    if lc.body is None:
        return rep

    import jax
    try:
        closed = jax.make_jaxpr(lc.body)()
    except Exception as e:  # noqa: BLE001 — surfaced as a finding
        rep.add("KB431", "error", CHECKER, where,
                f"body trace raised {type(e).__name__}: {e}")
        return rep
    calls = _pallas_eqns(closed.jaxpr, [])
    if len(calls) != 1:
        rep.add("KB431", "error", CHECKER, where,
                f"body traced to {len(calls)} pallas_call equations "
                f"(contracts describe exactly one launch)")
        return rep
    eqn = calls[0]
    kernel_jaxpr = eqn.params["jaxpr"]
    gm = eqn.params.get("grid_mapping")
    grid = tuple(int(g) for g in getattr(gm, "grid", lc.grid))
    if grid != tuple(lc.grid):
        rep.add("KB431", "error", CHECKER, where,
                f"traced grid {grid} != contract grid {tuple(lc.grid)} — "
                f"the contract drifted from the kernel")
        return rep

    nsp = lc.num_scalar_prefetch
    invars = kernel_jaxpr.invars
    if len(invars) < nsp + len(lc.blocks):
        rep.add("KB431", "error", CHECKER, where,
                f"kernel body has {len(invars)} ref operand(s) but the "
                f"contract declares {nsp} prefetch + {len(lc.blocks)} "
                f"blocks")
        return rep

    env = _Env()
    n_in = len(lc.blocks) - len(outputs)
    for i in range(nsp):
        arr = np.asarray(lc.scalars[i])
        ref = RefInfo(f"prefetch[{i}]", tuple(arr.shape), "prefetch",
                      scalars=arr)
        shape = tuple(getattr(invars[i].aval, "shape", arr.shape))
        if shape != tuple(arr.shape):
            rep.add("KB431", "error", CHECKER, where,
                    f"prefetch operand {i}: traced shape {shape} != "
                    f"contract scalar shape {tuple(arr.shape)}")
            return rep
        env.refs[invars[i]] = ref
    for j, b in enumerate(lc.blocks):
        var = invars[nsp + j]
        shape = tuple(getattr(var.aval, "shape", b.block_shape))
        if shape != tuple(b.block_shape):
            rep.add("KB431", "error", CHECKER, where,
                    f"block {b.name!r}: traced kernel ref shape {shape} != "
                    f"contract block shape {tuple(b.block_shape)} — the "
                    f"contract drifted from the kernel")
            return rep
        taint = CODES_T if b.quant else (SCALE if b.scale_for else CLEAN)
        env.refs[var] = RefInfo(b.name, shape, "output" if b.is_output
                                else "input", block=b, taint=taint)
    for s, var in enumerate(invars[nsp + len(lc.blocks):]):
        env.refs[var] = RefInfo(f"scratch[{s}]",
                                tuple(getattr(var.aval, "shape", ())),
                                "scratch")

    interp = _BodyInterp(rep, where, grid)
    try:
        interp.interpret(kernel_jaxpr, env)
    except Exception as e:  # noqa: BLE001 — interpreter bug, not a pass
        rep.add("KB431", "error", CHECKER, where,
                f"body interpretation raised {type(e).__name__}: {e}")
    return rep


def check_kernel_bodies(reg: Optional[KernelRegistry] = None,
                        sweep_values: Optional[dict] = None,
                        report: Optional[Report] = None) -> Report:
    """Sweep every registered contract's body over case x policy tiles.

    KB430 warns once per (op, impl) whose contracts never declare a body —
    the coverage analogue of KC100, required to be zero on main.
    """
    reg = reg if reg is not None else default_registry
    rep = report if report is not None else Report()
    for op, impl in reg.pallas_impls():
        fn = reg.contract(op, impl)
        where = f"{op}/{impl}"
        if fn is None:
            continue                        # KC100 already covers this
        policies: Sequence[ExecutionPolicy] = policy_sweep(
            fn.sweep_fields, values=sweep_values)
        saw_body = False
        for ci, case in enumerate(fn.cases):
            for policy in policies:
                tiles = {f: getattr(policy, f) for f in fn.sweep_fields}
                at = f"{where} case[{ci}] {tiles}" if tiles \
                    else f"{where} case[{ci}]"
                try:
                    lc = fn(case, policy)
                except Exception:  # noqa: BLE001 — KC105 already reports it
                    continue
                saw_body = saw_body or lc.body is not None
                check_body(lc, at, rep)
        if fn.cases and not saw_body:
            rep.add("KB430", "warning", CHECKER, where,
                    "no contract case declares a body= thunk — the kernel "
                    "body is invisible to the KB4xx interpreter (declare "
                    "one on the LaunchContract)")
    return rep
