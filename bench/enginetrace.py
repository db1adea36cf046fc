"""The serving engine's own records in a profiler trace, reduced beside
`bench/devtrace.py`.

The engine writes host spans into the profiler's trace: `engine.step`
around each step, and inside it `engine.admit`, `engine.launch` (the inputs
of one launch and its dispatch), `engine.sync` (the host blocking on a
device value) and `engine.emit` (the token bookkeeping after a sync). Its
step programs name their parts with `jax.named_scope` (`SCOPES`), which XLA
keeps in each instruction's `metadata={op_name=...}`. Its `EngineStats`
count admissions and their wait in the queue.

Everything here reads the trace file itself and leaves `devtrace.Planes`
alone: `devtrace.load` keeps only the harness's `bench.*` spans, so the
metrics PR 13 defined read what they read before the engine had spans.

    python3 bench/enginetrace.py --workload <cell> --seed <n> [--seconds 10]

runs one cell as a `--trace 1` run does (the first seconds of its window
under the profiler), and prints one JSON line: the idle split, the self time
of each engine span, each scope's share of the step programs' device time,
the step programs' ten longest ops with their scopes, and the mean queue
wait. It needs the chip, as a run does.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional

if __package__ in (None, ""):                  # run as a script
    import sys
    from pathlib import Path

    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import devtrace as tr
from bench.devtrace import Event, Planes

SPAN_PREFIX = "engine."
SCOPES = ("kv_cache", "attention", "weight_cast", "linear", "unembed",
          "health")
UNSCOPED = "unscoped"


def load_spans(path: str) -> List[Event]:
    """The engine's host spans (`engine.*`) of a trace file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(Event(e.name, e.start_ns, e.duration_ns)
                           for e in line.events
                           if e.name.startswith(SPAN_PREFIX))
    return out


def _overlap(a: List[tuple], b: List[tuple]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _window(planes: Planes) -> Optional[tuple]:
    w = [h for h in planes.host if h.name == "bench.trace"]
    return (w[0].start_ns, w[0].end_ns) if w else None


@dataclasses.dataclass
class IdleSplit:
    window_s: float
    idle_s: float      # device idle in the window, averaged over devices
    host_s: float      # ... inside engine.step, outside engine.sync
    sync_s: float      # ... inside engine.sync
    other_s: float     # ... outside the engine: idle_s - host_s - sync_s

    def shares(self) -> Dict[str, float]:
        return {k: getattr(self, k + "_s") / self.window_s
                for k in ("idle", "host", "sync", "other")}


def idle_split(planes: Planes, spans: Iterable[Event]) -> Optional[IdleSplit]:
    """Split the device's idle time in the traced interval (`bench.trace`)
    by what the engine was doing: busy and idle intervals are the ones
    `devtrace.reduce` takes, so `idle_s / window_s` is `device_idle_share`
    exactly. None when the trace has no device or no traced interval."""
    win = _window(planes)
    if not planes.devices or win is None:
        return None
    t0, t1 = win
    spans = list(spans)
    steps = tr.merge(tr.clip([s for s in spans if s.name == "engine.step"],
                             t0, t1))
    syncs = tr.merge(tr.clip([s for s in spans if s.name == "engine.sync"],
                             t0, t1))
    idle = host = sync = 0.0
    for lines in planes.devices.values():
        ops = [e for e in lines.get(tr.OPS_LINE, [])
               if e.end_ns > t0 and e.start_ns < t1]
        mods = [e for e in lines.get(tr.MODULES_LINE, [])
                if e.end_ns > t0 and e.start_ns < t1]
        gaps = tr.gaps(tr.merge(tr.clip(ops or mods, t0, t1)), t0, t1)
        idle += sum(e - s for s, e in gaps)
        in_sync = _overlap(gaps, syncs)
        sync += in_sync
        host += _overlap(gaps, steps) - in_sync
    n = len(planes.devices)
    idle, host, sync = idle / n, host / n, sync / n
    return IdleSplit(window_s=(t1 - t0) * 1e-9, idle_s=idle * 1e-9,
                     host_s=host * 1e-9, sync_s=sync * 1e-9,
                     other_s=(idle - host - sync) * 1e-9)


def self_times(spans: Iterable[Event], t0: float = float("-inf"),
               t1: float = float("inf")) -> Dict[str, float]:
    """Seconds of each span name's self time (its duration less the part
    its child spans cover), over the spans that begin in [t0, t1]. Spans
    of one thread nest, so a span's children are the spans it contains
    that no smaller span between them contains."""
    spans = sorted((s for s in spans if t0 <= s.start_ns <= t1),
                   key=lambda s: (s.start_ns, -s.dur_ns))
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []                 # [span, child time]

    def close(entry):
        span, child = entry
        out[span.name] += (span.dur_ns - child) * 1e-9
        if stack:
            stack[-1][1] += span.dur_ns

    for s in spans:
        while stack and s.start_ns >= stack[-1][0].end_ns:
            close(stack.pop())
        stack.append([s, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s")
_OPCODE = re.compile(r"[\]\})] ([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|body|condition)=(%[\w.\-]+)")
_OPERAND = re.compile(r"%[\w.\-]+")
# ops that only move or regroup bytes: such an op moves the bytes of the
# first instruction up its operands that does something else
_MOVES = ("bitcast", "copy", "copy-start", "copy-done", "slice",
          "slice-start", "slice-done", "dynamic-slice", "reshape",
          "transpose", "get-tuple-element", "custom-call")


def scope_of(op_name: str) -> str:
    """The innermost of `SCOPES` in an HLO op_name path
    ("jit(f)/while/body/kv_cache/convert_element_type"), or UNSCOPED."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


@dataclasses.dataclass
class _Instr:
    opcode: str
    operands: List[str]
    op_name: str                       # "" where there is no metadata
    called: List[str]
    line: str


def _parse_hlo(text: str) -> Dict[str, Dict[str, _Instr]]:
    comps: Dict[str, Dict[str, _Instr]] = {}
    cur = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        k = _OPCODE.search(line, m.end()) if m else None
        if k and cur is not None:
            name, opcode = m.group(1), k.group(1)
            args = line[k.end():].split(")", 1)[0]
            op = _OP_NAME.search(line)
            cur[name] = _Instr(opcode, _OPERAND.findall(args),
                               op.group(1) if op else "",
                               _CALLED.findall(line), line)
            continue
        c = _COMPUTATION.match(line)
        if c:
            cur = comps.setdefault(c.group(1), {})
    return comps


def _is_move(ins: _Instr) -> bool:
    return ins.opcode in _MOVES and (ins.opcode != "custom-call" or
                                     "tpu_custom_call" not in ins.line)


def _moved_from(comp: Dict[str, _Instr], name: str) -> Optional[str]:
    """The instruction whose bytes instruction `name` carries: its first
    operand, followed through ops that only move bytes."""
    seen = set()
    while name in comp and name not in seen and comp[name].operands:
        seen.add(name)
        name = comp[name].operands[0]
        if name not in comp or not _is_move(comp[name]):
            return name
    return None


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name ("%fusion.12") -> scope, from a compiled module's
    HLO text (`compiled.as_text()`), where each instruction keeps the
    `op_name` of the source op it came from:

    - an instruction takes the innermost of `SCOPES` in its op_name; one
      without any takes the scope of the loop or call whose body holds it
      (a scatter XLA lowers to a loop keeps the scatter's scope);
    - a fusion keeps the op_name of one fused op; where another fused op
      carries `kv_cache`, the fusion goes to `kv_cache` (XLA merges the
      cache's cast into the reshape that reads it, under the reshape's
      name, and fuses both with the cache's read);
    - an op that only moves bytes (a copy, a transpose, ...) of a
      `kv_cache` result is `kv_cache` (the relayout of the cast cache for
      the kernel keeps the kernel's reshape's name);
    - a `convert` of a weight (a parameter of the step program's `p`,
      through ops that only move bytes) is `weight_cast`: XLA puts these in
      for f32 dots at default precision and gives them no metadata.
    """
    comps = _parse_hlo(text)
    base = {n: scope_of(i.op_name) for instrs in comps.values()
            for n, i in instrs.items()}
    for instrs in comps.values():
        for n, i in instrs.items():
            if i.opcode == "fusion" and any(
                    base.get(k) == "kv_cache" for callee in i.called
                    for k in comps.get(callee, {})):
                base[n] = "kv_cache"
    # the scope each computation inherits from the op that calls it
    context: Dict[str, str] = {}
    for c, instrs in comps.items():
        for n, i in instrs.items():
            for callee in i.called:
                if base[n] != UNSCOPED:
                    context[callee] = base[n]
    out = {}
    for c, instrs in comps.items():
        for n, i in instrs.items():
            scope, src = base[n], _moved_from(instrs, n)
            if _is_move(i) and base.get(src) == "kv_cache":
                scope = "kv_cache"
            elif scope == UNSCOPED and i.opcode == "convert" and \
                    src in instrs and instrs[src].opcode == "parameter" and \
                    instrs[src].op_name.startswith("p["):
                scope = "weight_cast"
            if scope == UNSCOPED:
                scope = context.get(c, UNSCOPED)
            out[n] = scope
    return out


def step_op_seconds(planes: Planes, launches: List[tuple],
                    scopes: Dict[str, Dict[str, str]]) -> Dict[str, dict]:
    """Device seconds of the step programs' ops in the traced interval,
    per program kind and scope: {kind: {scope: seconds}}. The step
    programs and their kinds are the ones `devtrace.reduce` finds, from
    the same `launches`; `scopes[kind]` maps that program's instruction
    names to scopes (`hlo_scopes`). Loops and calls, which contain other
    ops, are left out, as `devtrace` leaves them out of its breakdown."""
    win = _window(planes)
    if not planes.devices or win is None:
        return {}
    t0, t1 = win
    spans = sorted((h for h in planes.host if h.name == "bench.step"
                    and t0 <= h.start_ns and h.end_ns <= t1),
                   key=lambda h: h.start_ns)
    out: Dict[str, dict] = defaultdict(lambda: defaultdict(float))
    for lines in planes.devices.values():
        mods = [e for e in lines.get(tr.MODULES_LINE, [])
                if e.end_ns > t0 and e.start_ns < t1]
        family = defaultdict(float)
        for m in mods:
            family[tr._base(m.name)] += m.dur_ns
        if not family:
            continue
        step_family = max(family, key=family.get)
        steps = sorted((m for m in mods if tr._base(m.name) == step_family
                        and t0 <= m.start_ns and m.end_ns <= t1),
                       key=lambda m: m.start_ns)
        kind_of = tr.classify(steps, spans, launches)
        starts = [m.start_ns for m in steps]
        for e in lines.get(tr.OPS_LINE, []):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i < 0 or e.start_ns >= steps[i].end_ns:
                continue
            kind = kind_of.get(steps[i].name)
            name, opcode = tr.op_name(e.name)
            if kind is None or opcode in tr._CONTAINERS:
                continue
            scope = scopes.get(kind, {}).get(name, UNSCOPED)
            out[kind][scope] += e.dur_ns * 1e-9
    return {k: dict(v) for k, v in out.items()}


def scope_share(seconds: Dict[str, dict], scope: str) -> Optional[float]:
    """`scope`'s share of the device time of both step programs."""
    total = sum(t for per in seconds.values() for t in per.values())
    if total <= 0:
        return None
    return sum(per.get(scope, 0.0) for per in seconds.values()) / total


def queue_wait_ms(deltas: Iterable[dict]) -> Optional[float]:
    """Mean wait from `submit()` to a first slot over the admissions of
    the given steps (each step's `EngineStats` deltas), in ms."""
    n = wait = 0
    for d in deltas:
        n += d.get("admissions", 0)
        wait += d.get("queue_wait_s", 0.0)
    return 1e3 * wait / n if n else None


def breakdown_scopes(top_ops: List[list],
                     scopes: Dict[str, Dict[str, str]]) -> List[list]:
    """`devtrace` breakdown rows ("decode:%convert.23 convert", seconds)
    with the scope of each op appended."""
    out = []
    for label, secs in top_ops:
        kind, _, rest = label.partition(":")
        out.append([label, secs,
                    scopes.get(kind, {}).get(rest.split(" ")[0], UNSCOPED)])
    return out


def main(argv=None) -> int:
    import argparse
    import dataclasses
    import json
    import tempfile
    import time
    from pathlib import Path

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)

    import jax
    from bench import harness, loadgen
    from bench import weights as W
    from bench.driver import Driver
    from repro import api
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seconds = args.seconds or harness.TRACE_S
    cell = harness.load_cell(args.workload, harness.ROOT)
    device = harness.device_info(cell.chips)
    spec, mix = cell.spec, cell.mix
    cfg = harness.program_config(spec)
    abstract = jax.eval_shape(lambda k: init_params(k, cfg),
                              jax.random.key(0))
    params = W.program_params(W.seed_key(args.seed), abstract, spec)
    eng = ServingEngine(cfg, params, slots=spec["engine"]["slots"],
                        max_len=mix["max_len"],
                        policy=api.ExecutionPolicy()).warmup()
    scopes = {("decode" if w == 1 else "chunk"): hlo_scopes(eng.step_hlo(w))
              for w in eng.step_widths()}
    deltas = []

    class StatsDriver(Driver):
        """Keeps each step's EngineStats deltas beside its StepLog."""

        def _step(self):
            before = dataclasses.asdict(self.eng.stats)
            super()._step()
            after = dataclasses.asdict(self.eng.stats)
            deltas.append((self.steps[-1], {k: after[k] - before[k]
                                            for k in after}))

    vocab_ids = spec["derived"].get("tokenizer_vocab", spec["vocab_size"])
    gen = loadgen.make(mix, args.seed, vocab_ids, eng.slots,
                       [mix["warm_s"], seconds, harness.TAIL_S])
    drv = StatsDriver(eng, gen, request_cls=Request,
                      annotate=jax.profiler.TraceAnnotation,
                      clock=time.perf_counter)
    with tempfile.TemporaryDirectory(prefix="engine-trace-") as d:
        log = drv.run(mix["warm_s"], seconds, harness._Tracer(d),
                      trace_s=seconds)
        (path,) = [str(p) for p in Path(d).rglob("*.xplane.pb")]
        planes, spans = tr.load(path), load_spans(path)
    t0, t1 = log.traced
    steps = log.steps_in(t0, t1)
    launches = [(s.chunk_launches, s.decode_launches) for s in steps]
    summary = tr.reduce(planes, launches)
    split = idle_split(planes, spans)
    if summary is None or split is None:
        raise SystemExit("the trace has no device plane or no window")
    w0 = [h for h in planes.host if h.name == "bench.trace"][0]
    n_steps = sum(s.name == "engine.step" and w0.start_ns <= s.start_ns
                  <= w0.end_ns for s in spans)
    selfs = self_times(spans, w0.start_ns, w0.end_ns)
    op_s = step_op_seconds(planes, launches, scopes)
    in_window = [d for s, d in deltas if t0 <= s.t_begin and s.t_end <= t1]
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "device": device,
        "window_s": split.window_s, "steps": n_steps,
        "device_idle_share": split.shares(),
        "self_ms_per_step": {k: 1e3 * v / max(n_steps, 1)
                             for k, v in sorted(selfs.items())},
        "step_ms": {k: sum(v) / len(v) for k, v in summary.step_ms.items()},
        "scope_share": {s: scope_share(op_s, s) for s in SCOPES +
                        (UNSCOPED,)},
        "device_ops": breakdown_scopes(summary.top_ops, scopes),
        "idle_gaps": summary.idle_gaps,
        "queue_wait_ms": queue_wait_ms(in_window)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
