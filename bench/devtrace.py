"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

`load` reads the trace with `jax.profiler.ProfileData` into plain events.
`reduce` then takes, within the traced interval (the host span
`bench.trace`):

- busy time: the union of the intervals in which an operation ran on each
  device, averaged over the devices;
- each execution of the engine's two step programs (chunked prefill and
  decode), told apart by the engine's own launch counts of each step;
- the time of the Pallas kernels (`tpu_custom_call` ops) inside each;
- the idle gaps between busy intervals, each named by the innermost host
  span (`bench.*`) that covers its middle;
- the device operations that took most time, named by program and HLO
  instruction (loops and calls, which contain other ops, left out).

On a TPU an op event's name is its HLO instruction's text, and a module
event's name is the program's name and fingerprint. Device and host events
of one trace share one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Planes:
    devices: Dict[str, Dict[str, List[Event]]]   # plane -> line -> events
    host: List[Event]                             # bench.* host spans


def load(path: str) -> Planes:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [Event(e.name, e.start_ns,
                                              e.duration_ns)
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIX))
    return Planes(devices, host)


def merge(intervals) -> List[tuple]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(events: List[Event], t0: float, t1: float) -> List[tuple]:
    """(start, end) of each event, cut to [t0, t1]; empty ones dropped."""
    out = []
    for e in events:
        s, t = max(e.start_ns, t0), min(e.end_ns, t1)
        if t > s:
            out.append((s, t))
    return out


def gaps(busy: List[tuple], t0: float, t1: float) -> List[tuple]:
    """Idle (start, end) intervals of [t0, t1] between merged busy ones."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def span_at(host: List[Event], t: float) -> str:
    """The innermost host span covering time t, or "none"."""
    best = None
    for h in host:
        if h.name != "bench.trace" and h.start_ns <= t <= h.end_ns and \
                (best is None or h.dur_ns < best.dur_ns):
            best = h
    return best.name if best else "none"


_OPCODE = re.compile(r"[\]\})] ([a-z][\w-]*)\(")
_CONTAINERS = ("while", "conditional", "call")
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'


def op_name(text: str) -> tuple:
    """(instruction name, opcode) of an op event, whose name is the HLO
    instruction's text."""
    name = text.split(" = ", 1)[0]
    m = _OPCODE.search(text)
    return name, (m.group(1) if m else "")


def _base(module_name: str) -> str:
    return module_name.split("(", 1)[0]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over the devices
    step_ms: Dict[str, List[float]]     # program kind -> each execution, ms
    pallas_s: Dict[str, float]          # program kind -> its Pallas kernels
    top_ops: List[list]                 # [[name, seconds], ...]
    idle_gaps: List[list]               # [[host span, seconds], ...]


def classify(modules: List[Event], spans: List[Event],
             launches: List[tuple]) -> Dict[str, str]:
    """Module name (program fingerprint) -> "chunk" or "decode", from the
    engine steps of the traced interval: `launches[i]` is (chunk, decode)
    launches of the step whose host span is `spans[i]`, and a step that
    launches both runs its chunk program first. Each module goes to the
    last step span that began before it. Where the votes give two programs
    the same kind (a module that ran after its step's span), the one that
    runs longer is the chunk program: it is the same model over
    `prefill_chunk` tokens a row where decode has one."""
    if len(spans) != len(launches):
        return {}
    starts = [h.start_ns for h in spans]
    per_step: Dict[int, List[Event]] = defaultdict(list)
    for m in sorted(modules, key=lambda e: e.start_ns):
        i = bisect.bisect_right(starts, m.start_ns) - 1
        if i >= 0:
            per_step[i].append(m)
    votes: Dict[str, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for i, (chunk, decode) in enumerate(launches):
        ms = per_step.get(i, [])
        kinds = ["chunk"] * chunk + ["decode"] * decode
        if len(ms) == len(kinds):
            for m, k in zip(ms, kinds):
                votes[m.name][k] += 1
    kind_of = {name: max(v, key=v.get) for name, v in votes.items()}
    if len(kind_of) == 2 and len(set(kind_of.values())) == 1:
        runs = defaultdict(list)
        for m in modules:
            runs[m.name].append(m.dur_ns)
        longer, shorter = sorted(
            kind_of, key=lambda n: -sorted(runs[n])[len(runs[n]) // 2])
        kind_of = {longer: "chunk", shorter: "decode"}
    return kind_of


def reduce(planes: Planes, launches: List[tuple],
           top: int = 10) -> Optional[Summary]:
    """Reduce the traced interval (the host span `bench.trace`).
    `launches` lists (chunk, decode) launches of each engine step whose
    host span `bench.step` lies in it, in order. The step programs are the
    modules of the family that takes most device time. None when the trace
    has no device or no `bench.trace` span."""
    window = [h for h in planes.host if h.name == "bench.trace"]
    if not planes.devices or not window:
        return None
    t0, t1 = window[0].start_ns, window[0].end_ns
    spans = sorted((h for h in planes.host if h.name == "bench.step"
                    and t0 <= h.start_ns and h.end_ns <= t1),
                   key=lambda h: h.start_ns)
    busy_total, ops_time = 0.0, defaultdict(float)
    step_ms: Dict[str, List[float]] = defaultdict(list)
    pallas_s: Dict[str, float] = defaultdict(float)
    all_gaps = []
    for lines in planes.devices.values():
        ops = [e for e in lines.get(OPS_LINE, [])
               if e.end_ns > t0 and e.start_ns < t1]
        mods = [e for e in lines.get(MODULES_LINE, [])
                if e.end_ns > t0 and e.start_ns < t1]
        busy = merge(clip(ops or mods, t0, t1))
        busy_total += sum(e - s for s, e in busy)
        all_gaps.extend(gaps(busy, t0, t1))
        family = defaultdict(float)
        for m in mods:
            family[_base(m.name)] += m.dur_ns
        step_family = max(family, key=family.get) if family else None
        steps = sorted((m for m in mods if _base(m.name) == step_family
                        and t0 <= m.start_ns and m.end_ns <= t1),
                       key=lambda m: m.start_ns)
        kind_of = classify(steps, spans, launches)
        for m in steps:
            if m.name in kind_of:
                step_ms[kind_of[m.name]].append(m.dur_ns * 1e-6)
        m_starts = [m.start_ns for m in steps]
        for e in ops:
            name, opcode = op_name(e.name)
            i = bisect.bisect_right(m_starts, e.start_ns) - 1
            inside = i >= 0 and e.start_ns < steps[i].end_ns
            kind = kind_of.get(steps[i].name) if inside else None
            if PALLAS_MARK in e.name and kind is not None:
                pallas_s[kind] += e.dur_ns * 1e-9
            if opcode in _CONTAINERS:
                continue
            label = f"{kind or 'other'}:{name} {opcode}".strip()
            ops_time[label] += (min(e.end_ns, t1) - max(e.start_ns, t0))
    n_dev = len(planes.devices)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[span_at(planes.host, (s + e) / 2), (e - s) * 1e-9]
            for s, e in all_gaps[:top]]
    top_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(t1 - t0) * 1e-9,
                   busy_s=busy_total * 1e-9 / n_dev,
                   step_ms=dict(step_ms), pallas_s=dict(pallas_s),
                   top_ops=[[n, t * 1e-9] for n, t in top_ops],
                   idle_gaps=idle)
