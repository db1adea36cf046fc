"""The load generator's loop: offers the generated requests to the engine
through `submit` and `step`, and logs what a client sees and what each step
did.

Single-threaded, as the engine is: before each step every request that has
become due is submitted; the engine is stepped; every token a request got in
the step is stamped with the step's return, which is when it reached the
host (the engine syncs on every launch whose tokens it reads). When nothing
is in flight the loop sleeps until the next arrival. Host spans
(`bench.submit`, `bench.step`, `bench.sleep`, `bench.trace`) go into the
profiler's trace, so that device idle gaps can be named by what the host
was doing.

Per step it also records the work the step did, for the rooflines: each
prefilling row's prompt positions, and each decode-generated token's
context. It reads them from what a client can see (each request's tokens and
the engine's public occupancy and counters), assuming the engine's chunked
admission (each prefilling row advances by up to `prefill_chunk` tokens per
step); a step where that does not add up to the engine's own counters is
marked inconsistent, and the readers that need the split stay silent.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

from .stats import Record

TERMINAL = ("done", "TIMEOUT", "REJECTED", "FAILED")


@dataclasses.dataclass
class StepLog:
    t_begin: float
    t_end: float
    chunk_launches: int
    decode_launches: int
    prefill_rows: List[tuple]       # (start, stop) prompt positions per row
    decode_keys: List[int]          # context of each decode-generated token
    consistent: bool


@dataclasses.dataclass
class Log:
    records: Dict[int, Record]
    steps: List[StepLog]
    window: tuple                   # (w0, w1) on the host clock
    closed: float                   # when the loop stopped (w1 + grace)
    traced: Optional[tuple] = None  # (t0, t1) of the traced interval
    compiles_in_window: int = 0

    def steps_in(self, t0: float, t1: float) -> List[StepLog]:
        return [s for s in self.steps if s.t_begin >= t0 and s.t_end <= t1]


@dataclasses.dataclass
class _Flight:
    req: object
    rec: Record
    seen: int = 0
    prompt_done: int = 0


class Driver:
    def __init__(self, engine, gen, *, request_cls, annotate,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 compile_count: Callable[[], int] = lambda: 0):
        self.eng = engine
        self.gen = gen
        self.request_cls = request_cls
        self.annotate = annotate
        self.clock = clock
        self.sleep = sleep
        self.compile_count = compile_count
        self.flights: Dict[int, _Flight] = {}
        self.records: Dict[int, Record] = {}
        self.steps: List[StepLog] = []
        # rid -> (prompt, served tokens) of every request that finished
        self.served: Dict[int, tuple] = {}

    def _submit(self, planned, start: Optional[float]):
        now = self.clock()
        req = self.request_cls(planned.rid, planned.prompt,
                               max_new_tokens=planned.max_new)
        with self.annotate("bench.submit"):
            accepted = self.eng.submit(req)
        rec = Record(rid=planned.rid, prompt_len=len(planned.prompt),
                     max_new=planned.max_new,
                     start=now if start is None else start, submitted=now,
                     due=start, client=planned.client)
        self.records[planned.rid] = rec
        if accepted:
            self.flights[planned.rid] = _Flight(req, rec)
        else:
            rec.status = req.status
            rec.finished = now
            if planned.client is not None:
                self._submit(self.gen.next(planned.client), None)

    def _stats(self) -> dict:
        return dataclasses.asdict(self.eng.stats)

    def _step(self):
        chunk = self.eng.prefill_chunk
        before = self._stats()
        t_begin = self.clock()
        with self.annotate("bench.step"):
            self.eng.step()
        t_end = self.clock()
        after = self._stats()
        occupied = {o["rid"]: (slot, o["generated"])
                    for slot, o in enumerate(self.eng.occupancy())
                    if o is not None}
        prefilling = {rid for rid, (_, g) in occupied.items() if g == 0}
        rows, keys, firsts = [], [], 0
        for rid, f in list(self.flights.items()):
            if f.rec.slot is None and rid in occupied:
                f.rec.slot = occupied[rid][0]
            n = len(f.req.out_tokens)
            if n > f.seen:
                f.rec.token_times.extend([t_end] * (n - f.seen))
            if rid in prefilling or (f.seen == 0 and n >= 1):
                left = f.rec.prompt_len - f.prompt_done
                take = left if n >= 1 else min(chunk, left)
                rows.append((f.prompt_done, f.prompt_done + take))
                f.prompt_done += take
                firsts += n >= 1
            keys.extend(f.rec.prompt_len + j for j in range(max(f.seen, 1), n))
            f.seen = n
            if f.req.status in TERMINAL:
                f.rec.status = f.req.status
                f.rec.finished = t_end
                self.served[rid] = (f.req.prompt, list(f.req.out_tokens))
                del self.flights[rid]
                if f.rec.client is not None:
                    self._submit(self.gen.next(f.rec.client), None)
        d = {k: after[k] - before[k] for k in after}
        consistent = (sum(b - a for a, b in rows) == d["prefill_tokens"]
                      and len(keys) + firsts == d["generated_tokens"])
        self.steps.append(StepLog(
            t_begin, t_end, d["prefill_chunk_calls"], d["decode_steps"],
            rows, keys, consistent))

    def _awaiting_first(self, w0: float, w1: float) -> bool:
        return any(w0 <= f.rec.start <= w1 and not f.rec.token_times
                   for f in self.flights.values())

    def run(self, warm_s: float, seconds: float, tracer=None,
            trace_s: float = 0.0, grace_s: float = 0.0) -> Log:
        """Warm for `warm_s` seconds, then measure `seconds`. Past the
        window the loop serves on, arrivals included, for up to `grace_s`
        seconds until every request started in the window has its first
        token. With a `tracer` (start/stop callables) the profiler runs for
        the first `trace_s` seconds of the window instead: it starts as the
        warm period ends and the window begins once it has started; its
        stop stalls the host, so a traced run's window serves the
        per-layer metrics of the traced part and the correctness sample
        only, and takes no grace."""
        t_sched = self.clock()
        w0 = t_sched + warm_s
        open_loop = hasattr(self.gen, "due_by")
        if not open_loop:
            for p in self.gen.first():
                self._submit(p, None)
        span, w1, traced, compiles0, compiles = None, None, None, 0, None
        while True:
            now = self.clock()
            if w1 is None and now >= w0:
                if tracer is not None:
                    tracer.start()
                    span = self.annotate("bench.trace")
                    span.__enter__()
                    now = w0 = self.clock()
                w1 = w0 + seconds
                compiles0 = self.compile_count()
            if open_loop:
                for p in self.gen.due_by(now - t_sched):
                    self._submit(p, t_sched + p.due)
            if span is not None and traced is None and \
                    now >= min(w0 + trace_s, w1):
                # every step of the span, the last one included, ends by
                # now, and every request due by now is submitted before the
                # profiler's stop stalls the host
                traced = (w0, now)
                span.__exit__(None, None, None)
                tracer.stop()
            if w1 is not None and now >= w1:
                if compiles is None:
                    compiles = self.compile_count() - compiles0
                if tracer is not None or now >= w1 + grace_s or \
                        not self._awaiting_first(w0, w1):
                    break
            if self.eng.pending():
                self._step()
                continue
            nxt = self.gen.next_due() if open_loop else None
            until = w0 if w1 is None else w1 if now < w1 else w1 + grace_s
            if nxt is not None:
                until = min(until, t_sched + nxt)
            if until > now:
                with self.annotate("bench.sleep"):
                    self.sleep(until - now)
        return Log(self.records, self.steps, (w0, w1), now, traced,
                   compiles)
