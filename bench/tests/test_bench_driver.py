"""The load generator's loop against a stub engine: the window, the trace's
stalls kept out of it, and the grace for first tokens."""
import contextlib
import dataclasses
import time

from bench import loadgen
from bench.driver import Driver

MIX = {"loop": "open", "rate_per_s": 50.0,
       "prompt_len": {"dist": "uniform", "min": 4, "max": 8},
       "output_len": {"dist": "uniform", "min": 2, "max": 3}}


@dataclasses.dataclass
class _Stats:
    prefill_chunk_calls: int = 0
    decode_steps: int = 0
    prefill_tokens: int = 0
    generated_tokens: int = 0


class _Engine:
    """One token per request per step, 5 ms a step; a prompt is prefilled
    whole in the step that gives its first token."""
    prefill_chunk = 32

    def __init__(self):
        self.stats = _Stats()
        self.live = []

    def submit(self, req):
        req.out_tokens, req.status = [], "queued"
        self.live.append(req)
        return True

    def step(self):
        time.sleep(0.005)
        fresh = [r for r in self.live if not r.out_tokens]
        self.stats.prefill_chunk_calls += bool(fresh)
        self.stats.prefill_tokens += sum(len(r.prompt) for r in fresh)
        for r in self.live:
            r.out_tokens.append(1)
            self.stats.generated_tokens += 1
            if len(r.out_tokens) >= r.max_new_tokens:
                r.status = "done"
        self.stats.decode_steps += 1
        self.live = [r for r in self.live if r.status != "done"]

    def pending(self):
        return bool(self.live)

    def occupancy(self):
        return []


class _Request:
    def __init__(self, rid, prompt, max_new_tokens):
        self.rid, self.prompt = rid, prompt
        self.max_new_tokens = max_new_tokens
        self.out_tokens, self.status = [], "new"


class _StallingTracer:
    """A profiler whose start and stop stall the host, as a TPU's does."""

    def start(self):
        time.sleep(0.2)

    def stop(self):
        time.sleep(0.3)


def _drive(tracer=None):
    gen = loadgen.make(MIX, 5, 100, 4, [0.3, 1.0, 1.0])
    drv = Driver(_Engine(), gen, request_cls=_Request,
                 annotate=lambda name: contextlib.nullcontext())
    return drv.run(0.3, 1.0, tracer, trace_s=0.4, grace_s=1.0)


def test_the_profiler_stalls_stay_out_of_the_traced_interval():
    log = _drive(_StallingTracer())
    t0, t1 = log.traced
    assert 0.4 <= t1 - t0 < 0.45
    lags = [r.submitted - r.due for r in log.records.values()
            if t0 <= r.due <= t1]
    assert lags and max(lags) < 0.05
    assert log.steps_in(t0, t1)


def test_a_run_serves_on_until_first_tokens_and_logs_each_token():
    log = _drive()
    w0, w1 = log.window
    assert w1 - w0 == 1.0 and log.closed >= w1
    started = [r for r in log.records.values() if w0 <= r.start <= w1]
    # the window's 50 arrivals, and the warm period's last one, which its
    # schedule puts on the window's first instant
    assert len(started) in (50, 51)
    assert all(r.token_times and r.token_times[0] <= log.closed
               for r in started)
    assert all(s.consistent for s in log.steps)
    assert log.compiles_in_window == 0
