"""Fault-tolerance matrix: every injected fault class either recovers to
byte-identical greedy output or fails loudly with the right terminal state.

The recoverable rows (logits/KV poison, kernel-launch demotion, latency) must
converge to EXACTLY the un-faulted outputs — quarantine replays the retained
prompt, demotion lands on the byte-identical ref route. The unrecoverable
rows (weight poison) must fail requests terminally and then recover through
snapshot/restore. Hostile submissions must be rejected at `submit()` with a
diagnostic, never inside a trace."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.api import ExecutionPolicy
from repro.configs import get_smoke
from repro.models import init_params
from repro.serving import (EngineStalledError, Fault, FaultPlan,
                           KernelLaunchError, Request, ServingEngine,
                           drive_with_plan)

MAX_LEN = 64
NAN = float("nan")
INF = float("inf")


def _params(seed=0, kv_quant=False):
    cfg = get_smoke("qwen2_1p5b")
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    return cfg, init_params(jax.random.key(seed), cfg)


def _spec(vocab, lens, outs, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, vocab, l).astype(np.int32), m)
            for l, m in zip(lens, outs)]


def _engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(cfg, params, **kw)


def _baseline(cfg, params, spec, **kw):
    eng = _engine(cfg, params, **kw)
    for rid, (p, m) in enumerate(spec):
        eng.submit(Request(rid, p, max_new_tokens=m))
    return {r.rid: r.out_tokens for r in eng.run_until_drained()}


def _drain_with(cfg, params, spec, plan, **kw):
    eng = _engine(cfg, params, **kw)
    eng.arm_fault_plan(plan)
    for rid, (p, m) in enumerate(spec):
        eng.submit(Request(rid, p, max_new_tokens=m))
    eng.run_until_drained()
    return eng


# ======================================================== poison -> quarantine
@pytest.mark.parametrize("value", [NAN, INF, -INF])
def test_logits_poison_quarantines_and_replays(value):
    """A slot whose logits go non-finite mid-decode is quarantined and its
    request replayed byte-identically from the retained prompt; the other
    slot never notices. The jit cache still holds exactly the two lifetime
    widths — the guard and the replay added no traced shapes."""
    cfg, params = _params()
    spec = _spec(cfg.vocab, [4, 9], [6, 4])
    want = _baseline(cfg, params, spec)

    plan = FaultPlan.single("poison", step=3, slot=0, target="logits",
                            value=value)
    eng = _drain_with(cfg, params, spec, plan)
    got = {r.rid: r.out_tokens for r in eng.finished}
    assert got == want
    assert eng.stats.quarantines == 1
    assert all(r.status == "done" for r in eng.finished)
    assert plan.exhausted() and plan.faults[0].tripped
    assert eng.step_trace_count() == len(eng.step_widths()) == 2


@pytest.mark.parametrize("kv_quant", [False, True],
                         ids=["dense-kv", "int8-kv"])
def test_kv_poison_recovers(kv_quant):
    """Cache corruption (bf16 K rows, or the f32 scales of the int8
    QuantKVCache) surfaces through attention as non-finite logits at the
    slot's next consuming launch; quarantine scrubs the row and the replay
    converges to the un-faulted output."""
    cfg, params = _params(seed=1, kv_quant=kv_quant)
    spec = _spec(cfg.vocab, [5, 11], [5, 3], seed=1)
    want = _baseline(cfg, params, spec)

    plan = FaultPlan.single("poison", step=2, slot=1, target="kv", value=NAN)
    eng = _drain_with(cfg, params, spec, plan)
    got = {r.rid: r.out_tokens for r in eng.finished}
    assert got == want
    assert eng.stats.quarantines >= 1
    assert all(r.status == "done" for r in eng.finished)


def test_replay_budget_exhaustion_fails_request():
    """With the replay budget at zero, the first quarantine is terminal:
    status FAILED, counted, and the engine still drains the healthy slot."""
    cfg, params = _params()
    spec = _spec(cfg.vocab, [4, 6], [5, 5])
    plan = FaultPlan.single("poison", step=3, slot=0, target="logits")
    eng = _drain_with(cfg, params, spec, plan, max_replays=0)
    by_status = {r.status for r in eng.finished}
    assert by_status == {"done", "FAILED"}
    assert eng.stats.failed_requests == 1
    assert len(eng.finished) == 2


# ==================================================== launch-fault -> demotion
def test_launch_fault_demotes_to_ref_byte_identically():
    """An injected kernel-launch failure on a pallas-pinned engine re-pins
    the policy to the ref backend, rebuilds the jits and retries the SAME
    step — outputs match a ref engine exactly, and `degraded_routes()`
    records the before/after routes."""
    cfg, params = _params(seed=2)
    spec = _spec(cfg.vocab, [3, 7], [4, 3], seed=2)
    want = _baseline(cfg, params, spec,
                     policy=ExecutionPolicy(backend="ref"))

    plan = FaultPlan.single("launch", step=0)
    eng = _drain_with(cfg, params, spec, plan,
                      policy=ExecutionPolicy(backend="pallas"))
    got = {r.rid: r.out_tokens for r in eng.finished}
    assert got == want
    assert eng.stats.demotions == 1
    assert eng.policy.backend == "ref"
    (event,) = eng.degraded_routes()
    assert "KernelLaunchError" in event["error"]
    assert event["from"]["decode"].startswith("pallas")
    assert event["to"] == {"decode": "ref", "prefill": "ref"}


def test_dispatch_boundary_fault_demotes_unwarmed_engine():
    """A dispatch-boundary fault fires inside the registry hook the first
    time the step TRACES (the lowering-failure stand-in); the engine demotes
    and the retry traces straight down the ref route."""
    cfg, params = _params(seed=2)
    spec = _spec(cfg.vocab, [3], [3], seed=2)
    want = _baseline(cfg, params, spec,
                     policy=ExecutionPolicy(backend="ref"))

    plan = FaultPlan.single("launch", step=0, boundary="dispatch")
    eng = _drain_with(cfg, params, spec, plan,
                      policy=ExecutionPolicy(backend="pallas"))
    assert {r.rid: r.out_tokens for r in eng.finished} == want
    assert eng.stats.demotions == 1
    assert plan.faults[0].tripped


def test_serve_launcher_exits_nonzero_after_demotion(monkeypatch, capsys):
    """Demotion keeps the engine serving, but the serve launcher must not
    pass a run whose requested kernels never ran: it prints the DEGRADED
    event and exits non-zero."""
    from repro.launch import serve

    class FaultyEngine(serve.ServingEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.arm_fault_plan(FaultPlan.single("launch", step=0))

    monkeypatch.setattr(serve, "ServingEngine", FaultyEngine)
    with pytest.raises(SystemExit) as exc:
        serve._run_engine("qwen2_1p5b", True, 2, 2,
                          policy=ExecutionPolicy(backend="pallas"))
    assert "1 route demotion(s)" in str(exc.value.code)
    assert "DEGRADED at step 0" in capsys.readouterr().out


def test_launch_fault_on_ref_engine_raises():
    """No route below ref: the failure propagates instead of demoting."""
    cfg, params = _params()
    eng = _engine(cfg, params, policy=ExecutionPolicy(backend="ref"))
    eng.arm_fault_plan(FaultPlan.single("launch", step=0))
    eng.submit(Request(0, np.asarray([1, 2, 3], np.int32), max_new_tokens=2))
    with pytest.raises(KernelLaunchError):
        eng.run_until_drained()
    assert eng.stats.demotions == 0


# ================================================================== latency
def test_latency_fault_delays_but_never_corrupts():
    cfg, params = _params()
    spec = _spec(cfg.vocab, [4, 6], [3, 3])
    want = _baseline(cfg, params, spec)

    plan = FaultPlan.single("latency", step=1, delay_s=0.2)
    t0 = time.monotonic()
    eng = _drain_with(cfg, params, spec, plan)
    assert time.monotonic() - t0 >= 0.2
    assert {r.rid: r.out_tokens for r in eng.finished} == want
    assert plan.faults[0].tripped
    assert eng.stats.quarantines == eng.stats.demotions == 0


# ========================================================== malformed inputs
def test_malformed_matrix_rejected_cleanly():
    """Every hostile-submission defect is turned away at submit() with a
    ValueError/TypeError diagnostic; the well-formed request in flight is
    untouched."""
    from repro.serving.faults import MALFORMED_KINDS
    cfg, params = _params()
    spec = _spec(cfg.vocab, [5], [4])
    want = _baseline(cfg, params, spec)

    plan = FaultPlan([Fault("malformed", step=i, target=d)
                      for i, d in enumerate(MALFORMED_KINDS)])
    eng = _engine(cfg, params)
    eng.submit(Request(0, spec[0][0], max_new_tokens=spec[0][1]))
    finished, rejections = drive_with_plan(eng, plan)
    assert len(rejections) == len(MALFORMED_KINDS)
    assert all(msg for _, _, msg in rejections)
    assert plan.exhausted()
    assert {r.rid: r.out_tokens for r in finished} == want


def test_max_new_tokens_zero_still_legal():
    """0 is a valid budget (emit nothing) — hardening must not break it."""
    cfg, params = _params()
    eng = _engine(cfg, params)
    assert eng.submit(Request(7, np.asarray([1, 2], np.int32),
                              max_new_tokens=0))
    (req,) = eng.run_until_drained()
    assert req.rid == 7 and req.out_tokens == [] and req.status == "done"


# ============================================================== seeded sweep
def test_seeded_plan_is_deterministic_and_recovers():
    """Same seed -> same plan; a seeded mix of recoverable faults converges
    to the un-faulted outputs."""
    kinds = ("poison", "latency")
    assert (FaultPlan.seeded(11, steps=10, slots=2, kinds=kinds).describe()
            == FaultPlan.seeded(11, steps=10, slots=2,
                                kinds=kinds).describe())
    cfg, params = _params(seed=3)
    spec = _spec(cfg.vocab, [4, 8, 5], [5, 3, 4], seed=3)
    want = _baseline(cfg, params, spec)
    plan = FaultPlan.seeded(11, steps=10, slots=2, kinds=kinds, n_faults=4)
    eng = _drain_with(cfg, params, spec, plan, max_replays=8)
    assert {r.rid: r.out_tokens for r in eng.finished} == want
    assert all(r.status == "done" for r in eng.finished)


# ===================================== weight poison -> snapshot/restore
def test_weight_poison_fails_over_to_snapshot_restore(tmp_path):
    """Weight corruption hits every slot at once — quarantine cannot help,
    so requests burn their replay budget and FAIL; restoring the pre-fault
    snapshot (params included) replays the stream byte-identically."""
    cfg, params = _params(seed=4)
    spec = _spec(cfg.vocab, [4, 9], [6, 5], seed=4)
    want = _baseline(cfg, params, spec, weight_format="int8")

    eng = _engine(cfg, params, weight_format="int8", max_replays=1)
    for rid, (p, m) in enumerate(spec):
        eng.submit(Request(rid, p, max_new_tokens=m))
    eng.step()
    eng.step()
    eng.snapshot(tmp_path, include_params=True)

    eng.arm_fault_plan(FaultPlan.single(
        "poison", step=eng.step_no, target="weight", value=NAN))
    eng.run_until_drained()
    assert all(r.status == "FAILED" for r in eng.finished)
    assert eng.stats.failed_requests == len(spec)
    assert eng.stats.quarantines >= len(spec)

    eng.arm_fault_plan(None)
    eng.restore(tmp_path)
    got = {r.rid: r.out_tokens for r in eng.run_until_drained()}
    assert got == want
    assert all(r.status == "done" for r in eng.finished)


# =============================================== snapshot/restore round trips
@pytest.mark.parametrize("variant", ["dense", "int8-kv", "resident-int8"])
def test_snapshot_restore_midstream_byte_identical(variant, tmp_path):
    """Snapshot a busy engine mid-stream (rows mid-prefill AND mid-decode),
    restore into a FRESH engine, and finish: the restored engine's outputs
    must be byte-identical to the original continuing — across the dense,
    quantized-KV and resident-weight cache/param layouts."""
    cfg, params = _params(seed=5, kv_quant=(variant == "int8-kv"))
    kw = {"weight_format": "int8"} if variant == "resident-int8" else {}
    spec = _spec(cfg.vocab, [4, 10, 6], [5, 4, 6], seed=5)

    a = _engine(cfg, params, **kw)
    for rid, (p, m) in enumerate(spec):
        a.submit(Request(rid, p, max_new_tokens=m))
    for _ in range(3):
        a.step()
    pre = {r.rid for r in a.finished}
    a.snapshot(tmp_path)

    b = _engine(cfg, params, **kw)
    assert b.restore(tmp_path) == 3
    got_b = {r.rid: r.out_tokens for r in b.run_until_drained()}

    a.run_until_drained()
    got_a = {r.rid: r.out_tokens for r in a.finished if r.rid not in pre}
    assert got_b == got_a
    assert set(got_b) | pre == set(range(len(spec)))


def test_restore_rejects_geometry_mismatch(tmp_path):
    """A snapshot only restores into a same-shaped engine: cache-shape
    drift (different max_len here) raises instead of silently mixing."""
    cfg, params = _params()
    _engine(cfg, params, slots=2).snapshot(tmp_path)
    with pytest.raises(ValueError):
        _engine(cfg, params, slots=2,
                max_len=MAX_LEN // 2).restore(tmp_path)


# ===================================================== deadlines / timeouts
def test_deadline_steps_times_out_resident_request():
    """A per-request step deadline finishes the request with status TIMEOUT
    (deterministic — counted in engine steps, not wall clock)."""
    cfg, params = _params()
    eng = _engine(cfg, params)
    eng.submit(Request(0, np.asarray([1, 2, 3], np.int32),
                       max_new_tokens=40, deadline_steps=3))
    eng.submit(Request(1, np.asarray([4, 5], np.int32), max_new_tokens=2))
    done = eng.run_until_drained()
    by = {r.rid: r for r in done}
    assert by[0].status == "TIMEOUT" and by[0].done
    assert len(by[0].out_tokens) < 40
    assert by[1].status == "done" and len(by[1].out_tokens) == 2
    assert eng.stats.timeouts == 1


def test_ttl_times_out_queued_request():
    """A wall-clock TTL expires a request that never reached a slot."""
    cfg, params = _params()
    eng = _engine(cfg, params, slots=1)
    eng.submit(Request(0, np.asarray([1, 2], np.int32), max_new_tokens=3))
    eng.submit(Request(1, np.asarray([3, 4], np.int32), max_new_tokens=3,
                       ttl_s=0.0))
    time.sleep(0.01)
    done = eng.run_until_drained()
    by = {r.rid: r for r in done}
    assert by[1].status == "TIMEOUT" and by[1].out_tokens == []
    assert by[0].status == "done"
    assert eng.stats.timeouts == 1


# ================================================= backpressure / stall
def test_bounded_queue_backpressure():
    """max_queue bounds admission: the overflowing submit returns False,
    marks the request REJECTED, and queues nothing; a later submit (after
    the queue drains into a slot) is accepted again."""
    cfg, params = _params()
    eng = _engine(cfg, params, slots=1, max_queue=1)
    a = Request(0, np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
    b = Request(1, np.asarray([4, 5], np.int32), max_new_tokens=2)
    c = Request(2, np.asarray([6, 7], np.int32), max_new_tokens=2)
    assert eng.submit(a) is True
    assert eng.submit(b) is False
    assert b.status == "REJECTED" and eng.stats.rejected_submits == 1
    eng.step()                      # a admitted; queue has room again
    assert eng.submit(c) is True
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == [0, 2]


def test_stalled_drain_raises_diagnostic():
    """run_until_drained over budget raises EngineStalledError carrying the
    stuck occupancy and queue depth instead of a bare step count."""
    cfg, params = _params()
    eng = _engine(cfg, params, slots=1)
    eng.submit(Request(0, np.asarray([1, 2, 3], np.int32),
                       max_new_tokens=30))
    eng.submit(Request(1, np.asarray([4, 5], np.int32), max_new_tokens=5))
    with pytest.raises(EngineStalledError) as ei:
        eng.run_until_drained(max_steps=3)
    assert ei.value.stuck and ei.value.stuck[0]["rid"] == 0
    assert ei.value.queue_depth == 1
    assert "stuck slot" in str(ei.value)


# ============================================================ submit hygiene
@pytest.mark.parametrize("defect,exc", [
    ("empty-prompt", ValueError), ("float-prompt", TypeError),
    ("2d-prompt", ValueError), ("negative-max-new", ValueError),
    ("float-max-new", TypeError), ("absurd-max-new", ValueError)])
def test_submit_rejects_each_defect(defect, exc):
    from repro.serving.faults import malformed_request
    cfg, params = _params()
    eng = _engine(cfg, params)
    with pytest.raises(exc):
        eng.submit(malformed_request(defect))
    assert not eng.pending()
