"""The trace reduction, on a synthetic trace and on a recorded one."""
import glob

import pytest

from bench import devtrace as tr
from bench.devtrace import Event, Planes

US = 1000.0   # ns


PALLAS = 'custom_call_target="tpu_custom_call"'


def _op(name, opcode, start, dur, extra=""):
    return Event(f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p){extra}",
                 start * US, dur * US)


def _planes():
    # window [0, 100] us. Step A (0-45) launches chunk then decode; step B
    # (50-95) decode only. Program fingerprints: P(2) chunk, P(1) decode.
    mods = [Event("jit__step_program(2)", 0, 10 * US),
            Event("jit__step_program(1)", 12 * US, 18 * US),
            Event("jit_argmax(5)", 31 * US, 2 * US),
            Event("jit__step_program(1)", 50 * US, 30 * US)]
    ops = [_op("fusion.1", "fusion", 0, 10),
           _op("custom-call.7", "custom-call", 12, 13, ", " + PALLAS),
           _op("reduce.2", "reduce", 31, 2),
           Event("%while = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
                 50 * US, 30 * US),
           _op("fusion.5", "fusion", 50, 10),
           _op("custom-call.7", "custom-call", 60, 20, ", " + PALLAS)]
    host = [Event("bench.trace", 0, 100 * US),
            Event("bench.step", 0, 45 * US),
            Event("bench.submit", 30 * US, 15 * US),
            Event("bench.step", 50 * US, 45 * US)]
    return Planes({"/device:TPU:0": {tr.OPS_LINE: ops,
                                      tr.MODULES_LINE: mods}}, host)


def test_busy_union_programs_kernels_and_gaps():
    s = tr.reduce(_planes(), [(1, 1), (0, 1)])
    assert s.window_s == pytest.approx(100e-6)
    # union of [0, 10], [12, 25], [31, 33], [50, 80]; the loop op that
    # contains others counts once
    assert s.busy_s == pytest.approx(55e-6)
    assert s.step_ms == {"chunk": [pytest.approx(0.010)],
                         "decode": [pytest.approx(0.018),
                                    pytest.approx(0.030)]}
    assert s.pallas_s == {"decode": pytest.approx(33e-6)}
    # gaps (33, 50) in the submit span, (80, 100) and (25, 31) in steps
    assert s.idle_gaps == [["bench.step", pytest.approx(20e-6)],
                           ["bench.submit", pytest.approx(17e-6)],
                           ["bench.step", pytest.approx(6e-6)],
                           ["bench.step", pytest.approx(2e-6)]]
    top = dict(s.top_ops)
    assert top["decode:%custom-call.7 custom-call"] == pytest.approx(33e-6)
    assert top["chunk:%fusion.1 fusion"] == pytest.approx(10e-6)
    assert top["other:%reduce.2 reduce"] == pytest.approx(2e-6)
    assert not any("while" in name for name in top)


def test_steps_that_do_not_match_the_log_classify_nothing():
    s = tr.reduce(_planes(), [(0, 1)])
    assert s.step_ms == {} and s.pallas_s == {}
    assert s.busy_s == pytest.approx(55e-6)


def test_two_programs_voted_alike_are_told_apart_by_duration():
    # each step's log says decode only, but the first step's module is the
    # chunk program, which ran late: the longer program is the chunk one
    mods = [Event("jit__step_program(2)", 5 * US, 40 * US),
            Event("jit__step_program(1)", 55 * US, 25 * US),
            Event("jit__step_program(1)", 85 * US, 10 * US)]
    spans = [Event("bench.step", 0, 50 * US), Event("bench.step", 50 * US,
                                                    30 * US),
             Event("bench.step", 80 * US, 20 * US)]
    assert tr.classify(mods, spans, [(0, 1), (0, 1), (0, 1)]) == {
        "jit__step_program(2)": "chunk", "jit__step_program(1)": "decode"}


def test_merge_and_gaps():
    assert tr.merge([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_no_device_or_no_window_reads_nothing():
    p = _planes()
    assert tr.reduce(Planes({}, p.host), []) is None
    assert tr.reduce(Planes(p.devices, []), []) is None


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.trace"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = tr.load(path[0])
    names = sorted(h.name for h in planes.host)
    assert names == ["bench.step", "bench.trace"]
    step = next(h for h in planes.host if h.name == "bench.step")
    window = next(h for h in planes.host if h.name == "bench.trace")
    assert window.start_ns <= step.start_ns and step.end_ns <= window.end_ns
    # the CPU backend has no TPU plane: nothing to reduce
    assert planes.devices == {} and tr.reduce(planes, [(0, 0)]) is None
