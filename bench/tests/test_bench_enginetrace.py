"""The engine's records in a trace: its spans kept apart from the
harness's, the idle split, span self times, the scope of each op, and the
queue wait."""
import jax
import numpy as np
import pytest

from bench import devtrace as tr
from bench import enginetrace as et
from bench.devtrace import Event
from bench.tests.test_bench_devtrace import US, _planes


def _spans():
    # step A: gaps (25, 31) and (33, 50) of the fixture; step B: (80, 100)
    return [Event(n, a * US, (b - a) * US) for n, a, b in [
        ("engine.step", 1, 44), ("engine.admit", 1, 3),
        ("engine.launch", 3, 12), ("engine.sync", 24, 32),
        ("engine.emit", 32, 44),
        ("engine.step", 51, 94), ("engine.admit", 51, 52),
        ("engine.launch", 52, 60), ("engine.sync", 78, 88),
        ("engine.emit", 88, 94)]]


def test_the_idle_split_adds_up_to_the_idle_share():
    planes = _planes()
    split = et.idle_split(planes, _spans())
    summary = tr.reduce(planes, [(1, 1), (0, 1)])
    assert split.idle_s / split.window_s == pytest.approx(
        1 - summary.busy_s / summary.window_s)
    # in sync: (25, 31) and (80, 88); in a step outside sync: (10, 12),
    # (33, 44) and (88, 94); the rest, (44, 50) and (94, 100), outside the
    # engine
    assert split.sync_s == pytest.approx(14e-6)
    assert split.host_s == pytest.approx(19e-6)
    assert split.other_s == pytest.approx(12e-6)
    shares = split.shares()
    assert shares["host"] + shares["sync"] + shares["other"] == \
        pytest.approx(shares["idle"])
    assert et.idle_split(tr.Planes({}, planes.host), _spans()) is None


def test_self_times_take_out_the_children():
    got = et.self_times(_spans())
    assert got["engine.step"] == pytest.approx((43 - 31 + 43 - 25) * 1e-6)
    assert got["engine.launch"] == pytest.approx(17e-6)
    assert got["engine.sync"] == pytest.approx(18e-6)
    assert et.self_times(_spans(), 50 * US, 100 * US)["engine.step"] == \
        pytest.approx(18e-6)


HLO = """\
HloModule jit__step_program, entry_computation_layout={()->f32[8]}

%fused_computation.26 (param_0: bf16[8], param_1: pred[8]) -> f32[8] {
  %param_0 = bf16[8]{0} parameter(0)
  %param_1 = pred[8]{0} parameter(1)
  %select_n.37 = bf16[8]{0} select(%param_1, %param_0, %param_0), metadata={op_name="jit(_step_program)/while/body/closed_call/kv_cache/jit(_where)/select_n"}
  ROOT %convert.42 = f32[8]{0} convert(%select_n.37), metadata={op_name="jit(_step_program)/while/body/closed_call/attention/reshape"}
}

%scatter_body (t: (s32[], bf16[8])) -> (s32[], bf16[8]) {
  %t = (s32[], bf16[8]{0}) parameter(0)
  ROOT %dynamic-update-slice.6 = bf16[8]{0} dynamic-update-slice(%t, %t, %t)
}

%body (p: f32[8]) -> (f32[8], f32[8]) {
  %p = f32[8]{0} parameter(0)
  %fusion.133 = (f32[8]{0}, f32[8]{0}) fusion(%p, %p), kind=kLoop, calls=%fused_computation.26, metadata={op_name="jit(_step_program)/while/body/closed_call/attention/reshape" stack_frame_id=7}
  %get-tuple-element.446 = f32[8]{0} get-tuple-element(%fusion.133), index=0
  %copy.73 = f32[8]{0} copy(%get-tuple-element.446), metadata={op_name="jit(_step_program)/while/body/closed_call/attention/reshape"}
  %while.14 = (s32[], bf16[8]{0}) while(%p), condition=%scatter_cond, body=%scatter_body, metadata={op_name="jit(_step_program)/while/body/closed_call/kv_cache/vmap(vmap())/scatter"}
  %convert.3 = f32[8]{0} convert(f32[8]{0} %p), metadata={op_type="convert_element_type" op_name="jit(_step_program)/while/body/closed_call/attention/kv_cache/convert_element_type" source_file="a.py" source_line=1}
  ROOT %fusion.7 = f32[8]{0} fusion(f32[8]{0} %convert.3), kind=kLoop, calls=%fc, metadata={op_name="jit(_step_program)/while/body/closed_call/linear/dot_general"}
}

ENTRY %main.15 (p__mlp__down__w.1: f32[2,8], c_k.1: bf16[2,8]) -> f32[8] {
  %p__mlp__down__w.1 = f32[2,8]{1,0:T(8,128)} parameter(0), metadata={op_name="p[\\'mlp\\'][\\'down\\'][\\'w\\']"}
  %c_k.1 = bf16[2,8]{1,0:T(8,128)} parameter(1), metadata={op_name="c[0].k"}
  %convert.21 = bf16[2,8]{1,0:T(8,128)(2,1)S(1)} convert(%p__mlp__down__w.1), backend_config={"flag_configs":[]}
  %slice-start.6 = ((f32[2,8]{1,0}), f32[1,8]{1,0}) slice-start(%p__mlp__down__w.1), slice={[0:1], [0:8]}
  %slice-done.6 = f32[1,8]{1,0:T(8,128)S(1)} slice-done(%slice-start.6)
  %custom-call.8 = f32[1,8]{1,0} custom-call(%slice-done.6), custom_call_target="ConcatBitcast"
  %convert.16 = bf16[1,8]{1,0} convert(%custom-call.8)
  %convert.30 = f32[2,8]{1,0} convert(%c_k.1)
  %copy.71 = bf16[2,8]{1,0} copy(%c_k.1)
  ROOT %custom-call.2 = f32[8]{0} custom-call(%convert.21), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step_program)/health/reduce_and"}
}
"""


def test_each_instruction_takes_its_scope():
    got = et.hlo_scopes(HLO)
    assert got["%convert.3"] == "kv_cache"
    assert got["%fusion.7"] == "linear"
    assert got["%custom-call.2"] == "health"
    # a fusion holding a cache op goes to kv_cache, whatever its own name
    assert got["%fusion.133"] == "kv_cache"
    # the cast cache's relayout for the kernel, named after its reshape
    assert got["%copy.73"] == "kv_cache"
    # a scatter lowered to a loop: its body's ops keep the scatter's scope
    assert got["%while.14"] == got["%dynamic-update-slice.6"] == "kv_cache"
    # converts of a weight, directly or through ops that move bytes
    assert got["%convert.21"] == got["%convert.16"] == "weight_cast"
    # a convert of the cache and a copy nobody named stay unscoped
    assert got["%convert.30"] == got["%copy.71"] == et.UNSCOPED
    assert got["%slice-done.6"] == et.UNSCOPED
    assert et.scope_of("jit(f)/unembed/bld,vd->blv/dot_general") == "unembed"


def test_step_op_seconds_per_program_and_scope():
    planes = _planes()
    scopes = {"chunk": {"%fusion.1": "linear"},
              "decode": {"%custom-call.7": "attention"}}
    got = et.step_op_seconds(planes, [(1, 1), (0, 1)], scopes)
    # the loop op and the op of another program are left out
    assert got == {"chunk": {"linear": pytest.approx(10e-6)},
                   "decode": {"attention": pytest.approx(33e-6),
                              et.UNSCOPED: pytest.approx(10e-6)}}
    assert et.scope_share(got, "attention") == pytest.approx(33 / 53)
    assert et.scope_share(got, "kv_cache") == 0.0
    assert et.scope_share({}, "kv_cache") is None


def test_queue_wait_is_the_mean_over_admissions():
    deltas = [{"admissions": 2, "queue_wait_s": 0.5},
              {"admissions": 0, "queue_wait_s": 0.0},
              {"admissions": 1, "queue_wait_s": 0.25}]
    assert et.queue_wait_ms(deltas) == pytest.approx(250.0)
    assert et.queue_wait_ms(deltas[1:2]) is None
    # a parent without the counters reads nothing
    assert et.queue_wait_ms([{"decode_steps": 1}]) is None


def test_devtrace_reads_none_of_the_engine_spans(tmp_path):
    """A recorded trace holds the engine's spans inside the harness's; the
    planes every PR 13 metric is read from keep only the harness's, so each
    of those metrics reads what it read before the engine had spans."""
    from repro.configs import get_smoke
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    cfg = get_smoke("qwen2_1p5b")
    eng = ServingEngine(cfg, init_params(jax.random.key(1), cfg), slots=2,
                        max_len=32, prefill_chunk=4).warmup()
    eng.submit(Request(0, np.arange(1, 7, dtype=np.int32), max_new_tokens=2))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.trace"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    planes = tr.load(str(path))
    assert sorted(h.name for h in planes.host) == \
        ["bench.step", "bench.step", "bench.trace"]
    spans = et.load_spans(str(path))
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == 2 and {"engine.admit", "engine.launch",
                                "engine.sync", "engine.emit"} <= \
        {s.name for s in spans}
    for s in steps:
        assert any(h.start_ns <= s.start_ns and s.end_ns <= h.end_ns
                   for h in planes.host if h.name == "bench.step")
