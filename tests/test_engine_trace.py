"""The serving engine's own trace records: host spans in the profiler's
trace, named scopes in the step program's compiled HLO, and the admission
counters of `EngineStats`."""
import dataclasses
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import init_params
from repro.models.layers import QuantPolicy, linear
from repro.serving import Request, ServingEngine
from repro.serving import engine as engine_mod

CHILDREN = ("engine.admit", "engine.launch", "engine.sync", "engine.emit")


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke("qwen2_1p5b")
    return cfg, init_params(jax.random.key(3), cfg)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 500, n).astype(np.int32)


def _engine_spans(path):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        {k: v for k, v in e.stats}) for e in line.events
                       if e.name.startswith("engine."))
    return out


def test_a_traced_step_nests_its_spans(model, tmp_path):
    cfg, params = model
    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        prefill_chunk=4).warmup()
    for rid in range(2):
        eng.submit(Request(rid, _prompt(6, rid), max_new_tokens=3))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        eng.step()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = _engine_spans(str(path))
    steps = sorted(s for s in spans if s[0] == "engine.step")
    assert [s[3]["step"] for s in steps] == [0, 1, 2]
    kids = [s for s in spans if s[0] in CHILDREN]
    assert {s[0] for s in kids} == set(CHILDREN)
    for name, a, b, _ in kids:
        assert any(s[1] <= a and b <= s[2] for s in steps), name
    # step 0 admits both requests and launches their first chunk; step 1
    # finishes the prompts (a chunk launch that syncs) and decodes
    admit = sorted((s for s in spans if s[0] == "engine.admit"),
                   key=lambda s: s[1])
    assert admit[0][3] == {"rids": "0 1"} and admit[1][3] == {}
    kinds = [s[3]["kind"] for s in sorted(spans, key=lambda s: s[1])
             if s[0] == "engine.launch"]
    assert kinds == ["chunk", "chunk", "decode", "decode"]
    assert sum(s[0] == "engine.sync" for s in spans) == 3


def test_spans_cost_nothing_to_build_when_the_profiler_is_off(model,
                                                              monkeypatch):
    calls = []

    def args():
        calls.append(1)
        return {"step": 0}

    assert not jax.profiler.TraceAnnotation.is_enabled()
    with engine_mod._span("engine.step", args):
        pass
    assert calls == []


def test_admissions_and_queue_wait(model, monkeypatch):
    """One slot, three requests queued at once: each waits for the slot
    until the step that admits it."""
    cfg, params = model
    clock = [0.0]
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        monotonic=lambda: clock[0], sleep=lambda s: None))
    eng = ServingEngine(cfg, params, slots=1, max_len=32)
    for rid in range(3):
        assert eng.submit(Request(rid, _prompt(3, rid), max_new_tokens=2))
    admitted_at = {}
    for k in range(20):
        if not eng.pending():
            break
        clock[0] = 10.0 * (k + 1)
        before = eng.stats.admissions
        eng.step()
        occ = eng.occupancy()[0]
        if eng.stats.admissions > before:
            rid = occ["rid"] if occ is not None else eng.finished[-1].rid
            admitted_at[rid] = clock[0]
    assert sorted(admitted_at) == [0, 1, 2]
    assert eng.stats.admissions == 3
    assert eng.stats.queue_wait_s == pytest.approx(
        sum(admitted_at.values()))
    # each request is served within the step that admits it
    assert eng.stats.queue_wait_s == pytest.approx(10.0 + 20.0 + 30.0)


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _scopes(cfg, params) -> set:
    eng = ServingEngine(cfg, params, slots=2, max_len=32, prefill_chunk=4)
    return {p for n in _op_names(eng.step_hlo(4)) for p in n.split("/")}


def test_the_step_program_carries_its_scopes(model):
    cfg, params = model
    parts = _scopes(cfg, params)
    for scope in ("attention", "linear", "unembed", "health"):
        assert scope in parts, scope
    # the per-slot cache is written inside the attention op; an int8
    # cache is still quantized and written by XLA, under `kv_cache`
    qcfg = dataclasses.replace(cfg, kv_quant=True)
    assert "kv_cache" in _scopes(qcfg, init_params(jax.random.key(3), qcfg))


def test_a_weight_conversion_in_linear_is_scoped():
    p = {"w": jnp.ones((16, 8), jnp.float32)}
    x = jnp.ones((2, 16), jnp.float32)
    hlo = jax.jit(lambda p, x: linear(p, x, QuantPolicy(weights="int8"))
                  ).lower(p, x).compile().as_text()
    assert any("/weight_cast/" in n for n in _op_names(hlo))


def test_the_serve_launcher_prints_the_mean_queue_wait(capsys):
    from repro.api import ExecutionPolicy
    from repro.launch import serve

    serve._run_engine("qwen2_1p5b", True, 3, 2,
                      policy=ExecutionPolicy(backend="ref"))
    out = capsys.readouterr().out
    assert re.search(r"admissions=3 mean queue wait \d+\.\d\d ms", out)
