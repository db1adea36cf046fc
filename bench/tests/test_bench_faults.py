"""The harness driven on the CPU at a tiny size, past its look for a chip:
a sound run is correct, a run with the timed path broken underneath is not,
and neither is the control (the reference in bfloat16 activations, read
over the same sample).

The tiny model is the program's qwen2_1p5b smoke preset (2 layers,
d_model 64), whose configuration file states the precision the program
runs at on the CPU (float32 matmuls, a bfloat16 KV cache). The runs take
their time from a clock that advances 2 ms per reading, so that the steps,
the requests finished and the sample compared do not depend on the host's
speed. The limit on the mean gap sits between the readings of sound runs
(over seeds 1-16 it was 0.0 on every seed: the reference rounds where the
program does) and the control's (the smallest was 1.53e-5, at seed 6).
"""
import itertools
import json
from pathlib import Path

import pytest

from bench import harness

DATA = Path(__file__).resolve().parent / "data"
LIMIT = 5e-6


def _run(seed, control=False):
    from repro.configs import get_smoke

    b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.Cell(
        "tiny", 1, json.loads((DATA / "tiny.json").read_text()),
        json.loads((DATA / "tiny_mix.json").read_text()),
        {"mean_logit_gap": LIMIT}, b["end_to_end"], [])
    ticks = itertools.count()
    return harness.run_cell(cell, seed, 1.5, False, t_start=0.0,
                            clock=lambda: next(ticks) * 2e-3,
                            program_cfg=get_smoke("qwen2_1p5b"),
                            require_tpu=False, control=control,
                            log=lambda m: None)


def test_a_sound_run_is_correct():
    res, extra = _run(4)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"ttft_p90_ms", "ttft_p90_ms.batch", "itl_p95_ms",
            "output_tok_s", "setup_s"} <= set(res["metrics"])
    assert extra["comparison"]["tokens"] >= 100
    assert all(s.consistent for s in extra["ctx"].log.steps)
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    from repro.serving import ServingEngine

    step = ServingEngine._step_program

    def broken(self, p, c, t, lens, m):
        logits, caches, health = step(self, p, c, t, lens, m)
        if fault == "state_unchanged":          # the cache never advances
            return logits, c, health
        # every row's next token is altered where it is produced
        return logits.at[..., 7].add(1e3), caches, health

    monkeypatch.setattr(ServingEngine, "_step_program", broken)
    res, _ = _run(2)
    assert res["correct"] is False
    assert res["checks"]["mean_logit_gap"]["value"] > 10 * LIMIT


def test_the_control_is_not_correct():
    res, extra = _run(6, control=True)
    assert res["correct"] is True
    control = extra["comparison"]["control"]
    assert not harness._passes({"value": control["mean_gap"],
                                "limit": LIMIT})
