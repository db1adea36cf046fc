"""BENCHMARK.json and the files the harness finds by name."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_and_metric_resolves():
    b = _bench()
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        assert cell.spec["name"] == w["config"]
        assert "mean_logit_gap" in cell.limit
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.reader(m["name"]))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer


def test_the_file_keeps_to_the_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"]: w for w in b["workloads"]}
    confs = {c["name"] for c in b["configs"]}
    assert {w["config"] for w in cells.values()} == confs
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert "workloads" not in moved or w in moved["workloads"], \
                (m["name"], w)
    assert len(json.dumps(b)) < 64 * 1024


def test_a_new_cell_needs_only_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    mix = {"loop": "open", "rate_per_s": 1.0, "warm_s": 1,
           "prompt_len": {"dist": "uniform", "min": 8, "max": 16},
           "output_len": {"dist": "uniform", "min": 4, "max": 8},
           "max_len": 128}
    (tmp_path / "bench/traffic/burst.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/qwen2_1p5b.burst.json").write_text(
        json.dumps({"mean_logit_gap": 0.5}))
    (tmp_path / "bench/metrics/queue_wait_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    b["workloads"].append({"name": "qwen2_1p5b.burst", "config": "qwen2_1p5b",
                           "traffic": "burst", "chips": 1, "why": "bursts"})
    b["per_layer"].append({"name": "queue_wait_ms", "unit": "ms",
                           "better": "lower", "source": "program_counter",
                           "layer": "scheduler", "moves": "itl_p95_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = harness.load_cell("qwen2_1p5b.burst", tmp_path)
    assert cell.mix == mix and cell.limit == {"mean_logit_gap": 0.5}
    assert "queue_wait_ms" in [m["name"] for m in cell.per_layer]
    assert harness.reader("queue_wait_ms", tmp_path / "bench")(None) == 1.5
    with pytest.raises(harness.HarnessError, match="no workload"):
        harness.load_cell("qwen2_1p5b.absent", tmp_path)
