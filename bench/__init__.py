"""Chip benchmark of the serving path: one harness, driven by data.

`BENCHMARK.json` (repository root) names the cells. A cell is a model
configuration (`bench/configs/<name>.json`) under a traffic mix
(`bench/traffic/<mix>.json`); every metric is read by its own module
(`bench/metrics/<name>.py`), and every cell's correctness limit sits in
`bench/limits/<cell>.json`. A new cell or metric is new files plus a new
entry in `BENCHMARK.json`; no harness code changes.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
