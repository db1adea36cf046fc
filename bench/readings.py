#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the logit gaps of the program
and of the control, on several seeds, in one process.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 --seconds 50

Each seed runs the cell as `bench/run.py` does (its traffic, its window)
and compares the served tokens with the reference at the configuration's
precision; over the same sample it reads the control, the reference one
step below that precision, which has to read above the limit. One JSON line
per seed. It needs the chip, as a run does; the benchmark's own runs never
read the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        res, extra = harness.run_cell(
            cell, seed, args.seconds, False, t_start=time.perf_counter(),
            clock=time.perf_counter, control=True, log=lambda m: None)
        print(json.dumps({"seed": seed, **extra["comparison"],
                          "metrics": res["metrics"],
                          "counters": extra["counters"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
