#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. `--trace 0` measures the cell's end-to-end metrics with the
profiler off; `--trace 1` profiles the first seconds of the window and
reports the per-layer metrics. The last line of standard output is one JSON
object: correct, attempted, failed, metrics, device, (breakdown), checks.
The last lines of standard error give each number compared beside its
limit. Without a TPU, with fewer chips than the cell asks for, or with a
route that is not the Pallas one, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout's root (for `bench`) and `src` (the system under test), in
# place of this script's own directory
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


class CompileCounter:
    """Counts JAX's trace and compile events (a jit cache miss each)."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration_secs: float, **kwargs):
        if event.startswith("/jax/core/compile/"):
            self.n += 1

    def count(self) -> int:
        return self.n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program, however quick to compile, is kept: only a cell's first
    # run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)

    def log(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    log(f"compile cache: {cache_dir}")
    try:
        cell = harness.load_cell(args.workload, ROOT)
        result, extra = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, clock=time.perf_counter,
            compile_count=counter.count, log=log)
    except harness.HarnessError as err:
        log(f"not run: {err}")
        return 2
    cmp, counters = extra["comparison"], extra["counters"]
    log(f"comparison: {json.dumps(cmp)}")
    log(f"counters: {json.dumps(counters)}")
    for name, c in result["checks"].items():
        bound = f"limit {c['limit']}" if "limit" in c \
            else f"at least {c['at_least']}"
        log(f"check {name} {c['value']!r} {bound}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
