#!/usr/bin/env python3
"""Find the rate an open-loop cell sustains: run the cell at each of a few
fixed rates, in one process, and report per rate the client-side tails and
whether the backlog grew over the window.

    python3 bench/sweep.py --workload qwen2_1p5b.chat --seed <n> \\
        --seconds 50 --rates 0.5,0.7,0.9,1.1

A cell's rate is fixed in its traffic file; this is how that number was
found, and how a later benchmark change finds it again. It needs the chip,
as a run does.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def waiting(records, t):
    """Requests started by t that had no first token by t."""
    return sum(r.start <= t and not (r.token_times and r.token_times[0] <= t)
               for r in records)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import dataclasses
    import jax
    from bench import harness, stats
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload, ROOT)
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate))
        res, extra = harness.run_cell(
            c, args.seed, args.seconds, False, t_start=time.perf_counter(),
            clock=time.perf_counter, log=lambda m: None)
        log = extra["ctx"].log
        recs = list(log.records.values())
        w0, w1 = log.window
        ttft = stats.ttft_ms(recs, w0, w1, until=log.closed)
        firsts = sum(bool(r.token_times) and w0 < r.token_times[0] <= w1
                     for r in recs)
        print(json.dumps({
            "rate_per_s": rate, "arrived": len(ttft), "first_tokens": firsts,
            "waiting_at_start": waiting(recs, w0),
            "waiting_at_end": waiting(recs, w1),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "itl_p95_ms": stats.percentile(stats.itl_ms(recs, w0, w1), 95),
            "output_tok_s": stats.tokens_in(recs, w0, w1) / (w1 - w0),
            "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
