"""Serving launcher — single- or multi-tenant.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_1p5b --smoke \\
        --requests 6 --max-new 8
    PYTHONPATH=src python -m repro.launch.serve --multi-tenant --smoke

--multi-tenant runs the paper's §VI-C scenario shape: two engines (a
captioning-style tenant and a classification-style tenant stand-in) on
mesh partitions chosen by the morphable scheduler.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from .. import api
from ..configs import get_config, get_smoke
from ..models import init_params
from ..serving import Request, ServingEngine
from ..tenancy import MorphableScheduler, Tenant
from .compile_cache import enable_compile_cache


def _occupancy_line(eng: ServingEngine) -> str:
    cells = ["--" if o is None else f"r{o['rid']}+{o['generated']}"
             for o in eng.occupancy()]
    return f"slots [{' '.join(cells)}] util {eng.utilization():.2f}"


def _run_engine(arch: str, smoke: bool, n_requests: int, max_new: int,
                seed: int = 0, policy: api.ExecutionPolicy = None,
                sched=None, tenant: str = None, weight_format: str = None,
                prefill_chunk: int = 32, max_queue: int = None,
                deadline_steps: int = None, ttl_s: float = None,
                paged: bool = False, block_size: int = 16,
                pool_blocks: int = None, swap_watermark: float = 1.0,
                priorities: list = None):
    cfg = get_smoke(arch) if smoke else get_config(arch)
    if policy is not None and policy.format != "bf16":
        # the policy's format plane reaches the model through its
        # QuantPolicy: every linear fake-quants acts+weights to the format
        import dataclasses
        from ..models.layers import QuantPolicy
        cfg = dataclasses.replace(cfg, quant=QuantPolicy(
            activations=policy.format, weights=policy.format))
    params = init_params(jax.random.key(seed), cfg)
    if weight_format not in (None, "none"):
        # quantize at load and DONATE the dense pytree into the pass: the
        # f32 weights are freed as the codes are built (untouched leaves
        # alias through), so HBM never holds weights twice. The engine then
        # serves from the code pytree — no dense weight in its hot loop.
        from ..models import quantize_params
        params = jax.jit(lambda p: quantize_params(p, weight_format),
                         donate_argnums=(0,))(params)
    eng = ServingEngine(cfg, params, slots=4, max_len=128, policy=policy,
                        prefill_chunk=prefill_chunk, max_queue=max_queue,
                        deadline_steps=deadline_steps, ttl_s=ttl_s,
                        paged=paged, block_size=block_size,
                        pool_blocks=pool_blocks,
                        swap_watermark=swap_watermark)
    # compile the decode- and chunk-shaped step programs up front: the first
    # request pays zero compile stall, and the fixed chunk shape means these
    # two traces are ALL the engine ever compiles
    t_warm = time.time()
    eng.warmup()
    print(f"[serve:{arch}] warmup traced decode + chunk({prefill_chunk}) "
          f"prefill in {time.time() - t_warm:.2f}s "
          f"(prefill route {eng.prefill_route()}, "
          f"decode route {eng.decode_route()})")
    if weight_format not in (None, "none"):
        print(f"[serve:{arch}] weight residency: {eng.weight_route()}")
    if sched is not None and tenant is not None:
        sched.attach_engine(tenant, eng)
    rng = np.random.RandomState(seed)
    t0 = time.time()
    for rid in range(n_requests):
        prompt = rng.randint(1, cfg.vocab, rng.randint(3, 10)).astype(np.int32)
        prio = priorities[rid % len(priorities)] if priorities else 0
        if not eng.submit(Request(rid, prompt, max_new_tokens=max_new,
                                  priority=prio)):
            print(f"[serve:{arch}] request {rid} REJECTED "
                  f"(queue full at {max_queue})")
    # drive step-by-step so per-slot occupancy is observable mid-flight
    while eng.pending():
        eng.step()
        if eng.stats.decode_steps in (1, max(2, max_new // 2)):
            print(f"[serve:{arch}] step {eng.stats.decode_steps}: "
                  f"{_occupancy_line(eng)}")
    done = eng.finished
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    st = eng.stats
    print(f"[serve:{arch}] {len(done)} requests, {toks} tokens, "
          f"{dt:.2f}s ({toks/dt:.1f} tok/s; {st.decode_steps} decode steps, "
          f"{st.prefill_chunk_calls} chunked prefills)")
    # the fault surface: zero everywhere on a healthy run, and the first
    # place to look when outputs or latency drift
    print(f"[serve:{arch}] fault counters: quarantines={st.quarantines} "
          f"demotions={st.demotions} timeouts={st.timeouts} "
          f"rejected={st.rejected_submits} failed={st.failed_requests}")
    if paged:
        ps = eng.pool_stats()
        print(f"[serve:{arch}] pool: {ps['pool_blocks']} blocks "
              f"(block_size={ps['block_size']}, watermark="
              f"{ps['swap_watermark']:.2f} -> soft cap "
              f"{ps['watermark_blocks']} blocks) "
              f"evictions={ps['evictions']} skips={ps['eviction_skips']} "
              f"deferred={ps['deferred_admissions']}")
        print(f"[serve:{arch}] swap: preemptions={ps['preemptions']} "
              f"out={ps['swap_outs']} in={ps['swap_ins']} "
              f"bytes_out={ps['swap_bytes_out']} "
              f"bytes_in={ps['swap_bytes_in']} "
              f"host_resident={ps['host_blocks']} blk "
              f"({ps['host_bytes']} B)")
    for ev in eng.degraded_routes():
        print(f"[serve:{arch}] DEGRADED at step {ev['step']}: "
              f"{ev['from']} -> {ev['to']} ({ev['error']})")
    if st.demotions:
        # the engine kept serving on the ref route; the launcher still
        # fails, so a broken kernel path never passes as a healthy run
        raise SystemExit(f"[serve:{arch}] {st.demotions} route demotion(s): "
                         f"the requested kernels did not run")
    return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_1p5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--multi-tenant", action="store_true")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "ref"),
                    help="ExecutionPolicy backend plane; 'auto' is "
                         "'pallas' on a TPU backend and 'ref' elsewhere; "
                         "'pallas' routes "
                         "decode-step attention to the flash-decode kernel, "
                         "chunked admission prefill to the varlen "
                         "flash-prefill kernel, and 128-aligned prefill to "
                         "the flash kernel (see api.ops.attention_route)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="tokens a new prompt advances per admission launch "
                         "(interleaved with decode steps): small chunks keep "
                         "resident slots generating smoothly while a long "
                         "prompt admits, large chunks admit in fewer "
                         "launches; greedy outputs identical either way")
    ap.add_argument("--format", default="bf16",
                    choices=("bf16", "fp8a", "fp8b", "int8", "int4"),
                    help="AIO format: applied to every linear via the model's "
                         "QuantPolicy (bf16 = no fake-quant)")
    ap.add_argument("--weight-format", default="none",
                    choices=("none", "int4", "int8", "fp8a", "fp8b"),
                    help="make Linear weights RESIDENT in this AIO format: "
                         "quantized once at load (dense pytree donated away) "
                         "and served as packed codes through "
                         "api.ops.matmul_codes — int4 is 8x less HBM weight "
                         "traffic than f32, greedy outputs byte-identical to "
                         "the fake-quant path")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged block-pool KV cache (prefix "
                         "sharing + CoW + host-swap under pressure)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per physical KV block (--paged)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="physical blocks in the pool (--paged; default "
                         "sized so every slot can reach max_len)")
    ap.add_argument("--swap-watermark", type=float, default=1.0,
                    help="high-watermark fraction of the pool above which "
                         "admission evicts cold prefixes and then PREEMPTS "
                         "lower-priority rows (live KV swapped to host, "
                         "byte-identical resume); 1.0 = swap only when a "
                         "reservation cannot be met at all")
    ap.add_argument("--priority", default=None,
                    help="comma-separated priority cycle assigned to "
                         "submitted requests, e.g. '0,1' alternates low/"
                         "high; higher preempts lower under pool pressure")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue: submits beyond this "
                         "depth are REJECTED (backpressure) instead of "
                         "queueing without limit")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="per-request deadline in engine steps; expired "
                         "requests finish with status TIMEOUT")
    ap.add_argument("--ttl-s", type=float, default=None,
                    help="per-request wall-clock TTL in seconds")
    args = ap.parse_args()

    enable_compile_cache()
    policy = api.ExecutionPolicy(format=args.format, backend=args.backend)
    priorities = ([int(x) for x in args.priority.split(",")]
                  if args.priority else None)
    if not args.multi_tenant:
        _run_engine(args.arch, args.smoke, args.requests, args.max_new,
                    policy=policy, weight_format=args.weight_format,
                    prefill_chunk=args.prefill_chunk,
                    max_queue=args.max_queue,
                    deadline_steps=args.deadline_steps, ttl_s=args.ttl_s,
                    paged=args.paged, block_size=args.block_size,
                    pool_blocks=args.pool_blocks,
                    swap_watermark=args.swap_watermark,
                    priorities=priorities)
        return

    # §VI-C-shaped scenario: two tenants, morphable mesh partitions
    sched = MorphableScheduler()
    parts = sched.reconfigure([
        Tenant("captioning", weight_rows=64, weight_cols=512, fmt="int8"),
        Tenant("classification", weight_rows=64, weight_cols=768, fmt="int8"),
    ])
    print(f"[serve] fusion plan: {sched.plan.describe()}; partitions: "
          f"{[p.tenants for p in parts]}")
    for tenant, arch in (("captioning", "olmoe_1b_7b"),
                         ("classification", "qwen2_1p5b")):
        sched.run(tenant, _run_engine, arch, True, args.requests,
                  args.max_new, policy=policy, sched=sched, tenant=tenant,
                  weight_format=args.weight_format,
                  prefill_chunk=args.prefill_chunk)
    for name, occ in sched.occupancy().items():
        print(f"[serve] tenant {name}: final {len(occ)} slots, "
              f"{sum(o is not None for o in occ)} busy")


if __name__ == "__main__":
    main()
