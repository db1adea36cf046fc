#!/usr/bin/env python3
"""Chip smoke test: serve qwen2-1.5b at its full published widths on one TPU
through the Pallas kernels.

    python chip_smoke.py                # one chip: three serving phases
    python chip_smoke.py --four-chips   # four chips: the trainer path only

One chip. Builds `ServingEngine` for `get_config("qwen2_1p5b")` (28 layers,
d_model 1536, 12/2 heads, d_ff 8960, vocab 151936) with random weights drawn
from --seed, and drives it the way `repro.launch.serve` does (`init_params`,
optional `quantize_params`, `ServingEngine`, `warmup`, `submit`, `step`) in
three phases: (a) per-slot KV, (b) paged KV, (c) resident int8 weights. Each
phase checks that decode, chunked prefill and (c) the resident GEMMs ran
through the Pallas kernels, that no route was demoted, that every request got
its tokens with finite logits, and that the logits of the kernel route agree
with the engine's reference route under highest matmul precision.

Four chips (`--four-chips`). Runs `Trainer` for qwen2-1.5b at full width
(depth cut to TRAIN_LAYERS so the optimizer state fits a 2-way model shard)
on a (data=2, model=2) mesh for a few steps, then the same first batch on a
(data=1, model=4) mesh; checks that the step-0 losses agree, that the losses
are finite, and that all four devices hold bytes.

The script exits non-zero, and prints no result line, when JAX finds no TPU,
when any check fails, or when it runs outside a checkout of this repository.
Its last stdout line is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ARCH = "qwen2_1p5b"
SLOTS = 8
MAX_LEN = 2048                # 16 KV blocks of 128: decode visits many blocks
PREFILL_CHUNK = 32
PROMPT_LEN = (256, 1536)      # prompts span 8..48 prefill chunks
MAX_NEW = (16, 32)
REQUESTS_PER_PHASE = 10       # > SLOTS, so finished slots are refilled
COMPARE_CHUNKS = 12           # prefill launches compared against the ref route
# Kernel route vs ref route: max |logit error| / max |ref logit|. The kernel
# route runs at the TPU's default matmul precision (one bf16 pass, operand
# rounding 2^-9), the ref route at "highest"; 28 layers of ~7 matmuls each
# compound that to ~sqrt(196) * 2^-9 ~ 3e-2 at worst, so 5e-2 passes rounding
# and fails a wrong mask, position or scale (those move logits by O(1)).
LOGIT_REL_BOUND = 5e-2
# Resident int8 weights: the Pallas matmul_codes route also quantizes each
# activation row to int8 codes with a pow2 scale (rounding up to 2^-7, four
# times bf16's), which the ref route (dequantized weights, float
# activations) does not; the same compounding gives four times the bound.
# The kernel itself is checked tightly against its integer oracle instead.
LOGIT_REL_BOUND_A8 = 4 * LOGIT_REL_BOUND
# Pallas matmul_codes vs the decode-then-f32 oracle on the same codes at
# highest precision: int32 accumulation is exact, pow2 scales are exact, so
# only the oracle's f32 sums (up to 127^2 * 8960 > 2^24) round
GEMM_REL_BOUND = 1e-5
TRAIN_LAYERS = 8
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 256, 3
# (data=2, model=2) vs (data=1, model=4): same math, different reduction
# splits; f32 accumulation keeps the step-0 loss within this relative gap
LOSS_REL_TOL = 1e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# What ran: the Pallas kernels inside an engine step program
# ---------------------------------------------------------------------------

def step_kernels(closed_jaxpr) -> set:
    """Names ("<package>/<module>.<kernel>[<first operand dtype>]") of every
    pallas_call in a step program's jaxpr, nested jaxprs included."""
    from repro.analysis.hotloop import iter_eqns

    found = set()
    for eqn in iter_eqns(closed_jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        src = eqn.params["jaxpr"].debug_info.func_src_info
        func, _, where = src.partition(" at ")
        path = Path(where.rsplit(":", 1)[0])
        # past the scalar-prefetch operands, the first operand (q, or the
        # x codes of a GEMM) names the compute dtype
        skip = eqn.params["grid_mapping"].num_index_operands
        found.add(f"{path.parent.name}/{path.stem}.{func}"
                  f"[{eqn.invars[skip].aval.dtype}]")
    return found


# ---------------------------------------------------------------------------
# Kernel route vs reference route on the logits
# ---------------------------------------------------------------------------

def compare_with_ref(eng, rng, n_chunks: int = COMPARE_CHUNKS) -> dict:
    """Run `n_chunks` chunk-prefill launches and one decode launch through
    the engine's model step twice: under the engine's policy, and under its
    ref route (`policy.demoted()`) at highest matmul precision. Returns the
    max-abs and relative logit errors over the valid positions."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.models import transformer as T

    cfg, slots, chunk = eng.cfg, eng.slots, eng.prefill_chunk
    pool = eng.pool_stats()
    paged = (pool["pool_blocks"], pool["block_size"]) if pool["paged"] \
        else None
    # row b's prompt ends partway through a chunk for odd b: the varlen
    # kernel's padded tail is exercised, and one row sits idle (lengths 0)
    plen = np.asarray([n_chunks * chunk - (b * 5 if b % 2 else 0)
                       for b in range(slots)])
    plen[-1] = 0
    prompts = rng.randint(1, cfg.vocab, (slots, n_chunks * chunk + 1))

    def run(policy, precision):
        step = jax.jit(lambda p, c, t, n: T.decode_step(p, c, t, cfg,
                                                        lengths=n))
        caches = T.init_caches(cfg, batch=slots, max_len=eng.max_len,
                               paged=paged)
        outs = []
        with api.policy(policy), jax.default_matmul_precision(precision):
            for i in range(n_chunks + 1):
                if i < n_chunks:
                    lens = np.clip(plen - i * chunk, 0, chunk)
                    toks = prompts[:, i * chunk:(i + 1) * chunk]
                else:               # one decode step at each row's frontier
                    lens = (plen > 0).astype(np.int64)
                    toks = prompts[np.arange(slots), plen][:, None]
                logits, caches = step(eng.params, caches,
                                      jnp.asarray(toks, jnp.int32),
                                      jnp.asarray(lens, jnp.int32))
                logits = np.asarray(logits, np.float32)
                outs.append([logits[b, :lens[b]] for b in range(slots)])
        return outs

    pol = eng.policy if eng.policy is not None else api.default_policy
    got = run(pol, "default")
    want = run(pol.demoted(), "highest")
    first = max_err = ref_max = 0.0
    for i, (g_rows, w_rows) in enumerate(zip(got, want)):
        for g, w in zip(g_rows, w_rows):
            if g.size == 0:
                continue
            check(np.isfinite(g).all(), f"non-finite kernel-route logits "
                                        f"at launch {i}")
            err = float(np.abs(g - w).max())
            max_err = max(max_err, err)
            ref_max = max(ref_max, float(np.abs(w).max()))
            if i == 0:
                first = max(first, err)
    return {"max_abs_err": max_err, "first_chunk_max_abs_err": first,
            "ref_max_abs": ref_max, "rel_err": max_err / ref_max,
            "launches": n_chunks + 1}


# ---------------------------------------------------------------------------
# Serving phases (one chip)
# ---------------------------------------------------------------------------

def serve_phase(name: str, cfg, params, policy, *, slots: int = SLOTS,
                max_len: int = MAX_LEN, prompt_len=PROMPT_LEN,
                max_new=MAX_NEW, n_requests: int = REQUESTS_PER_PHASE,
                compare_chunks: int = COMPARE_CHUNKS, seed: int = 0,
                attn_kernel: str = "_dense_kernel", weight_kernels=(),
                logit_bound: float = LOGIT_REL_BOUND, **engine_kw) -> dict:
    """Build one engine through the public entry points, serve a batch of
    requests to completion, and check routes, kernels, counters and logits.
    attn_kernel: the attention kernel the decode (flash_attention/decode)
    and chunk (flash_attention/prefill) step programs must call;
    weight_kernels: kernels both step programs must call besides."""
    from repro.serving import Request, ServingEngine

    eng = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                        policy=policy, prefill_chunk=PREFILL_CHUNK,
                        **engine_kw)
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = time.perf_counter() - t0
    routes = {"decode": eng.decode_route(), "prefill": eng.prefill_route(),
              "weights": eng.weight_route()}
    kernels = {w: sorted(step_kernels(eng.step_trace(w)))
               for w in eng.step_widths()}
    log(f"{name}: warmup (decode + chunk({PREFILL_CHUNK}) compile) "
        f"{warm_s:.3f}s; routes {routes}")
    log(f"{name}: step kernels {kernels}")
    check(routes["decode"] == "pallas-decode",
          f"{name}: decode route is {routes['decode']}")
    check(routes["prefill"] == "pallas-prefill",
          f"{name}: prefill route is {routes['prefill']}")
    for w, names in kernels.items():
        module = "decode" if w == 1 else "prefill"
        for want in (f"flash_attention/{module}.{attn_kernel}[",
                     *weight_kernels):
            check(any(want in k for k in names),
                  f"{name}: width-{w} step has no {want} kernel ({names})")

    rng = np.random.RandomState(seed)
    reqs = []
    for rid in range(n_requests):
        plen = int(rng.randint(prompt_len[0], prompt_len[1] + 1))
        prompt = rng.randint(1, cfg.vocab, plen).astype(np.int32)
        reqs.append(Request(rid, prompt, max_new_tokens=int(
            rng.randint(max_new[0], max_new[1] + 1))))
        check(eng.submit(reqs[-1]), f"{name}: request {rid} rejected")
    t0 = time.perf_counter()
    while eng.pending():
        eng.step()
    serve_s = time.perf_counter() - t0
    st = eng.stats
    toks = sum(len(r.out_tokens) for r in reqs)
    log(f"{name}: served {len(reqs)} requests, {st.prefill_tokens} prompt "
        f"tokens, {toks} new tokens in {serve_s:.3f}s host wall "
        f"({st.prefill_chunk_calls} chunk launches, {st.decode_steps} decode "
        f"launches)")
    check(st.demotions == 0 and not eng.degraded_routes(),
          f"{name}: routes demoted: {eng.degraded_routes()}")
    # the fused per-row health guard checks every logit of every occupied
    # row on every launch; any non-finite value quarantines the row
    check(st.quarantines == 0 and st.failed_requests == 0,
          f"{name}: {st.quarantines} quarantines, {st.failed_requests} "
          f"failed requests (non-finite logits)")
    for r in reqs:
        check(r.status == "done" and len(r.out_tokens) == r.max_new_tokens,
              f"{name}: request {r.rid} {r.status} with "
              f"{len(r.out_tokens)}/{r.max_new_tokens} tokens")

    cmp = compare_with_ref(eng, np.random.RandomState(seed + 1),
                           n_chunks=compare_chunks)
    log(f"{name}: logits vs ref route (highest precision) over "
        f"{cmp['launches']} launches: max-abs err {cmp['max_abs_err']!r} "
        f"(first prefill chunk {cmp['first_chunk_max_abs_err']!r}), ref "
        f"max-abs {cmp['ref_max_abs']!r}, relative {cmp['rel_err']!r} "
        f"(bound {logit_bound})")
    check(cmp["rel_err"] <= logit_bound,
          f"{name}: relative logit error {cmp['rel_err']} > {logit_bound}")
    return {"phase": name, "warmup_s": warm_s, "serve_s": serve_s,
            "prompt_tokens": st.prefill_tokens, "new_tokens": toks,
            "routes": routes, "kernels": kernels, "logits": cmp}


def resident_gemm_check(cfg, policy, fmt: str, *, rows: int, seed: int = 0):
    """One (rows, d_model) x (d_model, d_ff) resident-weight GEMM through
    `api.ops.matmul_codes` under `policy`, against `aio_matmul_ref` on the
    same activation and weight codes at highest precision."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.core import formats as F
    from repro.kernels.aio_matmul import aio_matmul_ref

    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (rows, cfg.d_model), jnp.float32)
    w = jax.random.normal(kw, (cfg.d_model, cfg.d_ff), jnp.float32)
    qw = F.quantize_weight(w, fmt)
    got = np.asarray(api.ops.matmul_codes(x, qw, policy=policy))
    xq, xs = F.quantize_scaled(x, F.REGISTRY[fmt], axis=1, pow2=True)
    wcodes = F.unpack_int4(qw.codes.T, k=qw.k).T if fmt == "int4" \
        else qw.codes
    with jax.default_matmul_precision("highest"):
        want = np.asarray(aio_matmul_ref(xq, wcodes, xs,
                                         qw.scale.reshape(1, -1), mode=fmt))
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    log(f"matmul_codes {fmt} ({policy.impl()}) {x.shape} x {w.shape}: "
        f"relative error vs integer oracle {rel!r} (bound {GEMM_REL_BOUND})")
    check(policy.impl() == "pallas", f"matmul_codes ran {policy.impl()}")
    check(np.isfinite(got).all() and rel <= GEMM_REL_BOUND,
          f"matmul_codes {fmt}: relative error {rel} > {GEMM_REL_BOUND}")


def serve_phases(cfg, policy, *, seed: int = 0, **kw) -> list:
    """Phases (a) per-slot KV, (b) paged KV, (c) resident int8 weights,
    over one set of random weights drawn from `seed`."""
    import jax
    from repro.models import init_params, quantize_params

    params = init_params(jax.random.key(seed), cfg)
    results = [
        serve_phase("a/per-slot", cfg, params, policy, seed=seed, **kw),
        serve_phase("b/paged", cfg, params, policy, seed=seed,
                    attn_kernel="_paged_dense_kernel", paged=True, **kw),
    ]
    gc.collect()
    for fmt in ("int8", "int4"):
        resident_gemm_check(cfg, policy, fmt,
                            rows=kw.get("slots", SLOTS) * PREFILL_CHUNK,
                            seed=seed)
    # as the serve launcher does: quantize at load, donating the dense tree
    params = jax.jit(lambda p: quantize_params(p, "int8"),
                     donate_argnums=(0,))(params)
    results.append(serve_phase(
        "c/resident-int8", cfg, params, policy, seed=seed,
        weight_kernels=("aio_matmul/kernel._mm_kernel[int8]",),
        logit_bound=LOGIT_REL_BOUND_A8, **kw))
    check(results[-1]["routes"]["weights"] == "resident-int8",
          f"weights route {results[-1]['routes']['weights']}")
    return results


# ---------------------------------------------------------------------------
# Trainer across meshes (four chips)
# ---------------------------------------------------------------------------

def train_on_mesh(cfg, model_axis: int, steps: int, *, batch: int,
                  seq: int, seed: int = 0) -> dict:
    """`steps` trainer steps on a (data=n/model_axis, model=model_axis)
    mesh of every local device; returns losses and per-device bytes."""
    import jax
    from repro.data import DataConfig, SyntheticLM
    from repro.dist.sharding import set_mesh
    from repro.launch.mesh import make_local_mesh
    from repro.runtime import Trainer, TrainerConfig

    mesh = make_local_mesh(model=model_axis)
    with tempfile.TemporaryDirectory() as ckpt, set_mesh(mesh):
        tcfg = TrainerConfig(ckpt_dir=ckpt, ckpt_every=10 ** 9,
                             total_steps=max(steps, 2), warmup=1)
        tr = Trainer(cfg, tcfg, mesh, key=jax.random.key(seed))
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, batch=batch,
                                      seq=seq, seed=seed))
        t0 = time.perf_counter()
        tr.run(data, steps)
        wall = time.perf_counter() - t0
        used = [int(d.memory_stats()["bytes_in_use"])
                if d.memory_stats() else 0 for d in mesh.devices.flat]
        losses = [m["loss"] for m in tr.metrics_log]
    del tr
    gc.collect()
    return {"mesh": dict(mesh.shape), "losses": losses,
            "bytes_in_use": used, "wall_s": wall}


def train_meshes(cfg, *, steps: int = TRAIN_STEPS, batch: int = TRAIN_BATCH,
                 seq: int = TRAIN_SEQ, seed: int = 0) -> dict:
    """(data=2, model=2) for `steps` steps vs (data=1, model=4) on the same
    first batch; checks loss agreement, finiteness and memory spread."""
    import jax

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found "
                                   f"{len(jax.devices())}")
    log(f"trainer: {cfg.n_layers} layers at full width, batch {batch} x "
        f"{seq} tokens")
    a = train_on_mesh(cfg, 2, steps, batch=batch, seq=seq, seed=seed)
    log(f"train {a['mesh']}: losses {a['losses']}, bytes_in_use per device "
        f"{a['bytes_in_use']}, {a['wall_s']:.3f}s host wall")
    b = train_on_mesh(cfg, 4, 1, batch=batch, seq=seq, seed=seed)
    log(f"train {b['mesh']}: losses {b['losses']}, bytes_in_use per device "
        f"{b['bytes_in_use']}")
    check(all(np.isfinite(a["losses"] + b["losses"])),
          f"non-finite losses {a['losses']} {b['losses']}")
    gap = abs(a["losses"][0] - b["losses"][0]) / abs(b["losses"][0])
    log(f"step-0 loss (2,2) {a['losses'][0]!r} vs (1,4) {b['losses'][0]!r}: "
        f"relative gap {gap!r} (tolerance {LOSS_REL_TOL})")
    check(gap <= LOSS_REL_TOL, f"step-0 losses disagree by {gap}")
    used = a["bytes_in_use"]
    check(max(used) > 0 and min(used) >= 0.5 * max(used),
          f"device memory not spread over the mesh: {used}")
    return {"data2_model2": a, "data1_model4": b, "loss_rel_gap": gap}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the trainer path on a 2x2 and a 1x4 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX backend is {jax.default_backend()!r}, not "
              f"a TPU; nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import api
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    dev = jax.devices()[0]
    cfg = get_config(ARCH)
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}")
    if args.four_chips:
        train_meshes(dataclasses.replace(cfg, n_layers=TRAIN_LAYERS),
                     seed=args.seed)
    else:
        # the serve launcher's default policy: backend "auto", which must
        # resolve to the kernels on this TPU backend
        results = serve_phases(cfg, api.ExecutionPolicy(), seed=args.seed)
        log(f"tokens served: {sum(r['new_tokens'] for r in results)} new, "
            f"{sum(r['prompt_tokens'] for r in results)} prompt")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
