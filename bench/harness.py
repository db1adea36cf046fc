"""One run of one cell: build the engine as the serve launcher does, warm
it, offer the cell's traffic for the window, read the metrics, and check
what was served against the plain reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
from pathlib import Path
from typing import Callable, Optional

from . import correct, devtrace as tr, loadgen, work
from . import weights as W
from .driver import Driver

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_S = 10.0          # seconds of the window a --trace 1 run profiles
GRACE_S = 60.0          # longest wait past the window for first tokens
TAIL_S = GRACE_S        # schedule beyond the window (open loops)
# roofline kernel -> (step program kind, the Pallas kernel it must be)
ROOFLINE_KERNELS = {"flash_decode": ("decode", "flash_attention/decode."),
                    "flash_prefill": ("chunk", "flash_attention/prefill.")}


class HarnessError(RuntimeError):
    """The run cannot be measured as the cell asks (no chip, a demoted or
    non-Pallas route, a configuration the program does not run)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict
    mix: dict
    limit: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell `workload` of `root/BENCHMARK.json`, with its configuration,
    traffic mix and limit files, found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise HarnessError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    bench_dir = root / "bench"
    return Cell(
        name=workload, chips=int(cell["chips"]),
        spec=json.loads((root / conf["file"]).read_text()),
        mix=json.loads((bench_dir / "traffic" /
                        f"{cell['traffic']}.json").read_text()),
        limit=json.loads((bench_dir / "limits" /
                          f"{workload}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reader(name: str, bench_dir: Path = BENCH) -> Callable:
    """The `read(ctx)` of `bench/metrics/<name>.py`."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def program_config(spec: dict, cfg=None):
    """The program's ModelConfig for the configuration file, checked
    against it: the file holds the configuration as it is run."""
    from repro.configs import get_config

    cfg = get_config(spec["program_arch"]) if cfg is None else cfg
    arch = spec["architecture"]
    want = {"n_layers": spec["num_hidden_layers"],
            "d_model": spec["hidden_size"],
            "n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "d_ff": spec["intermediate_size"],
            "vocab": spec["derived"]["embedding_size"],
            "hd": spec["derived"]["head_dim"],
            "rope_theta": spec["rope_theta"],
            "tie_embeddings": spec["tie_word_embeddings"],
            "qkv_bias": arch["qkv_bias"],
            "norm": {"rmsnorm": "rmsnorm",
                     "nonparam_layernorm": "nonparam_ln"}[arch["norm"]],
            "mlp_kind": arch["mlp"], "family": "dense"}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise HarnessError(f"program config differs from {spec['name']}: "
                           f"{bad} (program, file)")
    return cfg


def _iter_eqns(jaxpr):
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for item in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)


def step_kernels(closed_jaxpr) -> set:
    """Names ("<package>/<module>.<kernel>") of every pallas_call in a step
    program's jaxpr, nested jaxprs included."""
    found = set()
    for eqn in _iter_eqns(closed_jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        src = eqn.params["jaxpr"].debug_info.func_src_info
        func, _, where = src.partition(" at ")
        path = Path(where.rsplit(":", 1)[0])
        found.add(f"{path.parent.name}/{path.stem}.{func}")
    return found


def program_kernels(eng) -> dict:
    """Step program kind ("decode", "chunk") -> its Pallas kernels."""
    return {("decode" if w == 1 else "chunk"): step_kernels(eng.step_trace(w))
            for w in eng.step_widths()}


def route_problems(eng, kernels: dict) -> list:
    """Why the engine's step programs are not the Pallas ones, if they
    are not."""
    out = []
    if eng.decode_route() != "pallas-decode":
        out.append(f"decode route {eng.decode_route()}")
    if eng.prefill_route() != "pallas-prefill":
        out.append(f"prefill route {eng.prefill_route()}")
    for kind, want in (("decode", "flash_attention/decode."),
                       ("chunk", "flash_attention/prefill.")):
        names = kernels.get(kind, set())
        if not any(want in k for k in names):
            out.append(f"{kind} step runs no {want} kernel ({names})")
    return out


def kernel_seconds(summary, kernels: dict) -> dict:
    """Trace seconds of each roofline kernel: the Pallas time inside its
    step program, where that program runs that kernel and no other."""
    out = {}
    for key, (kind, want) in ROOFLINE_KERNELS.items():
        names = kernels.get(kind, set())
        if len(names) == 1 and want in next(iter(names)) and \
                kind in summary.pallas_s:
            out[key] = summary.pallas_s[kind]
    return out


def device_info(chips: int) -> dict:
    """The accelerator as JAX reports it; an error without a TPU or with
    fewer chips than the cell asks for."""
    import jax

    if jax.default_backend() != "tpu":
        raise HarnessError(f"JAX backend is {jax.default_backend()!r}, "
                           f"not a TPU")
    devs = jax.devices()
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips, JAX has "
                           f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class _Tracer:
    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self):
        import jax
        jax.profiler.start_trace(self.log_dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Context:
    """Everything a metric reader may read."""
    shape: work.Shape
    log: object                  # driver.Log
    setup_s: float
    device_kind: str
    trace: Optional[tr.Summary] = None
    kernel_s: dict = dataclasses.field(default_factory=dict)

    @property
    def window(self) -> tuple:
        return self.log.window

    @property
    def layer_window(self) -> tuple:
        """The interval per-layer metrics are read over: the traced one."""
        return self.log.traced or self.log.window

    def layer_steps(self):
        return self.log.steps_in(*self.layer_window)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, clock, program_cfg=None,
             require_tpu: bool = True, control: bool = False,
             compile_count: Callable[[], int] = lambda: 0,
             log: Callable[[str], None] = lambda m: print(m,
                                                          file=sys.stderr)):
    """One run. Returns (the result line's object, extra readings).
    `control` also reads the correctness control over the same sample
    (`bench/readings.py` does; the benchmark's own runs never do)."""
    import jax
    from repro import api
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    device = device_info(cell.chips) if require_tpu else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    spec, mix = cell.spec, cell.mix
    cfg = program_config(spec, program_cfg)
    key = W.seed_key(seed)
    t = clock()
    abstract = jax.eval_shape(lambda k: init_params(k, cfg),
                              jax.random.key(0))
    params = jax.block_until_ready(W.program_params(key, abstract, spec))
    log(f"weights made in {clock() - t:.3f}s")
    t = clock()
    eng = ServingEngine(cfg, params, slots=spec["engine"]["slots"],
                        max_len=mix["max_len"],
                        policy=api.ExecutionPolicy())
    eng.warmup()
    log(f"engine built and step programs warmed in {clock() - t:.3f}s")
    kernels = program_kernels(eng)
    if require_tpu:
        problems = route_problems(eng, kernels)
        if problems:
            raise HarnessError("; ".join(problems))
    log(f"routes: decode {eng.decode_route()}, prefill "
        f"{eng.prefill_route()}, weights {eng.weight_route()}; "
        f"prefill_chunk {eng.prefill_chunk}")
    vocab_ids = spec["derived"].get("tokenizer_vocab", spec["vocab_size"])
    gen = loadgen.make(mix, seed, vocab_ids, eng.slots,
                       [mix["warm_s"], seconds, TAIL_S])
    annotate = jax.profiler.TraceAnnotation
    drv = Driver(eng, gen, request_cls=Request, annotate=annotate,
                 clock=clock, compile_count=compile_count)
    with contextlib.ExitStack() as stack:
        tracer = None
        if trace:
            tracer = _Tracer(stack.enter_context(
                tempfile.TemporaryDirectory(prefix="bench-trace-")))
        run_log = drv.run(mix["warm_s"], seconds, tracer, trace_s=TRACE_S,
                          grace_s=GRACE_S)
        t = clock()
        summary = None
        if trace:
            files = list(Path(tracer.log_dir).rglob("*.xplane.pb"))
            if files and run_log.traced:
                launches = [(s.chunk_launches, s.decode_launches)
                            for s in run_log.steps_in(*run_log.traced)]
                summary = tr.reduce(tr.load(str(files[0])), launches)
            log(f"trace read in {clock() - t:.3f}s")
    setup_s = run_log.window[0] - t_start
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    st = eng.stats
    counters = {"demotions": st.demotions, "quarantines": st.quarantines,
                "failed_requests": st.failed_requests,
                "step_traces": eng.step_trace_count(),
                "compiles_in_window": run_log.compiles_in_window}
    ctx = Context(shape=work.Shape.from_spec(spec), log=run_log,
                  setup_s=setup_s, device_kind=device["kind"], trace=summary,
                  kernel_s=kernel_seconds(summary, kernels)
                  if summary is not None else {})
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    w0, w1 = run_log.window
    recs = list(run_log.records.values())
    started = [r for r in recs if w0 <= r.start <= w1]
    failed = sum(r.status in ("TIMEOUT", "REJECTED", "FAILED")
                 for r in started)
    sample = correct.pick(recs, drv.served, seed, (w0, w1))
    # the program's state goes before the reference runs
    del eng, params, drv
    gc.collect()
    t = clock()
    cmp = correct.compare(spec, key, sample, mix["max_len"],
                          mix["output_len"]["max"], control=control)
    log(f"reference compared {cmp['tokens']} tokens of {cmp['requests']} "
        f"requests from {cmp['slots']} slots in {clock() - t:.3f}s")
    checks = {
        "mean_logit_gap": {"value": cmp["mean_gap"],
                           "limit": cell.limit["mean_logit_gap"]},
        "compared_tokens": {"value": cmp["tokens"],
                            "at_least": correct.MIN_TOKENS // 3},
        "demotions": {"value": counters["demotions"], "limit": 0},
        "quarantines": {"value": counters["quarantines"], "limit": 0},
        "failed_requests": {"value": counters["failed_requests"],
                            "limit": 0},
    }
    ok = all(_passes(c) for c in checks.values())
    result = {"correct": ok, "attempted": len(started), "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result, {"comparison": cmp, "counters": counters, "ctx": ctx,
                    "summary": summary}


def _passes(check: dict) -> bool:
    v = check["value"]
    if v is None:
        return False
    if "limit" in check:
        return v <= check["limit"]
    return v >= check["at_least"]
