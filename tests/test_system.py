"""End-to-end system behaviour tests.

These exercise the whole stack the way a user would: train with
checkpointing, kill, restart, resume — and serve with the quantized format
plane — plus the dry-run machinery on a small in-process mesh.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_smoke, shape_support
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_local_mesh
from repro.models import forward, init_params
from repro.runtime import Trainer, TrainerConfig


def test_train_kill_restart_resume_bitexact(tmp_path):
    """The fault-tolerance contract: a run that checkpoints at step 4, dies,
    and restarts must produce the same params as an uninterrupted run."""
    cfg = get_smoke("olmo_1b")
    mesh = make_local_mesh()

    def data():
        return iter(SyntheticLM(DataConfig(vocab=cfg.vocab, batch=4, seq=32,
                                           seed=11)))

    def tcfg(d):
        return TrainerConfig(ckpt_dir=str(d), ckpt_every=4, total_steps=8,
                             base_lr=1e-3, warmup=2)

    # uninterrupted: 8 steps
    t_full = Trainer(cfg, tcfg(tmp_path / "full"), mesh, key=jax.random.key(7))
    t_full.run(data(), 8)

    # interrupted: 4 steps, "crash", restart, 4 more with resumed data state
    t_a = Trainer(cfg, tcfg(tmp_path / "int"), mesh, key=jax.random.key(7))
    src = SyntheticLM(DataConfig(vocab=cfg.vocab, batch=4, seq=32, seed=11))
    t_a.attach_pipeline(src.state)
    t_a.run(iter(src), 4)
    t_a.ckpt.wait()
    del t_a                                        # crash

    t_b = Trainer(cfg, tcfg(tmp_path / "int"), mesh, key=jax.random.key(99))
    step = t_b.maybe_restore()
    assert step == 4
    assert t_b.pipeline_state.step == 4            # data position restored
    # resume the data stream from the checkpointed pipeline state
    src2 = SyntheticLM(DataConfig(vocab=cfg.vocab, batch=4, seq=32, seed=11),
                       t_b.pipeline_state)
    t_b.attach_pipeline(src2.state)
    t_b.run(iter(src2), 4)

    for a, b in zip(jax.tree.leaves(t_full.params), jax.tree.leaves(t_b.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_loss_decreases_on_learnable_stream():
    """The synthetic stream has Markov structure; 30 steps must beat the
    starting loss by a clear margin."""
    cfg = get_smoke("olmo_1b")
    mesh = make_local_mesh()
    tr = Trainer(cfg, TrainerConfig(ckpt_dir="/tmp/sys_learn", ckpt_every=10**9,
                                    total_steps=60, base_lr=1e-2, warmup=5),
                 mesh, key=jax.random.key(0))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, batch=8, seq=64, seed=5))
    tr.run(iter(data), 60)
    first = tr.metrics_log[0]["loss"]
    last = min(m["loss"] for m in tr.metrics_log[-5:])
    assert last < first - 0.3, f"no learning: {first} -> {last}"


def test_quantized_serving_matches_fp_argmax_mostly():
    """PTQ int8 weights must keep greedy decisions for a majority of tokens
    (the inference-format premise of the paper)."""
    from repro.core import formats as F
    cfg = get_smoke("qwen2_1p5b")
    params = init_params(jax.random.key(0), cfg)

    def q(leaf):
        if leaf.ndim >= 2 and leaf.shape[-1] >= 8:
            codes, scale = F.quantize_scaled(leaf, F.INT8, axis=-1, pow2=True)
            return F.decode(codes, F.INT8) * scale
        return leaf
    qparams = jax.tree.map(q, params)
    toks = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab)
    lf, _ = forward(params, toks, cfg)
    lq, _ = forward(qparams, toks, cfg)
    agree = float(jnp.mean(jnp.argmax(lf, -1) == jnp.argmax(lq, -1)))
    assert agree > 0.7, agree


def test_dryrun_lowering_small_mesh_subprocess():
    """The dry-run machinery end-to-end on an 8-device in-process mesh:
    lower+compile a train cell, parse collectives, sane record."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import dataclasses, jax
from repro.configs import SHAPES, get_smoke
from repro.launch.dryrun import _lower_one, _costs
from repro.launch.mesh import make_mesh
cfg = dataclasses.replace(get_smoke("qwen2_1p5b"), scan_unroll=10**6)
mesh = make_mesh((2, 4), ("data", "model"))
cell = dataclasses.replace(SHAPES["train_4k"], batch=8, seq=64)
c = _costs(_lower_one(cfg, cell, mesh))
assert c["flops"] > 0 and c["bytes"] > 0, c
print("DRYRUN_SMALL_OK", c["flops"])
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=420, cwd="/root/repo")
    assert "DRYRUN_SMALL_OK" in out.stdout, out.stderr[-2000:]


def test_all_arch_shape_grid_is_self_describing():
    """Every assigned arch declares support for all four cells; skips carry
    reasons; exactly the two sub-quadratic archs run long_500k."""
    long_runners = []
    for arch in ARCH_IDS:
        if arch in ("gpt2_small", "llama2_7b"):
            continue
        sup = shape_support(arch)
        assert set(sup) == {"train_4k", "prefill_32k", "decode_32k",
                            "long_500k"}
        for shape, reason in sup.items():
            assert reason is None or isinstance(reason, str)
        if sup["long_500k"] is None:
            long_runners.append(arch)
    assert sorted(long_runners) == ["xlstm_1p3b", "zamba2_2p7b"]


def test_int8_kv_cache_decode_agrees_with_bf16():
    """QuantKVCache (the format plane on cache residency, §Perf it7) must
    keep greedy decode decisions."""
    import dataclasses
    cfg = get_smoke("internlm2_20b")
    qcfg = dataclasses.replace(cfg, kv_quant=True)
    params = init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    from repro.models import decode_step, init_caches
    c_fp = init_caches(cfg, 2, 16, dtype=jnp.float32)
    c_q = init_caches(qcfg, 2, 16)
    agree = 0
    sf = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))
    sq = jax.jit(lambda p, c, t: decode_step(p, c, t, qcfg))
    for t in range(8):
        lf, c_fp = sf(params, c_fp, toks[:, t:t + 1])
        lq, c_q = sq(params, c_q, toks[:, t:t + 1])
        agree += int((jnp.argmax(lf[:, -1], -1) ==
                      jnp.argmax(lq[:, -1], -1)).all())
    assert agree >= 7, agree


def test_compile_cache_follows_env_else_fixed_checkout_dir(monkeypatch):
    """The launchers' compile cache: $JAX_COMPILATION_CACHE_DIR when set
    (and then no other directory is configured), else `.jax_cache` at the
    root of the checkout — a fixed, git-ignored path."""
    from pathlib import Path

    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch.compile_cache import enable_compile_cache

    root = Path(__file__).resolve().parents[1]
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/deployment")
        assert enable_compile_cache() == "/set/by/deployment"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == str(root / ".jax_cache") == enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert "/.jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()
