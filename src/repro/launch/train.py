"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch olmo_1b --smoke \\
        --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Full-config runs use the same path on real hardware; on this CPU container
use --smoke (reduced config). Handles restart-from-checkpoint automatically;
--simulate-preemption N kills the loop at step N and restarts, exercising the
fault-tolerance path end-to-end.
"""
from __future__ import annotations

import argparse

import jax

from ..configs import get_config, get_smoke
from ..data import DataConfig, Prefetcher, SyntheticLM
from ..dist.sharding import set_mesh
from ..runtime import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache
from .mesh import make_local_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--simulate-preemption", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(model=args.model_parallel)
    tcfg = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         base_lr=args.lr, total_steps=args.steps,
                         warmup=max(args.steps // 20, 1))

    def make_data(state=None):
        src = SyntheticLM(DataConfig(
            vocab=cfg.vocab, batch=args.batch, seq=args.seq,
            frontend=cfg.frontend, frontend_len=cfg.frontend_len,
            d_model=cfg.d_model), state)
        # NOTE: prefetch depth advances the source state ahead of
        # consumption; on restart up to `depth` batches are skipped — a
        # documented at-most-once data guarantee.
        return src, Prefetcher(src)

    def on_step(step, m):
        if step % 10 == 0 or step == 1:
            print(f"step {step:5d}  loss {m['loss']:.4f}  "
                  f"gnorm {m['grad_norm']:.3f}  lr {m['lr']:.2e}  "
                  f"{m['step_time_s']*1e3:.0f} ms")

    with set_mesh(mesh):
        trainer = Trainer(cfg, tcfg, mesh, key=jax.random.key(0))
        resumed = trainer.maybe_restore()
        if resumed:
            print(f"[train] resumed from checkpoint step {resumed}")
        start = int(trainer.opt_state.step)
        todo = args.steps - start
        if args.simulate_preemption and start < args.simulate_preemption:
            todo = args.simulate_preemption - start
        src, data = make_data(trainer.pipeline_state)
        trainer.attach_pipeline(src.state)
        trainer.run(data, todo, on_step=on_step)
        trainer.checkpoint(int(trainer.opt_state.step))
        trainer.ckpt.wait()
        if args.simulate_preemption and \
                int(trainer.opt_state.step) < args.steps:
            print("[train] simulated preemption — restarting from checkpoint")
            trainer2 = Trainer(cfg, tcfg, mesh, key=jax.random.key(0))
            trainer2.maybe_restore()
            src2, data2 = make_data(trainer2.pipeline_state)
            trainer2.attach_pipeline(src2.state)
            trainer2.run(data2,
                         args.steps - int(trainer2.opt_state.step),
                         on_step=on_step)
            trainer2.checkpoint(int(trainer2.opt_state.step))
            trainer2.ckpt.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
