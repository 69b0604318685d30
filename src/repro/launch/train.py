"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch chatglm3-6b \
        --smoke --steps 100 --batch 8 --seq 128 --ckpt /tmp/run1

On a real TPU fleet this same entry point runs under multi-process JAX
(jax.distributed.initialize from the pod runtime env vars); on this CPU
container it drives the host mesh.  Auto-resumes from the latest
checkpoint in --ckpt; handles SIGTERM preemption by checkpointing.
"""
from __future__ import annotations

import argparse



def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.nn.module import param_count
    from repro.train.data import DataConfig
    from repro.train.optimizer import OptConfig, ScheduleConfig
    from repro.train.trainer import TrainConfig, Trainer

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(model=args.model_parallel)
    tcfg = TrainConfig(
        opt=OptConfig(lr=args.lr),
        schedule=ScheduleConfig(peak_lr=args.lr,
                                warmup_steps=max(args.steps // 20, 1),
                                total_steps=args.steps),
        microbatches=args.microbatches,
        grad_compress=args.grad_compress,
        ckpt_dir=args.ckpt, ckpt_interval=args.ckpt_interval)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    trainer = Trainer(cfg, tcfg, dcfg, mesh=mesh)
    trainer.preempt.__init__(install_signals=True)
    print(f"[train] arch={cfg.name} params={param_count(trainer.params):,} "
          f"mesh={dict(mesh.shape)}")
    if trainer.try_resume():
        print(f"[train] resumed from step {trainer.step}")
    final = trainer.run(args.steps)
    print(f"[train] done: {final}")


if __name__ == "__main__":
    main()
