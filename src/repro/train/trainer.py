"""Distributed trainer: jit-compiled train step + fault-tolerant loop.

make_train_step builds the sharded step function for any ArchConfig:
  - QAT ternary forward (the paper's technique) via nn/linear.py
  - chunked CE loss, MoE aux losses
  - gradient accumulation (scan over microbatches)
  - global-norm clipping, AdamW with ZeRO-sharded optimizer states
  - optional int8 error-feedback gradient compression (cross-pod DP)

The Trainer loop adds: async checkpointing + auto-resume, preemption
handling, straggler monitoring, and elastic restart (resume the same
run on a different DP size — the data pipeline is stateless in (step,
shard), so resharding is free).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distrib import sharding as shd
from repro.distrib.grad_compress import (compress_decompress,
                                         init_error_buffers)
from repro.models import transformer as tfm
from repro.models.losses import lm_loss
from repro.train.checkpoint import CheckpointManager
from repro.train.data import DataConfig, make_batch
from repro.train.fault import PreemptionHandler, StragglerMonitor
from repro.train.optimizer import (OptConfig, ScheduleConfig,
                                   clip_by_global_norm, lr_at,
                                   make_optimizer)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: OptConfig = OptConfig()
    schedule: ScheduleConfig = ScheduleConfig()
    microbatches: int = 1            # gradient accumulation
    grad_compress: bool = False      # int8 EF compression of DP grads
    zero_sharding: bool = True       # ZeRO opt-state sharding over data
    ckpt_dir: Optional[str] = None
    ckpt_interval: int = 50
    ckpt_keep: int = 3
    log_interval: int = 10


def make_train_step(arch: ArchConfig, tcfg: TrainConfig, mesh: Mesh,
                    rules: shd.Rules):
    """Returns (train_step, param_shardings, opt_shardings, init_fns)."""
    opt_init, opt_update = make_optimizer(tcfg.opt)

    def loss_fn(params, batch):
        return lm_loss(params, arch, batch)

    def grads_of(params, batch):
        if tcfg.microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads

        # split batch leading dim into microbatches and scan
        def reshape_mb(x):
            b = x.shape[0]
            mb = tcfg.microbatches
            return x.reshape(mb, b // mb, *x.shape[1:])

        mbatch = jax.tree_util.tree_map(reshape_mb, batch)

        def body(acc, mb):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            acc_g, acc_l = acc
            acc_g = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), acc_g, grads)
            return (acc_g, acc_l + loss), metrics

        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), metrics = jax.lax.scan(
            body, (zero_g, jnp.zeros((), jnp.float32)), mbatch)
        grads = jax.tree_util.tree_map(
            lambda g: g / tcfg.microbatches, grads)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return loss_sum / tcfg.microbatches, metrics, grads

    def train_step(params, opt_state, err_buf, batch):
        step = opt_state["step"]
        loss, metrics, grads = grads_of(params, batch)
        grads, gnorm = clip_by_global_norm(grads, tcfg.opt.grad_clip)
        if tcfg.grad_compress:
            grads, err_buf = compress_decompress(grads, err_buf)
        lr = lr_at(tcfg.schedule, step)
        params, opt_state = opt_update(params, grads, opt_state, lr)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr"] = lr
        return params, opt_state, err_buf, metrics

    # ---- shardings ----
    spec_tree = tfm.specs(arch)
    p_pspecs = shd.tree_pspecs(spec_tree, rules)

    def opt_pspecs_of(params_shapes):
        m_ps = p_pspecs
        if tcfg.zero_sharding:
            m_ps = shd.zero_shard_tree(p_pspecs, params_shapes, mesh)
        return {"step": P(), "m": m_ps, "v": m_ps} \
            if tcfg.opt.name == "adamw" else {"step": P(), "mom": m_ps}

    return train_step, p_pspecs, opt_pspecs_of, (opt_init,)


class Trainer:
    """End-to-end training driver (used by examples/ and launch/train)."""

    def __init__(self, arch: ArchConfig, tcfg: TrainConfig,
                 dcfg: DataConfig, mesh: Optional[Mesh] = None,
                 seed: int = 0):
        self.arch, self.tcfg, self.dcfg = arch, tcfg, dcfg
        if mesh is None:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.mesh = mesh
        self.rules = shd.make_rules(
            arch, mesh,
            batch_shardable=dcfg.global_batch % max(
                1, np.prod([mesh.shape[a] for a in mesh.axis_names
                            if a in ("pod", "data")])) == 0)
        (self.step_fn, self.p_pspecs, self.opt_pspecs_of,
         (self.opt_init,)) = make_train_step(arch, tcfg, mesh, self.rules)

        key = jax.random.PRNGKey(seed)
        with jax.set_mesh(self.mesh):
            self.params = jax.jit(
                lambda k: tfm.init(arch, k),
                out_shardings=shd.tree_shardings(
                    tfm.specs(arch), self.rules, mesh))(key)
            self.opt_state = self.opt_init(self.params)
        self.err_buf = (init_error_buffers(self.params)
                        if tcfg.grad_compress else {})
        self.step = 0

        self.ckpt = None
        if tcfg.ckpt_dir:
            self.ckpt = CheckpointManager(tcfg.ckpt_dir, tcfg.ckpt_keep,
                                          tcfg.ckpt_interval)
        self.preempt = PreemptionHandler()
        self.straggler = StragglerMonitor()
        self._jit_step = jax.jit(self.step_fn, donate_argnums=(0, 1, 2))

    # -- fault tolerance ---------------------------------------------------
    def try_resume(self) -> bool:
        if self.ckpt is None:
            return False
        from repro.train.checkpoint import latest_step
        if latest_step(self.ckpt.directory) is None:
            return False
        (state, step) = self.ckpt.restore_latest(
            {"params": self.params, "opt": self.opt_state})
        self.params, self.opt_state = state["params"], state["opt"]
        self.step = step
        return True

    def save(self, blocking: bool = False):
        if self.ckpt is not None:
            self.ckpt.save({"params": self.params, "opt": self.opt_state},
                           self.step, blocking=blocking)

    # -- loop ----------------------------------------------------------------
    def run(self, num_steps: int, log: Callable[[str], None] = print
            ) -> Dict[str, float]:
        num_shards = 1  # single-host data feed; sharded by GSPMD on entry
        history = []
        with jax.set_mesh(self.mesh):
            while self.step < num_steps:
                t0 = time.perf_counter()
                batch = make_batch(self.dcfg, self.arch, self.step,
                                   shard=0, num_shards=num_shards)
                self.params, self.opt_state, self.err_buf, metrics = \
                    self._jit_step(self.params, self.opt_state,
                                   self.err_buf, batch)
                self.step += 1
                dt = time.perf_counter() - t0
                self.straggler.record(dt)
                if self.step % self.tcfg.log_interval == 0 or \
                        self.step == num_steps:
                    m = {k: float(v) for k, v in metrics.items()}
                    history.append(m)
                    log(f"step {self.step}: loss={m['loss']:.4f} "
                        f"ce={m['ce']:.4f} acc={m['accuracy']:.3f} "
                        f"gnorm={m['grad_norm']:.2f} {dt*1e3:.0f}ms"
                        + (" [straggler]" if self.straggler.is_straggler(dt)
                           else ""))
                if self.ckpt and self.ckpt.should_save(self.step):
                    self.save()
                if self.preempt.should_stop:
                    log(f"preemption at step {self.step}: checkpointing")
                    self.save(blocking=True)
                    break
        if self.ckpt:
            self.save(blocking=True)
        return history[-1] if history else {}
