"""GPipe-style pipeline parallelism over a mesh axis (the pod axis).

At 2 pods the framework uses the pod axis as extra DP (validated by the
multi-pod dry-run); at 4+ pods cross-pod gradient all-reduces start to
dominate and pipelining the *depth* over pods becomes the better trade
(DESIGN.md §9).  This module provides that alternative:

  * the layer stack is split into S = mesh.shape[axis] contiguous
    stages; stage s's parameters live only on pod s (leading-dim
    sharding of the stacked params);
  * the batch splits into M microbatches; the classic GPipe schedule
    runs M + S - 1 ticks, each tick = one stage_fn application per pod
    with a collective_permute hand-off to the next pod;
  * bubble fraction = (S-1)/(M+S-1) — reported by ``bubble_fraction``
    so launchers can pick M.

Pure shard_map + ppermute: no torch-style runtime, works under jit, and
the dry-run's HLO census sees the real collective pattern (M*(S-1)
point-to-point permutes of one microbatch activation each — vs the
full-batch gradient all-reduce it replaces).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array,
                   mesh: Mesh, axis: str = "pod",
                   n_microbatches: int = 4) -> jax.Array:
    """Run ``y = stage_{S-1}(...stage_0(x))`` pipelined over ``axis``.

    stage_fn: (params_slice, activation) -> activation, applied once per
        stage (params_slice = stage_params[s] for stage s).
    stage_params: pytree stacked on a leading dim of size S (sharded
        over ``axis`` by the caller's in_shardings, or replicated — the
        shard_map in_spec slices it either way).
    x: (B, ...) global batch, replicated over ``axis``.
    Returns y: (B, ...) replicated over ``axis`` (valid on every pod).
    """
    s_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_microbatches == 0, (b, n_microbatches)
    mb = b // n_microbatches
    m = n_microbatches

    def body(params_local, x_local):
        # params_local: leading dim 1 (this pod's stage)
        my_params = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        fwd = [(i, (i + 1) % s_stages) for i in range(s_stages)]

        micro = x_local.reshape(m, mb, *x_local.shape[1:])
        out = jnp.zeros_like(micro)
        cur = jnp.zeros_like(micro[0])

        for t in range(m + s_stages - 1):
            # stage 0 injects microbatch t (when in range)
            inject = micro[min(t, m - 1)]
            cur = jnp.where(stage == 0,
                            jnp.where(t < m, inject, cur), cur)
            y = stage_fn(my_params, cur)
            # last stage banks its finished microbatch (t - (S-1))
            done_idx = t - (s_stages - 1)
            if 0 <= done_idx < m:
                bank = jnp.where(stage == s_stages - 1, y, out[done_idx])
                out = out.at[done_idx].set(bank)
            # hand off to the next stage
            if t != m + s_stages - 2:
                cur = jax.lax.ppermute(y, axis, fwd)
        # every pod returns the banked outputs of the LAST stage: bring
        # them back around the ring so the result is replicated
        out = jax.lax.psum(
            jnp.where(stage == s_stages - 1, out, jnp.zeros_like(out)),
            axis)
        return out.reshape(b, *x_local.shape[1:])

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        P(),
    )
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=P())(stage_params, x)


def reference_apply(stage_fn: Callable, stage_params, x: jax.Array
                    ) -> jax.Array:
    """Sequential oracle."""
    s = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    y = x
    for i in range(s):
        p_i = jax.tree_util.tree_map(lambda p: p[i], stage_params)
        y = stage_fn(p_i, y)
    return y
