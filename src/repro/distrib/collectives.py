"""shard_map collectives: overlap-friendly TP matmuls + helpers.

Two hand-scheduled TP matmul variants (the beyond-paper §Perf levers):

  * ``rowparallel_matmul`` — contraction dim sharded, one psum at the
    end: the activation all-gather is replaced by a (smaller) result
    reduction.
  * ``allgather_matmul_overlapped`` — the collective-matmul schedule:
    activation shards rotate around the TP ring via collective_permute
    while each step's partial matmul runs, so ICI transfers hide behind
    MXU time instead of serializing before it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def rowparallel_matmul(x, w, mesh: Mesh, axis: str = "model"):
    """y = x @ w with x (..., K) and w (K, N) both sharded on K over
    ``axis``; y replicated via a single psum."""
    def body(xs, ws):
        part = jnp.einsum("...k,kn->...n", xs, ws)
        return jax.lax.psum(part, axis)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(*([None] * (x.ndim - 1)), axis), P(axis, None)),
        out_specs=P(*([None] * x.ndim)),
    )(x, w)


def allgather_matmul_overlapped(x, w, mesh: Mesh, axis: str = "model"):
    """y = all_gather(x, seq) @ w_col_shard, ring-overlapped.

    x: (..., S, K) sharded over ``axis`` on the sequence dim (SP layout);
    w: (K, N) sharded over ``axis`` on N (column-parallel).
    Output: (..., S, N) with seq gathered and N sharded — each device
    ends holding its N shard for the full sequence.

    Instead of all-gathering S up front, each of the n steps matmuls the
    currently-held sequence chunk and permutes the chunk one hop around
    the ring — compute hides the permute latency.
    """
    n = mesh.shape[axis]
    seq_dim = x.ndim - 2

    def body(xs, ws):
        idx = jax.lax.axis_index(axis)
        # send to the *previous* rank so arrival order is idx, idx+1, ...
        perm = [(i, (i - 1) % n) for i in range(n)]
        parts = []
        cur = xs
        for i in range(n):
            parts.append(jnp.einsum("...sk,kn->...sn", cur, ws))
            if i != n - 1:
                cur = jax.lax.ppermute(cur, axis, perm)
        out = jnp.concatenate(parts, axis=seq_dim)  # arrival order
        # arrival position i holds owner (idx + i) % n; canonical order
        # is roll by idx chunks along the sequence dim
        return jnp.roll(out, idx * xs.shape[seq_dim], axis=seq_dim)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(*([None] * seq_dim), axis, None), P(None, axis)),
        out_specs=P(*([None] * (seq_dim + 1)), axis),
    )(x, w)


def psum_scalar(x, axis: str, mesh: Mesh):
    return shard_map(lambda v: jax.lax.psum(v, axis), mesh=mesh,
                     in_specs=P(), out_specs=P())(x)
