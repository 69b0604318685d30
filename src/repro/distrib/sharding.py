"""Logical-axis sharding rules: DP / TP / EP / SP over the production mesh.

Model code annotates params and activations with *logical* axis names
(nn/*.py ``specs()``).  This module resolves them to mesh axes per
architecture, applying the divisibility fallbacks documented in
DESIGN.md §5:

  * batch      -> ('pod', 'data')   [DP; dropped if batch < dp]
  * heads/ff   -> 'model'           [TP]
  * kv_heads   -> 'model' iff n_kv_heads % model == 0 else replicated
                  (Megatron GQA rule: replicate KV when too few heads)
  * experts    -> 'model' iff n_experts % model == 0 (EP), else the
                  per-expert ff dim takes the TP axis instead
  * vocab      -> 'model' (embeddings padded to /128 so it always divides)
  * cache_seq  -> 'data' for long-context decode (SP over the KV cache,
                  merged with the shard_map partial-attention path)
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.launch.mesh import dp_axis_names, mesh_axis_size

Rules = Dict[str, Any]


def make_rules(cfg: ArchConfig, mesh: Mesh,
               batch_shardable: bool = True,
               shard_cache_seq=False,   # False | 'data' | 'model'
               seq_shard: bool = False,
               moe_cap_shard: bool = False) -> Rules:
    model = mesh_axis_size(mesh, "model")
    dp = dp_axis_names(mesh)

    rules: Rules = {
        "batch": dp if batch_shardable else None,
        "layers": None,
        "vocab": "model" if cfg.vocab_padded % max(model, 1) == 0 else None,
        "ff": "model" if cfg.d_ff and cfg.d_ff % max(model, 1) == 0 else None,
        "heads": "model",
        "kv_heads": ("model" if cfg.n_kv_heads % max(model, 1) == 0
                     else None),
        "kv_heads_cache": ("model" if cfg.n_kv_heads % max(model, 1) == 0
                           else None),
        "cache_seq": (shard_cache_seq if isinstance(shard_cache_seq, str)
                      else ("data" if shard_cache_seq else None)),
        # §Perf levers: Megatron-style sequence-parallel residual stream
        # and data-sharded MoE dispatch buffers (both hint-gated)
        "seq": "model" if seq_shard else None,
        "moe_cap": "data" if moe_cap_shard else None,
    }
    # merged q-heads dim: shard when the merged width divides the axis
    if (cfg.n_heads * cfg.hd) % max(model, 1) != 0:
        rules["heads"] = None
    if cfg.moe is not None:
        if cfg.moe.num_experts % max(model, 1) == 0:
            rules["experts"] = "model"      # EP
            rules["expert_ff"] = None
        else:
            rules["experts"] = None         # TP inside experts
            rules["expert_ff"] = (
                "model" if cfg.moe.d_ff % max(model, 1) == 0 else None)
    if cfg.mamba is not None:
        di, nh = cfg.mamba.d_inner, cfg.mamba.n_heads
        rules["ssm_inner"] = "model" if di % max(model, 1) == 0 else None
        rules["ssm_heads"] = "model" if nh % max(model, 1) == 0 else None
    return rules


def spec_to_pspec(spec: Tuple[Optional[str], ...], rules: Rules) -> P:
    axes = []
    for name in spec:
        if name is None:
            axes.append(None)
        else:
            axes.append(rules.get(name))
    # drop trailing Nones (canonical form)
    while axes and axes[-1] is None:
        axes.pop()
    return P(*axes)


def tree_pspecs(spec_tree, rules: Rules):
    return jax.tree_util.tree_map(
        lambda s: spec_to_pspec(s, rules), spec_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def tree_shardings(spec_tree, rules: Rules, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_to_pspec(s, rules)), spec_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def batch_pspec(rules: Rules) -> P:
    b = rules.get("batch")
    return P(b) if b is not None else P()


def constrain(x, mesh: Mesh, spec: Tuple[Optional[str], ...], rules: Rules):
    """with_sharding_constraint via logical names."""
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_to_pspec(spec, rules)))


# ---------------------------------------------------------------------------
# Sharding hints: a context that lets *model code* place logical-axis
# constraints without threading mesh/rules through every function.
# Inactive by default (plain CPU tests see zero constraints); the
# dry-run and trainer activate it for §Perf variants.
# ---------------------------------------------------------------------------

_HINTS: contextvars.ContextVar[Optional[Rules]] = contextvars.ContextVar(
    "sharding_hints", default=None)


@contextlib.contextmanager
def sharding_hints(rules: Optional[Rules]):
    token = _HINTS.set(rules)
    try:
        yield
    finally:
        _HINTS.reset(token)


def hint_constrain(x, spec: Tuple[Optional[str], ...]):
    """Constrain ``x`` per the active hint rules (no-op when inactive
    or when every resolved axis is None).  Uses the ambient abstract
    mesh (requires tracing under jax.set_mesh)."""
    rules = _HINTS.get()
    if rules is None:
        return x
    ps = spec_to_pspec(spec, rules)
    if all(e is None for e in ps):
        return x
    return jax.lax.with_sharding_constraint(x, ps)


# Logical spec of the fused TiM matmuls' stacked operand: the fused xla
# routes (kernels/ops._st_matmul_xla_fused_*) stack the per-phase /
# per-bit-plane non-negative patterns along a FRESH leading axis, so the
# operand is a (phases, M, K) int8 tensor — leading axis unsharded
# (replicating it is the point: every device runs all phases over its M
# shard against its local W tile), M on the batch (DP) axes, K unsharded
# (it is the dot contraction against W's K).
TIM_STACKED_SPEC: Tuple[Optional[str], ...] = (None, "batch", None)


def tim_stacked_constraint(x):
    """Keep the fused-TiM stacked activation on the batch (DP) axes.

    The phase stack doubles (two-phase) or ``bits``-tuples (bit-serial)
    the per-device M work; without a constraint GSPMD may resolve the
    stack to fully replicated, which then re-gathers W for the single
    dot and forfeits the fused kernels' one-weight-stream win.  (The
    stack is a fresh leading axis on purpose — concatenating along the
    batch-sharded M dim miscompiles on XLA:CPU 0.4.x, summing the
    model-axis replicas of each activation shard.)  No-op outside an
    active ``sharding_hints`` context, so kernel-level tests and plain
    CPU runs see zero constraints.
    """
    return hint_constrain(x, TIM_STACKED_SPEC)


# ---------------------------------------------------------------------------
# ZeRO (optimizer-state sharding over the data axis)
# ---------------------------------------------------------------------------

def zero_pspec(pspec: P, shape: Tuple[int, ...], mesh: Mesh,
               dp_axes: Tuple[str, ...]) -> P:
    """Extend a param PartitionSpec by sharding its largest unsharded dim
    over the data axes (ZeRO-style).  Falls back to the original spec if
    nothing divides."""
    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    if dp <= 1 or not shape:
        return pspec
    used = set()
    entries = list(pspec) + [None] * (len(shape) - len(pspec))
    for e in entries:
        if e is None:
            continue
        for a in (e if isinstance(e, tuple) else (e,)):
            used.add(a)
    if any(a in used for a in dp_axes):
        return pspec
    # choose the largest dim divisible by dp and currently unsharded
    cand = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in cand:
        if entries[i] is None and shape[i] % dp == 0:
            entries[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            while entries and entries[-1] is None:
                entries.pop()
            return P(*entries)
    return pspec


def pspecs_for_params(spec_tree, params, rules: Rules,
                      mesh: Optional[Mesh] = None,
                      fsdp_axes: Tuple[str, ...] = ()):
    """Exact per-leaf PartitionSpecs for a param tree that may contain
    TernaryWeight leaves (whose scales have a size-1 contraction dim
    that must stay unsharded, and whose packed data dim is K/4).

    fsdp_axes: additionally shard each (large) weight over the DP axes
    (ZeRO-3 / FSDP layout) — applied to the largest unsharded divisible
    dim of the weight.
    """
    from repro.core.ternary import TernaryScales
    from repro.core.weights import TernaryWeight

    def weight_pspec(spec, shape):
        ps = spec_to_pspec(spec, rules)
        if fsdp_axes and mesh is not None and len(shape) >= 2:
            ps = zero_pspec(ps, shape, mesh, fsdp_axes)
        return ps

    def walk(spec, param):
        if isinstance(param, TernaryWeight):
            assert isinstance(spec, tuple)
            k_ax = len(spec) - 2
            data_ps = weight_pspec(spec, param.data.shape)
            sc_spec = tuple(None if i == k_ax else s
                            for i, s in enumerate(spec))
            if param.scales.pos.ndim == len(spec):
                sc_ps = spec_to_pspec(sc_spec, rules)
            else:
                sc_ps = P()
            scales = TernaryScales(sc_ps, sc_ps, param.scales.sym)
            return TernaryWeight(data_ps, scales, param.packed, param.k_dim)
        if isinstance(spec, tuple):
            shape = param.shape if hasattr(param, "shape") else ()
            return weight_pspec(spec, shape)
        assert isinstance(spec, dict) and isinstance(param, dict), (
            type(spec), type(param))
        return {k: walk(spec[k], param[k]) for k in param}

    return walk(spec_tree, params)


def zero_shard_tree(pspecs, shapes, mesh: Mesh):
    dp = dp_axis_names(mesh)

    def f(ps, shape_leaf):
        shp = tuple(shape_leaf.shape) if hasattr(shape_leaf, "shape") \
            else tuple(shape_leaf)
        return zero_pspec(ps, shp, mesh, dp)

    return jax.tree_util.tree_map(
        f, pspecs, shapes, is_leaf=lambda x: isinstance(x, P))
