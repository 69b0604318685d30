"""Useful work of paged attention in one engine step.

Each scheduled query token at position p attends to its p + 1 cached
positions: QK and PV are 2 * heads * head_dim operations per position
each.  Each slot's cached keys and values are read once per step,
however many of its tokens the step holds: 2 (K and V) * kv_heads *
head_dim * 2 bytes (bfloat16) per position, over every layer.
"""
from __future__ import annotations

import numpy as np

KV_BYTES = 2  # bfloat16


def work(dims, cfgfile, positions: np.ndarray, contexts: np.ndarray):
    """(bf16 operations, HBM bytes) for one step: ``positions`` of the
    scheduled query tokens, ``contexts`` the cached length of each slot
    that took part."""
    per_pos_ops = 4 * dims.n_heads * dims.hd * dims.n_layers
    ops = per_pos_ops * float(np.sum(positions + 1))
    per_pos_bytes = 2 * dims.n_kv * dims.hd * KV_BYTES * dims.n_layers
    return ops, per_pos_bytes * float(np.sum(contexts))
