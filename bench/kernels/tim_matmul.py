"""Useful work of the TiM ternary matmuls in one engine step.

Operations: 2 per weight per scheduled token (one multiply-accumulate),
counted once: not per activation phase, S/T pass or padded grid row.
Bytes: the stored weight codes, read once per step (1 byte per code as
int8, 1/4 byte when 2-bit packed); activations are negligible beside
them.  Both come from the configuration's widths, never from the
kernel's call shapes, so a later kernel or layout reads against the
same work.
"""
from __future__ import annotations


def linear_weights(dims) -> int:
    """Ternary weights of the whole stack (every layer's seven matmuls)."""
    return dims.n_layers * sum(i * o for _, i, o, _ in dims.linears())


def work(dims, cfgfile, positions, contexts):
    """(int8 operations, HBM bytes) for one step: ``positions`` of the
    scheduled tokens (only their number counts here)."""
    n = linear_weights(dims)
    packed = bool(cfgfile["program"]["pack"])
    return 2 * n * len(positions), n * (0.25 if packed else 1.0)
