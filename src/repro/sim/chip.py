"""Chip/host roofline constants, keyed by ``device_kind`` — ONE home for
the numbers the serving stack and the benchmark analyses both price
against (the engine's swap-vs-recompute crossover and
``benchmarks/roofline.py``).

``chip_peaks()`` returns the row of the device this process runs on, as
JAX reports it (``jax.devices()[0].device_kind``).  A TPU kind missing
from ``CHIPS`` is an error, never a default.  On the ``cpu`` backend
nothing is measured; the code then prices against ``MODELLED_KIND``,
the chip this repository targets, by name.

Distinct from ``sim/hwmodel.py``, which holds the *paper's* TiM-tile
constants (SPICE/RTL-calibrated, 32 nm) — those model the accelerator
being reproduced, these model the chip the reproduction runs on.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    kind: str
    peak_flops: float      # bf16 matmul FLOP/s
    hbm_bw: float          # HBM bytes/s
    link_bw: float         # bytes/s per ICI link (collective wire model)
    host_link_bw: float    # host<->device bytes/s (preemption swap)
    source: str


CHIPS = {
    "TPU v5 lite": ChipPeaks(
        kind="TPU v5 lite",
        peak_flops=197e12,
        hbm_bw=819e9,
        # 1,600 Gbit/s of interconnect per chip over four ICI links
        link_bw=50e9,
        # not published: a PCIe-class assumption until measured
        host_link_bw=16e9,
        source="Google Cloud documentation, 'TPU v5e' (bf16 peak, HBM "
               "bandwidth, ICI); host link assumed",
    ),
}

# what a CPU run models: the chip the serving path is built for
MODELLED_KIND = "TPU v5 lite"


def chip_peaks(kind: Optional[str] = None) -> ChipPeaks:
    """The row for ``kind``, or for this process's first device."""
    if kind is None:
        import jax
        dev = jax.devices()[0]
        kind = MODELLED_KIND if dev.platform == "cpu" else dev.device_kind
    try:
        return CHIPS[kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {kind!r}; add "
                       f"a row with its source to repro.sim.chip.CHIPS"
                       ) from None
