"""Reduce a profiler trace (``.xplane.pb``) of one window to what the
metric readers read.

  window     the host span ``bench.window`` (the benchmark's own
             TraceAnnotation around the measured window)
  busy       per device plane, the union of its operation intervals
             inside the window; averaged over the devices that ran any
  ops        device seconds per operation name: the HLO instruction's
             name without ``%`` and instance suffix (``%fusion.12 = ...``
             -> ``fusion``, ``tim_matmul_fused``); control-flow
             containers (``while``, ``conditional``, ``call``), whose
             events span the operations inside them, are left out
  gaps       the device's idle intervals inside the window, each named
             by the benchmark span (``bench.step``, ``bench.stamp``,
             ``bench.submit``) that covers most of it, else ``other``

Device planes are the ``/device:TPU:<n>`` planes; their operations are
the events of the line named ``XLA Ops``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=.*)?$", re.S)
CONTAINERS = ("while", "conditional", "call")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPANS = ("bench.submit", "bench.step", "bench.stamp")


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(event_name: str) -> str:
    """``%tim_matmul_fused.3 = bf16[...] custom-call(...)`` ->
    ``tim_matmul_fused``."""
    m = _NAME.match(event_name.strip())
    return m.group(1) if m else event_name


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap(a0, a1, spans, starts):
    """Overlap of [a0, a1] with ``spans``: one span name's intervals,
    sorted and disjoint, whose starts are ``starts``."""
    total, i = 0.0, bisect.bisect_left(starts, a1) - 1
    while i >= 0 and spans[i][1] > a0:
        total += max(0.0, min(a1, spans[i][1]) - max(a0, spans[i][0]))
        i -= 1
    return total


def reduce_events(host: Dict[str, List[Tuple[float, float]]],
                  devices: List[List[Tuple[str, float, float]]]) -> Dict:
    """The reduction on plain data: ``host`` maps span name to its
    (start, end) intervals in ns; ``devices`` holds, per device, its
    (op name, start, end) events in ns on the same clock."""
    win = host.get("bench.window")
    if not win:
        raise ValueError("the trace holds no bench.window span")
    spans = {s: sorted(host.get(s, [])) for s in SPANS}
    starts = {s: [a for a, _ in v] for s, v in spans.items()}
    w0, w1 = win[0]
    window_s = (w1 - w0) / 1e9
    ops: Dict[str, float] = defaultdict(float)
    busy_total, used = 0.0, 0
    gaps: Dict[str, float] = defaultdict(float)
    longest: List[Tuple[str, float]] = []
    for events in devices:
        inside = [(op_name(n), max(a, w0), min(b, w1))
                  for n, a, b in events if b > w0 and a < w1]
        inside = [e for e in inside if e[0] not in CONTAINERS]
        if not inside:
            continue
        used += 1
        for n, a, b in inside:
            ops[n] += (b - a) / 1e9
        busy = _union([(a, b) for _, a, b in inside])
        busy_total += sum(b - a for a, b in busy) / 1e9
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            cover = {s: _overlap(g0, g1, spans[s], starts[s]) for s in SPANS}
            name = max(cover, key=cover.get)
            if cover[name] <= 0.5 * (g1 - g0):
                name = "other"
            gaps[name] += (g1 - g0) / 1e9
            longest.append((name, (g1 - g0) / 1e9))
    if not used:
        raise ValueError("no device operation ran inside the window")
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    longest.sort(key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "busy_s": busy_total / used,
        "devices": used,
        "ops": dict(ops),
        "idle_by_span": dict(gaps),
        "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                      "idle_gaps": [[n, s] for n, s in longest[:10]]},
    }


def load(path: str):
    """(host spans, device events) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
            devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return dict(host), devices


def reduce_dir(trace_dir) -> Dict:
    return reduce_events(*load(find_xplane(trace_dir)))
