"""The engine's phases in a traced window, and its requests' stamps.

The program brackets each phase of ``ServeEngine.step`` in a profiler
span (``serve.step`` around ``serve.admit``, ``serve.schedule``,
``serve.upload``, ``serve.launch``, ``serve.account``, ``serve.fetch``,
``serve.emit``; ``serve.draft`` when speculating).  They land in the
same trace as the benchmark's ``bench.*`` spans and the device's
operations, on one clock.  From that trace:

  idle_by_phase  device-idle seconds in the window, split exactly by
                 the innermost host span over each idle nanosecond
                 (``serve.*`` inside ``serve.step`` inside
                 ``bench.step``; ``bench.submit``, ``bench.stamp``);
                 time under no span is ``other``.  Averaged over the
                 devices that ran any operation, as ``trace_reduce``
                 averages busy time, so on one device the values sum to
                 window - busy
  steps          ``serve.step`` spans that start in the window
  longest        the ten longest idle gaps, each named by the span
                 that covers most of it

The window, the device planes and their operations are read as
``trace_reduce`` reads them, whose keys this leaves alone.  A program
without the spans gives ``steps`` 0 and no ``serve.*`` key.

The program also stamps each request on the host clock
(``Request.t_submit``, ``t_admit``, ``t_first``); ``request_waits``
turns them into each request's wait for a slot and its prefill.
"""
from __future__ import annotations

import heapq
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import harness as H
import trace_reduce as T

PREFIXES = ("bench.", "serve.")
# the phases in which the host works while the device may sit idle
# (not serve.fetch, where the host waits on the device)
HOST_PHASES = ("serve.admit", "serve.schedule", "serve.upload",
               "serve.launch", "serve.account", "serve.emit")
TRACE = ".bench_trace"    # run_cell.TRACE: the traced window's profile

Span = Tuple[int, int, str]


def load(path: str):
    """(host spans, device events) from an ``.xplane.pb``, as
    ``trace_reduce.load`` gives them but with the program's ``serve.*``
    spans kept beside the benchmark's ``bench.*``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    devices = []
    for plane in pd.planes:
        if T.DEVICE_PLANE.match(plane.name):
            devices.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for line in plane.lines
                            if line.name == T.OPS_LINE
                            for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        host[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return dict(host), devices


def innermost(spans: List[Span]) -> List[Span]:
    """Cut the time ``spans`` cover into disjoint pieces, in order, each
    named by the innermost span over it: of the spans covering it, the
    one that started last (the shorter on a tie).  Spans of one thread
    nest, so that is the one the thread was in."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    bounds = sorted({t for a, b, _ in spans for t in (a, b)})
    live: list = []           # (-start, end, n, name): innermost on top
    out: List[Span] = []
    j = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while j < len(order) and order[j][0] <= t0:
            a, b, name = order[j]
            heapq.heappush(live, (-a, b, j, name))
            j += 1
        while live and live[0][1] <= t0:
            heapq.heappop(live)
        if live:
            out.append((t0, t1, live[0][3]))
    return out


def _idle(events, w0: int, w1: int) -> Optional[List[Tuple[int, int]]]:
    """The device's idle intervals inside [w0, w1]: the window less the
    union of its operations, as ``trace_reduce`` counts busy time; None
    if no operation ran inside the window."""
    busy = T._union([(max(a, w0), min(b, w1)) for n, a, b in events
                     if b > w0 and a < w1
                     and T.op_name(n) not in T.CONTAINERS])
    if not busy:
        return None
    edges = [w0] + [x for ab in busy for x in ab] + [w1]
    return [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]


def reduce_events(host: Dict[str, List[Tuple[int, int]]],
                  devices: List[List[Tuple[str, int, int]]]) -> Dict:
    """The reduction on plain data, in the shapes ``trace_reduce``
    takes: span name to (start, end) intervals in ns, and per device
    its (op name, start, end) events in ns on the same clock."""
    win = host.get("bench.window")
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = win[0]
    spans = [(max(a, w0), min(b, w1), name)
             for name, ivs in host.items() if name != "bench.window"
             for a, b in ivs if b > w0 and a < w1]
    pieces = innermost(spans)
    idle: Dict[str, int] = defaultdict(int)
    longest: List[Tuple[str, int]] = []
    used = 0
    for events in devices:
        gaps = _idle(events, w0, w1)
        if gaps is None:
            continue
        used += 1
        k = 0
        for g0, g1 in gaps:
            while k < len(pieces) and pieces[k][1] <= g0:
                k += 1
            cover: Dict[str, int] = defaultdict(int)
            i = k
            while i < len(pieces) and pieces[i][0] < g1:
                a, b, name = pieces[i]
                cover[name] += min(b, g1) - max(a, g0)
                i += 1
            rest = (g1 - g0) - sum(cover.values())
            if rest:
                cover["other"] += rest
            for name, ns in cover.items():
                idle[name] += ns
            longest.append((max(cover, key=cover.get), g1 - g0))
    if not used:
        raise ValueError("no device operation ran inside the window")
    longest.sort(key=lambda kv: -kv[1])
    return {
        "idle_by_phase": {n: ns / 1e9 / used for n, ns in idle.items()},
        "steps": sum(w0 <= a < w1 for a, _ in host.get("serve.step", [])),
        "longest": [[n, ns / 1e9] for n, ns in longest[:10]],
    }


_CACHE: Dict[Tuple[str, float], Dict] = {}


def of_run(run: "H.Run") -> Optional[Dict]:
    """The reduction of a traced run's profile (``run_cell`` leaves it
    in the checkout's ``.bench_trace``), computed once per trace; the
    first time it prints the split and the longest gaps to standard
    error.  None for a run without a trace."""
    if run.trace is None:
        return None
    try:
        path = T.find_xplane(run.bench_dir.parent / TRACE)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        r = _CACHE[key] = reduce_events(*load(path))
        H.log_err(f"phases: {r['steps']} serve.step spans; idle s by "
                  f"innermost span {r['idle_by_phase']}")
        for name, s in r["longest"]:
            H.log_err(f"phases: idle gap {1e3 * s:.3f} ms under {name}")
    return _CACHE[key]


def request_waits(run: "H.Run") -> Optional[List[Tuple[float, Optional[float]]]]:
    """Per request due in the window, (wait for a slot, prefill) in
    seconds on the engine's host-clock stamps: ``t_admit - t_submit``
    and ``t_first - t_admit``, each cut at the window's end; prefill is
    None for a request not admitted by then.  None where the program
    keeps no stamps."""
    due = [s for s in run.sent if s.in_window]
    if not due or getattr(due[0].req, "t_submit", None) is None:
        return None
    # the window's start on the engine's clock: each request was sent
    # (``Sent.sent``, seconds from the window's start) just before the
    # engine stamped it, so the least difference is the closest
    end = min(s.req.t_submit - s.sent for s in run.sent) + run.seconds
    out = []
    for s in due:
        r = s.req
        admit = r.t_admit if r.t_admit is not None else end
        wait = max(0.0, min(admit, end) - r.t_submit)
        prefill = None
        if admit < end:
            first = r.t_first if r.t_first is not None else end
            prefill = min(first, end) - admit
        out.append((wait, prefill))
    return out
