"""Loop-aware analysis of optimized HLO text.

XLA's cost_analysis counts while-loop bodies ONCE, which silently drops
~n_layers x the real FLOPs/collective bytes for scan-over-layers models.
This module re-derives both from the HLO text itself:

  * split the module into computations; build a per-computation symbol
    table (%name -> dtype/shape) so operand shapes resolve by name;
  * attribute dot FLOPs (2 * prod(result) * contraction) and collective
    result bytes to their computation;
  * map each `while` op to its condition/body computations, extract the
    trip count from the condition's compare constant, and propagate
    multipliers down the call graph (nested loops multiply).

Every number it produces is cross-checked against the analytic workload
model in benchmarks/roofline.py; disagreement > 2x is flagged there.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*\(?"
                     r"([a-z0-9]+)\[([\d,]*)\]")
_DOT_RE = re.compile(r"\bdot\(\s*%?([\w\.\-]+)")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_WHILE_RE = re.compile(
    r"\bwhile\(.*?\)(?:.*?)condition=%?([\w\.\-]+)(?:.*?)body=%?([\w\.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _numel(dims: str) -> int:
    if not dims:
        return 1
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n


_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _group_size(line: str) -> Optional[int]:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_V1_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return None


def _split_computations(hlo: str) -> Tuple[Dict[str, List[str]], str]:
    comps: Dict[str, List[str]] = {}
    entry = ""
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            m = _COMP_HDR_RE.match(line)
            if m and "{" in line:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
                continue
        if cur is not None and line.strip():
            comps[cur].append(line.strip())
    return comps, entry


def analyze_hlo(hlo: str, n_devices: int = 1) -> Dict[str, Any]:
    comps, entry = _split_computations(hlo)

    flops: Dict[str, float] = defaultdict(float)
    coll: Dict[str, List[Tuple[str, float, Optional[int]]]] = \
        defaultdict(list)
    while_edges: Dict[str, List[Tuple[str, str]]] = defaultdict(list)
    call_edges: Dict[str, List[str]] = defaultdict(list)

    for name, lines in comps.items():
        # symbol table: instruction name -> (dtype, dims)
        sym: Dict[str, Tuple[str, str]] = {}
        for line in lines:
            d = _DEF_RE.match(line)
            if d:
                sym[d.group(1)] = (d.group(2), d.group(3))
        for line in lines:
            d = _DEF_RE.match(line)
            wm = _WHILE_RE.search(line)
            if wm:
                while_edges[name].append((wm.group(1), wm.group(2)))
            for cm in _CALL_RE.finditer(line):
                call_edges[name].append(cm.group(1))
            cm2 = _COLL_RE.search(line)
            if cm2 and d and d.group(2) in _DTYPE_BYTES:
                size = _numel(d.group(3)) * _DTYPE_BYTES[d.group(2)]
                coll[name].append((cm2.group(1), size, _group_size(line)))
            dm = _DOT_RE.search(line)
            if dm and d:
                out_n = _numel(d.group(3))
                lhs = sym.get(dm.group(1))
                contraction = 1
                if lhs is not None:
                    lhs_dims = [int(x) for x in lhs[1].split(",") if x]
                    cdims = _LHS_CDIMS_RE.search(line)
                    if cdims and cdims.group(1):
                        for ci in cdims.group(1).split(","):
                            contraction *= lhs_dims[int(ci)]
                flops[name] += 2.0 * out_n * contraction

    def trip_count(cond_name: str) -> int:
        best = 1
        for line in comps.get(cond_name, []):
            if "compare" in line or "constant" in line:
                for c in _CONST_RE.finditer(line):
                    best = max(best, int(c.group(1)))
        return best

    mult: Dict[str, float] = defaultdict(float)

    def visit(name: str, m: float, depth=0):
        if depth > 64 or name not in comps:
            return
        mult[name] += m
        for cond, body in while_edges.get(name, []):
            tc = trip_count(cond)
            visit(body, m * tc, depth + 1)
            visit(cond, m * tc, depth + 1)
        for callee in call_edges.get(name, []):
            visit(callee, m, depth + 1)

    if entry:
        visit(entry, 1.0)
    for name in comps:
        if name not in mult:
            mult[name] = 1.0

    total_flops = sum(flops[n] * mult[n] for n in flops)
    raw_flops = sum(flops.values())

    coll_bytes: Dict[str, float] = defaultdict(float)
    coll_n: Dict[str, float] = defaultdict(float)
    wire: Dict[str, float] = defaultdict(float)
    for name, entries in coll.items():
        for kind, b, gsz in entries:
            m = mult[name]
            coll_bytes[kind] += b * m
            coll_n[kind] += m
            n = max(gsz or n_devices, 2)
            if kind == "all-gather":
                w = b * (n - 1) / n       # result-sized output gathered
            elif kind == "reduce-scatter":
                w = b * (n - 1)           # result is 1/n of the input
            elif kind == "all-reduce":
                w = 2 * b * (n - 1) / n
            elif kind == "all-to-all":
                w = b * (n - 1) / n
            else:                          # collective-permute
                w = b
            wire[kind] += w * m

    return {
        "dot_flops": total_flops,
        "dot_flops_unrolled_only": raw_flops,
        "loop_multiplier_effect": (total_flops / raw_flops
                                   if raw_flops else 1.0),
        "collective_result_bytes": dict(coll_bytes),
        "collective_counts": dict(coll_n),
        "collective_wire_bytes": dict(wire),
        "total_wire_bytes": sum(wire.values()),
        "n_computations": len(comps),
    }


# ---------------------------------------------------------------------------
# Fused-kernel HBM weight-stream accounting
# ---------------------------------------------------------------------------

def weight_stream_summary(report: Dict[str, int],
                          n_devices: int = 1) -> Dict[str, Any]:
    """Cost-model view of a serve cell's HBM weight traffic.

    ``report`` is serve/engine.weight_stream_report's aggregate (built
    from kernels/ops.weight_stream_stats over every TernaryWeight leaf):
    the fused single-launch kernels stream each weight tile once per
    matmul, the historical multi-launch route streams it once per phase
    x bit-plane.  Per-device numbers assume weights are fully sharded
    over the mesh (TP/2-D serving layouts — the dry-run's serving
    default), so they are the *lower bound* the roofline memory term
    should see; the ``fused_traffic_ratio`` is layout-independent.
    """
    fused = int(report["weight_bytes_streamed_fused"])
    unfused = int(report["weight_bytes_streamed_unfused"])
    nd = max(n_devices, 1)
    return {
        "weight_bytes_resident": int(report["weight_bytes_resident"]),
        "weight_bytes_streamed_fused": fused,
        "weight_bytes_streamed_unfused": unfused,
        "weight_bytes_streamed_fused_per_dev": fused // nd,
        "weight_bytes_streamed_unfused_per_dev": unfused // nd,
        "fused_traffic_ratio": (unfused / fused) if fused else 1.0,
    }


_KERNEL_RE = re.compile(r"^\s*(?:ROOT\s+)?%([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*="
                        r".*custom_call_target=\"tpu_custom_call\"")


def tpu_kernel_calls(hlo: str) -> Dict[str, int]:
    """Mosaic kernel launches in optimized HLO text, counted by kernel
    name (each ``pallas_call``'s ``name=``), one per call site: a call
    inside a loop body counts once."""
    counts: Dict[str, int] = defaultdict(int)
    for line in hlo.splitlines():
        m = _KERNEL_RE.match(line)
        if m:
            counts[m.group(1)] += 1
    return dict(counts)
