"""Roofline analysis (deliverable g): three terms per (arch x shape x
mesh) from the dry-run artifacts.

Hardware constants: the ``repro.sim.chip`` row of the device this runs
on (the TPU v5e row, "TPU v5 lite", when run on the CPU): 197 TFLOP/s
bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

Term definitions (all PER DEVICE, seconds):
  compute    = HLO_dot_FLOPs_per_device / 197e12
               (loop-aware count from launch/hlo_analysis; the raw XLA
               cost_analysis undercounts scan bodies and is reported
               alongside for reference)
  memory     = (argument + output bytes per device) / 819e9
               (compiled memory_analysis; a traffic *lower bound* —
               exact for decode where weights+cache stream once, under-
               estimates train activation recirculation)
  collective = per-device wire bytes (ring-model census over the
               partitioned HLO, loop-aware) / 50e9

MODEL_FLOPS = 6*N(active)*tokens for train, 2*N(active)*tokens for
inference — the useful-work yardstick; MODEL/HLO ratio exposes remat
and redundant compute.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

# chip constants shared with the serving engine's swap-vs-recompute
# crossover (ONE home: repro/sim/chip.py, keyed by device_kind)
from repro.sim.chip import chip_peaks

_PARAM_CACHE: Dict[str, Dict[str, float]] = {}


def arch_params(arch: str) -> Dict[str, float]:
    """Analytic total / active parameter counts (no device init)."""
    if arch in _PARAM_CACHE:
        return _PARAM_CACHE[arch]
    import jax
    from repro.configs import get_config
    from repro.models import transformer as tfm

    cfg = get_config(arch)
    sds = jax.eval_shape(lambda k: tfm.init(cfg, k),
                         jax.random.PRNGKey(0))
    total = sum(int(l.size) for l in jax.tree_util.tree_leaves(sds))
    active = total
    if cfg.moe is not None:
        # inactive share of expert weights
        layers = sds["layers"]
        expert_elems = 0
        for j, spec in enumerate(cfg.layout):
            if spec.ffn == "moe":
                blk = layers[f"b{j}"]["ffn"]
                for k in ("gate", "up", "down"):
                    if k in blk:
                        expert_elems += int(blk[k].size)
        frac = cfg.moe.top_k / cfg.moe.num_experts
        active = total - expert_elems * (1 - frac)
    _PARAM_CACHE[arch] = {"total": float(total), "active": float(active)}
    return _PARAM_CACHE[arch]


def model_flops(arch: str, shape: Dict[str, Any], kind: str) -> float:
    from repro.configs import SHAPES
    sc = SHAPES[shape] if isinstance(shape, str) else shape
    p = arch_params(arch)
    n_act = p["active"]
    if sc.kind == "train":
        tokens = sc.seq_len * sc.global_batch
        return 6.0 * n_act * tokens
    if sc.kind == "prefill":
        tokens = sc.seq_len * sc.global_batch
        return 2.0 * n_act * tokens
    if sc.kind == "mixed":
        # canonical unified-step fill: every slot decodes one token
        # except one streaming a full prefill chunk.  The (slots, chunk)
        # grid lowers more FLOPs than this — MODEL/HLO exposes the
        # padding overhead the token-budget scheduler amortizes against
        # the shared weight stream.  Paged cells with a prefix-cache
        # hit_rate shrink the useful chunk further: hit tokens are
        # served from shared KV blocks, not recomputed.
        return 2.0 * n_act * sc.scheduled_mixed_tokens
    # decode: one token per sequence
    return 2.0 * n_act * sc.global_batch


def kv_token_bytes_per_head(hd: int, kv_dtype: str) -> int:
    """HBM bytes of ONE token's K+V in one KV head (the
    init_paged_caches layout; int8 = codes + the bf16 per-(token, head)
    scale that rides alongside).  THE formula — kernel_bench's
    paged_attn_* rows import it so the baselines cannot drift from the
    roofline gather pricing."""
    if kv_dtype == "int8":
        return 2 * (hd * 1 + 2)
    return 2 * hd * 2


def _kv_write_bytes(arch: str, tokens: int) -> float:
    """HBM bytes of the per-layer K+V cache writes for ``tokens``
    tokens — what a prefix-cache hit skips (global, pre-sharding)."""
    from repro.configs import get_config
    cfg = get_config(arch)
    n_attn = cfg.n_periods * sum(1 for s in cfg.layout
                                 if s.mixer == "attn")
    per_head = kv_token_bytes_per_head(cfg.hd, cfg.kv_cache_dtype)
    return float(tokens) * n_attn * cfg.n_kv_heads * per_head


def roofline_row(cell: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if cell.get("status") != "ok":
        return None
    n_dev = cell["n_devices"]
    hlo = cell.get("hlo", {})
    mem = cell.get("memory", {})
    flops_dev = hlo.get("dot_flops", 0.0)
    mem_bytes = mem.get("argument_size_in_bytes", 0) + \
        mem.get("output_size_in_bytes", 0)
    wire = hlo.get("total_wire_bytes", 0.0)

    chip = chip_peaks()
    t_compute = flops_dev / chip.peak_flops
    t_memory = mem_bytes / chip.hbm_bw
    t_collective = wire / chip.link_bw
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    dominant = max(terms, key=terms.get)
    step_time = max(terms.values())

    mf = model_flops(cell["arch"], cell["shape"], cell["shape"])
    if cell.get("scheduled_tokens"):
        # mixed cells report BOTH the launched grid and the scheduled
        # token count; useful work is priced from the cell's own
        # scheduled_tokens — the padded (slots, chunk) grid only
        # inflates the lowered HLO term, it never adds useful FLOPs.
        mf = 2.0 * arch_params(cell["arch"])["active"] \
            * cell["scheduled_tokens"]
    mf_dev = mf / n_dev
    useful_frac = mf_dev / flops_dev if flops_dev else 0.0
    # roofline fraction: useful model FLOP/s achieved at the bound vs peak
    ach_flops = mf_dev / step_time if step_time else 0.0
    row = {
        "arch": cell["arch"], "shape": cell["shape"],
        "mesh": cell["mesh"], "variant": cell.get("variant", "baseline"),
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "model_flops_per_dev": mf_dev,
        "hlo_flops_per_dev": flops_dev,
        "model_over_hlo": useful_frac,
        "roofline_fraction": ach_flops / chip.peak_flops,
        "hbm_gb_per_dev": mem_bytes / 2**30,
        "temp_gb_per_dev": mem.get("temp_size_in_bytes", 0) / 2**30,
        "wire_mb_per_dev": wire / 2**20,
    }
    if "grid_tokens" in cell:
        # mixed cells: scheduled vs launched-grid accounting.  The
        # padding_efficiency (< 1 on the padded step, ~1 on the
        # token-packed step) is the fraction of grid rows doing real
        # work — the same digest serve/metrics.py reports live.
        grid = cell["grid_tokens"]
        row["sched_tokens"] = cell.get("scheduled_tokens", 0)
        row["grid_tokens"] = grid
        row["padding_efficiency"] = \
            row["sched_tokens"] / grid if grid else 0.0
    if "prefix_hit_rate" in cell:
        # paged mixed cell: the grid (and so every lowered term) is
        # identical to the unpaged one — the win is useful work (the
        # reduced model_flops above).  The hit tokens also skip their
        # per-layer KV pool writes: price that HBM saving explicitly.
        row["prefix_hit_rate"] = cell["prefix_hit_rate"]
        row["prefix_hit_tokens"] = cell.get("prefix_hit_tokens", 0)
        row["sched_tokens"] = cell.get("scheduled_tokens", 0)
        saved = _kv_write_bytes(cell["arch"],
                                row["prefix_hit_tokens"]) / n_dev
        row["kv_write_bytes_saved_per_dev"] = saved
        row["t_memory_shared_s"] = max(t_memory - saved / chip.hbm_bw, 0.0)
    if "gather_context_tokens" in cell:
        # paged-attention gather pricing (the kernel_bench paged_attn_*
        # rows, per-cell): the XLA-gather route re-materializes every
        # scan chunk's KV in HBM — one copy write plus one copy read on
        # top of the pool read the memory term already prices — while
        # the Pallas kernel's in-VMEM block gather adds nothing.  The
        # t_memory above IS the kernel route's floor; the XLA route
        # pays the extra round trip.
        extra = 2.0 * _kv_write_bytes(
            cell["arch"], cell["gather_context_tokens"]) / n_dev
        row["gather_bytes_saved_per_dev"] = extra
        row["t_memory_xla_gather_s"] = t_memory + extra / chip.hbm_bw
    if cell.get("draft_tokens"):
        # self-speculative serve cell: the verify grid's FLOPs are
        # already in the lowered terms (draft tokens are just extra
        # n_new rows), but the DRAFT passes run outside the dry-run
        # step — price them at the bit-serial rate.  A bit-serial
        # matmul lowers one pass per activation bit plane
        # (kernels/ops.weight_stream_stats), so a draft token through
        # the int2 encoding costs bitserial_pass_ratio(2, 4) = 0.5 of
        # a target token's passes — the PR-2 act-bits crossover,
        # re-used as the speculation overhead price.
        from repro.kernels.ops import bitserial_pass_ratio
        ratio = bitserial_pass_ratio(cell.get("draft_bits", 2),
                                     cell.get("target_bits", 4))
        n_act = arch_params(cell["arch"])["active"]
        draft_flops_dev = \
            2.0 * n_act * cell["draft_tokens"] * ratio / n_dev
        row["draft_cost_ratio"] = ratio
        row["draft_flops_per_dev"] = draft_flops_dev
        row["t_compute_spec_s"] = t_compute \
            + draft_flops_dev / chip.peak_flops
        row["spec_acceptance_rate"] = \
            cell.get("accepted_tokens", 0) / cell["draft_tokens"]
    ws = cell.get("weight_stream")
    if ws:
        # fused-kernel weight-stream terms (serve cells): the memory
        # term above prices one weight stream (argument bytes read
        # once); the unfused multi-launch route would have re-streamed
        # the extra bytes on top of it.
        extra = (ws["weight_bytes_streamed_unfused_per_dev"]
                 - ws["weight_bytes_streamed_fused_per_dev"])
        row.update({
            "weight_stream_fused_gb_per_dev":
                ws["weight_bytes_streamed_fused_per_dev"] / 2**30,
            "weight_stream_unfused_gb_per_dev":
                ws["weight_bytes_streamed_unfused_per_dev"] / 2**30,
            "fused_traffic_ratio": ws["fused_traffic_ratio"],
            "t_memory_unfused_s": t_memory + max(extra, 0) / chip.hbm_bw,
        })
    return row


def load_report(path: str) -> List[Dict[str, Any]]:
    with open(path) as f:
        return json.load(f)


def roofline_table(paths=("dryrun_single.json",)) -> List[Dict[str, Any]]:
    rows = []
    for p in paths:
        if not os.path.exists(p):
            continue
        for cell in load_report(p):
            r = roofline_row(cell)
            if r:
                rows.append(r)
    return rows


def advice(row: Dict[str, Any]) -> str:
    """One sentence on what would move the dominant term down."""
    d = row["dominant"]
    if d == "compute":
        if row["model_over_hlo"] < 0.5:
            return ("compute-bound with low useful fraction: cut remat "
                    "recompute / fuse epilogues")
        return ("compute-bound near useful peak: only lower-precision "
                "matmuls (int8 ternary path) move this")
    if d == "memory":
        return ("memory-bound: shrink resident bytes — 2-bit packed "
                "ternary weights cut weight traffic 4x vs int8")
    return ("collective-bound: reshard to remove the largest gathers "
            "(weight-gather -> 2D sharding, or overlap with compute)")


def print_table(rows) -> None:
    hdr = (f"{'arch':24s}{'shape':12s}{'mesh':10s}{'var':9s}"
           f"{'t_comp':>9s}{'t_mem':>9s}{'t_coll':>9s} {'dom':10s}"
           f"{'MF/HLO':>7s}{'roofl%':>7s}")
    print(hdr)
    for r in rows:
        print(f"{r['arch']:24s}{r['shape']:12s}{r['mesh']:10s}"
              f"{r['variant'][:8]:9s}"
              f"{r['t_compute_s']:>9.2e}{r['t_memory_s']:>9.2e}"
              f"{r['t_collective_s']:>9.2e} {r['dominant']:10s}"
              f"{r['model_over_hlo']:>7.2f}"
              f"{100*r['roofline_fraction']:>6.1f}%")


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--reports", nargs="*",
                    default=["dryrun_single.json", "dryrun_multi.json"])
    args = ap.parse_args()
    rows = roofline_table(args.reports)
    print_table(rows)


if __name__ == "__main__":
    main()
