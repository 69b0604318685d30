"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Prints, as CSV sections:
  1. the paper-table reproductions (one per table/figure, sim-backed);
  2. kernel wall-clock microbenchmarks (name,us_per_call,derived);
  3. the roofline table from the dry-run artifacts (if present).
"""
from __future__ import annotations

import csv
import io
import os


def _print_rows(name, rows) -> None:
    print(f"\n## {name}")
    if not rows:
        print("(no rows)")
        return
    keys = []
    for r in rows:
        for k in r:
            if k not in keys:
                keys.append(k)
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=keys)
    w.writeheader()
    for r in rows:
        w.writerow(r)
    print(buf.getvalue().rstrip())


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import paper_tables
    for fn in paper_tables.ALL:
        name, rows = fn()
        _print_rows(name, rows)

    import jax

    from benchmarks.kernel_bench import bench
    rows = bench()
    _print_rows(f"kernel_microbench, wall clock on "
                f"{jax.devices()[0].platform} (name,us_per_call,derived)",
                rows)

    from benchmarks.roofline import advice, roofline_table
    reports = [p for p in ("dryrun_single.json", "dryrun_multi.json",
                           "dryrun_perf.json", "dryrun_tuned.json",
                           "dryrun_tuned_multi.json")
               if os.path.exists(p)]
    if reports:
        rows = roofline_table(reports)
        flat = []
        for r in rows:
            flat.append({
                "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
                "variant": r["variant"],
                "t_compute_s": f"{r['t_compute_s']:.3e}",
                "t_memory_s": f"{r['t_memory_s']:.3e}",
                "t_collective_s": f"{r['t_collective_s']:.3e}",
                "dominant": r["dominant"],
                "model_over_hlo": round(r["model_over_hlo"], 3),
                "roofline_fraction": round(r["roofline_fraction"], 4),
                "advice": advice(r),
            })
        _print_rows("roofline (from dry-run)", flat)
    else:
        print("\n## roofline: no dryrun_*.json found — run "
              "PYTHONPATH=src python -m repro.launch.dryrun first")


if __name__ == "__main__":
    main()
