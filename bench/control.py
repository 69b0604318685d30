"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <name> --seeds 5,6,7 --seconds 30

For each seed, in one process: the cell is served as a benchmark run
serves it (same set-up, traffic and window), and the checks that decide
``correct`` are made twice: on the program's served tokens and KV (the
lower reading of each compared number), and with the control in the
program's place, the reference in float8 e4m3 instead of bfloat16: the
tokens it puts first at the same positions and its own first-layer KV
(the upper reading).  The control has to come out not correct.  One
JSON line per seed.  Not part of a benchmark run.
"""
from __future__ import annotations

import json
import sys

import run_cell


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run_cell.setup_env(run_cell.ROOT)
    c = run_cell.Cell(args.workload)
    run_cell.xla_flags(c.cfgfile)
    c.configure_jax()
    if run_cell.device_info(int(c.cell["chips"])) is None:
        print("control: needs the chip", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        run, sample, kv, _ = c.serve(seed, args.seconds)
        print(json.dumps({"seed": seed, **judged(c.cfgfile, run, seed,
                                                 sample, kv)}), flush=True)
    return 0


def judged(cfgfile, run, seed, sample, kv):
    """Each side's checks through ``check.compare``, and whether each
    comes out correct."""
    import check
    out = check.compare(cfgfile, run.dims, seed, sample, kv, run,
                        judge=("served", "control"))
    return {side: {"correct": all(x["ok"] for x in checks.values()),
                   "checks": {k: x["value"] for k, x in checks.items()}}
            for side, checks in out.items()}


if __name__ == "__main__":
    sys.exit(main())
