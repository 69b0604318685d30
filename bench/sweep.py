"""Find an open-loop cell's knee once, on the chip: the same cell at a
list of arrival rates, one build, warm-up and window each (the
benchmark's own ``Cell.serve``), in one process.

    python3 bench/sweep.py --workload <name> --seed 3 --seconds 30 \\
        --rates 0.2,0.4,0.6

One JSON line per rate: offered and completed requests per second, the
backlog (requests sent and not finished) at the window's start and
end, TTFT p90, ITL p50, output tokens per second, and the share of
steps that spent the engine's whole token budget.  The knee is the
highest rate whose backlog does not grow through the window and whose
steps do not all spend the whole token budget; a cell
then runs at about 0.8 x the knee, fixed in its traffic file.  Not part
of a benchmark run.
"""
from __future__ import annotations

import json
import sys

import run_cell


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    run_cell.setup_env(run_cell.ROOT)
    c = run_cell.Cell(args.workload)
    run_cell.xla_flags(c.cfgfile)
    c.configure_jax()
    if run_cell.device_info(int(c.cell["chips"])) is None:
        print("sweep: needs the chip", file=sys.stderr)
        return 1
    import harness as H
    for rate in (float(r) for r in args.rates.split(",")):
        run, _, _, _ = c.serve(args.seed, args.seconds,
                               mix=dict(c.mix, rate_per_s=rate),
                               check_=False)
        full = [len(pos) >= run.token_budget for pos, _ in run.steps]
        sent = [s for s in run.sent if s.in_window]
        carried = [s for s in run.sent if not s.in_window]
        done_in = [s for s in run.sent if s.req.done and s.times
                   and s.times[-1] <= args.seconds]
        row = {
            "rate_per_s": rate,
            "sent": len(sent),
            "completed_per_s": len(done_in) / args.seconds,
            "backlog_start": len(carried),
            "backlog_end": sum(not s.req.done for s in run.sent),
            "ttft_p90_s": H.percentile(H.ttft_values(run), 90),
            "itl_p50_ms": 1e3 * (H.percentile(H.itl_gaps(run), 50) or 0.0),
            "output_tok_per_s": len(run.window_tokens()) / args.seconds,
            "grid_fill_pct": H.metric_reader("grid_fill_pct")(run),
            "budget_full_share": sum(full) / max(len(full), 1),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
