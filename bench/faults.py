"""The timed path broken underneath, to show that ``correct`` catches
it: on the chip at a cell's own size, and in the CPU tests.

    python3 bench/faults.py --workload <name> --seeds 7,8 --seconds 10 \\
        --kinds state,half,token

For each kind and seed, in one process, one benchmark run with the
program's own functions replaced for its duration, printing its result
line.  The kinds:

  token   every greedy token altered where it is produced (+1)
  state   the step returns the KV state it was given, unchanged
  half    every second slot that the step schedules left out of it

Not part of a benchmark run.
"""
from __future__ import annotations

import contextlib
import sys

import run_cell

KINDS = ("token", "state", "half")


@contextlib.contextmanager
def broken(kind: str):
    """The program's step or sampler replaced for the duration."""
    import jax.numpy as jnp
    import repro.serve.engine as E
    if kind not in KINDS:
        raise ValueError(f"fault {kind!r}: one of {KINDS}")
    greedy, make_step = E.greedy_token, E.make_paged_unified_step
    if kind == "token":
        E.greedy_token = lambda lg: (greedy(lg) + 1) % lg.shape[-1]
    else:
        def make(cfg):
            inner = make_step(cfg)

            def step(params, batch, caches, cache_len, n_new, *rest):
                if kind == "half":
                    on = n_new > 0
                    n_new = jnp.where(on & (jnp.cumsum(on) % 2 == 0), 0,
                                      n_new)
                    return inner(params, batch, caches, cache_len, n_new,
                                 *rest)
                lg, _ = inner(params, batch, caches, cache_len, n_new, *rest)
                return lg, caches
            return step
        E.make_paged_unified_step = make
    try:
        yield
    finally:
        E.greedy_token, E.make_paged_unified_step = greedy, make_step


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--kinds", default=",".join(KINDS))
    args = ap.parse_args(argv)
    # before the program is imported: its sources on the path, and the
    # configuration's compiler flags
    run_cell.setup_env(run_cell.ROOT)
    run_cell.xla_flags(run_cell.Cell(args.workload).cfgfile)
    for kind in args.kinds.split(","):
        for seed in args.seeds.split(","):
            print(f"fault {kind} seed {seed}", file=sys.stderr, flush=True)
            with broken(kind):
                rc = run_cell.main(["--workload", args.workload, "--seed",
                                    seed, "--seconds", args.seconds])
            if rc:
                return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
