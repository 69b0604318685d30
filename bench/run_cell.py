"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for.  The cell (``BENCHMARK.json``: configuration x traffic mix)
is served through the program's ``ServeEngine`` with random weights
from the seed: set-up (weights, compile or compile-cache load, the
mix's primer and warm-up) is timed as ``setup_s``; then the mix runs
for ``--seconds`` and every output token is stamped on the host clock.
``--trace 1`` records a profiler trace of the window and reports the
cell's per-layer metrics instead of its end-to-end ones.

At the window's close the first layer's KV of every request in flight
is read back through its block table; then a sample of the finished
requests, drawn from the seed and holding the longest, is served to
its end, the engine is freed, and both are checked against the plain
reference (``bench/reference.py``): the gap by which each served
token's logit lies below the reference's best, and the cached K and V
against the reference's.  The
numbers compared are printed beside their limits as the last lines of
standard error and, last, in the result line.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` when
traced) and ``checks``.  Off a TPU, or with fewer chips than the cell
asks for, the command exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ".jax_cache"           # JAX's persistent compile cache, in the checkout
TRACE = ".bench_trace"         # the traced window's profile, in the checkout


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_env(root: pathlib.Path) -> None:
    """Before JAX is imported: the compile cache inside the checkout,
    compiler logs off, and the program's sources on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / CACHE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))


def xla_flags(cfgfile) -> None:
    """Before JAX is imported: compile as the configuration's numerics
    state.  With ``xla_allow_excess_precision`` false, XLA rounds to the
    compute dtype wherever the program casts, instead of carrying f32
    on (its default), which ternary thresholds turn into other codes."""
    if cfgfile["numerics"].get("xla_allow_excess_precision", True):
        return
    flag = "--xla_allow_excess_precision=false"
    have = os.environ.get("XLA_FLAGS", "")
    if flag not in have:
        os.environ["XLA_FLAGS"] = f"{have} {flag}".strip()


def device_info(need: int):
    """The accelerator, or None: only a TPU with enough chips runs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        return None
    return devs


class Cell:
    """A cell's files, found by name, and the program built for it."""

    def __init__(self, workload: str, root: pathlib.Path = ROOT):
        import harness as H
        self.root = root
        bench = H.benchmark(root)
        self.cell = H.workload(bench, workload)
        self.cfgfile = H.config_file(bench, self.cell["config"], root)
        self.mix = H.traffic_file(self.cell["traffic"], root / "bench")
        self.bench = bench

    def configure_jax(self):
        import jax
        jax.config.update("jax_compilation_cache_dir", str(self.root / CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def serve(self, seed: int, seconds: float, trace: bool = False,
              t_start: float = None, mix: dict = None, check_: bool = True):
        """Build the program from the seed, run set-up and the window
        (under ``mix`` in place of the cell's, where given).  Returns the
        run record (program state already dropped), the checked sample,
        the KV read back at the window's close and the device's peak
        memory; ``check_`` false skips the drain and the read-backs."""
        import harness as H
        import check
        import traffic_gen
        from reference import dims_of, model_key
        from repro.serve.engine import ServeEngine, init_serving
        t_start = time.perf_counter() if t_start is None else t_start
        dims = dims_of(self.cfgfile)
        cfg = H.program_config(self.cfgfile)
        params = init_serving(cfg, model_key(seed))
        srv = self.cfgfile["serving"]
        engine = ServeEngine(params, cfg, batch_slots=int(srv["slots"]),
                             max_len=int(srv["max_len"]))
        traffic = traffic_gen.Traffic(mix or self.mix, dims.vocab, seed,
                                      seconds)
        check.compile_warm(engine, dims.vocab)
        trace_dir = None
        if trace:
            trace_dir = self.root / TRACE
            shutil.rmtree(trace_dir, ignore_errors=True)
        t_setup_end = []
        run = H.drive(engine, traffic, seconds, trace_dir=trace_dir,
                      record_steps=trace or not check_,
                      on_window_start=lambda: t_setup_end.append(
                          time.perf_counter()))
        run.cell, run.cfgfile, run.dims = self.cell["name"], self.cfgfile, dims
        run.bench_dir = self.root / "bench"
        run.setup_s = t_setup_end[0] - t_start
        run.token_budget = engine.token_budget
        import jax
        dev = jax.devices()[0]
        sample, kv = [], []
        if check_:
            kv = check.kv_readback(engine)
            run.drain_s = H.drain(engine, run, check.SAMPLE_TOKENS,
                                  check.DRAIN_CAP_S)
            sample = check.sample(run, seed)
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        # the program's state goes before the reference runs
        del engine, params
        gc.collect()
        jax.clear_caches()
        if trace:
            import trace_reduce
            run.trace = trace_reduce.reduce_dir(trace_dir)
        return run, sample, kv, mem


def main(argv=None, *, require_tpu: bool = True, root: pathlib.Path = ROOT
         ) -> int:
    args = parse(argv)
    setup_env(root)
    import harness as H
    c = Cell(args.workload, root)
    wanted = H.cell_metrics(c.bench, c.cell["name"], bool(args.trace))
    xla_flags(c.cfgfile)
    c.configure_jax()
    import jax
    devs = device_info(int(c.cell["chips"]))
    if require_tpu and devs is None:
        H.log_err(f"run_cell: needs {c.cell['chips']} TPU chip(s); JAX "
                  f"found {jax.devices()}; nothing was run")
        return 1
    dev = jax.devices()[0]
    import check
    import peaks
    run, sample, kv, mem = c.serve(args.seed, args.seconds,
                                   bool(args.trace), T_START)
    run.peaks = peaks.for_device(dev.device_kind) if devs else None
    metrics = {}
    for m in wanted:
        v = H.metric_reader(m["name"], root / "bench")(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks = check.compare(c.cfgfile, run.dims, args.seed, sample, kv,
                           run)["served"]
    correct = all(x["ok"] for x in checks.values())
    attempted = sum(s.in_window for s in run.sent)
    failed = sum(s.in_window and s.req.truncated for s in run.sent)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = {k: {"value": x["value"], "limit": x["limit"]}
                        for k, x in checks.items()}
    for k, x in checks.items():
        H.log_err(f"check {k}: {x['value']!r} (limit {x['limit']!r}) "
                  f"{'ok' if x['ok'] else 'FAIL'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
