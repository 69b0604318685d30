"""The benchmark's harness: finds a cell's parts by name, builds the
program, drives its serving engine through a warm-up and one measured
window, and keeps the record that the metric readers read.

Everything that belongs to one configuration, traffic mix, metric or
kernel sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

  bench/configs/<config>.json    sizes, numerics, serving slots, limits
  bench/traffic/<mix>.json       parameters of the one generator
  bench/metrics/<metric>.py      ``read(run) -> float | None``
  bench/kernels/<kernel>.py      ``work(dims, cfgfile, positions,
                                 contexts) -> (ops, bytes)`` per step

The program is reached only through ``init_serving``, ``ServeEngine``
(``submit``, ``step``, ``stats``) and its per-step host state, which
the harness reads after each step returns.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(bench: Dict, config: str, root: pathlib.Path = ROOT) -> Dict:
    for c in bench["configs"]:
        if c["name"] == config:
            return load_json(root / c["file"])
    raise KeyError(f"no configuration {config!r} in BENCHMARK.json")


def traffic_file(mix: str, bench_dir: pathlib.Path = BENCH) -> Dict:
    return load_json(bench_dir / "traffic" / f"{mix}.json")


def _module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH
                  ) -> Callable[["Run"], Optional[float]]:
    return _module(bench_dir / "metrics" / f"{name}.py").read


def kernel_work(name: str, bench_dir: pathlib.Path = BENCH):
    return _module(bench_dir / "kernels" / f"{name}.py").work


def cell_metrics(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def program_config(cfgfile: Dict):
    """The program's ArchConfig for a configuration file, under the
    paper's serving policy (asymmetric ternary weights, ternary
    activations), with its widths checked against the file."""
    from repro.configs import get_config
    prog = cfgfile["program"]
    cfg = get_config(prog["arch"], smoke=bool(prog.get("smoke"))).replace(
        n_layers=int(prog["n_layers"]))
    cfg = cfg.replace(ternary=cfg.ternary.replace(
        enabled=True, encoding="asymmetric", act_mode="ternary",
        act_threshold=float(cfgfile["numerics"]["act_threshold"]),
        pack=bool(prog["pack"])))
    from reference import dims_of
    dims = dims_of(cfgfile)
    have = dict(n_layers=cfg.n_layers, d=cfg.d_model, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, hd=cfg.hd, d_ff=cfg.d_ff,
                vocab_padded=cfg.vocab_padded, rope_theta=cfg.rope_theta,
                rope=cfg.rope_variant, compute=cfg.compute_dtype,
                kv=cfg.kv_cache_dtype)
    want = dict(n_layers=dims.n_layers, d=dims.d, n_heads=dims.n_heads,
                n_kv=dims.n_kv, hd=dims.hd, d_ff=dims.d_ff,
                vocab_padded=dims.vocab_padded, rope_theta=dims.rope_theta,
                rope=dims.rope,
                compute=cfgfile["numerics"]["compute_dtype"],
                kv=cfgfile["numerics"]["kv_dtype"])
    if have != want:
        raise ValueError(f"program config {have} differs from the "
                         f"configuration file {want}")
    return cfg


# ---------------------------------------------------------------------------
# the window's record
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Sent:
    """A request as the load generator sent it, with every output
    token's time (seconds from the window's start, host clock, stamped
    when ``step()`` returned)."""
    req: Any
    due: float
    sent: float
    in_window: bool
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: str
    seconds: float
    setup_s: float = 0.0
    sent: List[Sent] = dataclasses.field(default_factory=list)
    counters0: Dict[str, int] = dataclasses.field(default_factory=dict)
    counters1: Dict[str, int] = dataclasses.field(default_factory=dict)
    # per step with work, traced runs only: (positions (tokens,),
    # per-slot context after the step (slots,))
    steps: List[Any] = dataclasses.field(default_factory=list)
    drain_s: float = 0.0
    token_budget: int = 0
    step_compiles: int = 0
    backend_compiles: int = 0
    trace: Optional[Dict] = None
    dims: Any = None
    cfgfile: Optional[Dict] = None
    peaks: Optional[Dict] = None
    bench_dir: pathlib.Path = BENCH

    def window_tokens(self) -> List[float]:
        return [t for s in self.sent for t in s.times
                if 0.0 <= t <= self.seconds]

    def counter(self, key: str) -> int:
        return self.counters1[key] - self.counters0[key]


class Annotate:
    """Host spans: profiler TraceAnnotations when tracing, else nothing."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()


_COMPILES = []


def backend_compiles() -> int:
    """Backend compiles in this process so far (persistent-cache hits
    are not compiles); the listener is registered on first use."""
    if not _COMPILES:
        import jax
        _COMPILES.append(0)

        def listen(event, duration, **_):
            if event.endswith("backend_compile_duration"):
                _COMPILES[0] += 1
        jax.monitoring.register_event_duration_secs_listener(listen)
    return _COMPILES[0]


def _submit(engine, planned, uid: int, due: float, now: float,
            in_window: bool) -> Sent:
    from repro.serve.engine import Request
    req = Request(uid=uid, prompt=planned.prompt,
                  max_new_tokens=planned.max_new)
    engine.submit(req)
    return Sent(req, due, now, in_window)


def serve(engine, traffic, seconds: float, plan, ann: Annotate,
          in_window: bool, origin: float, uid0: int = 0,
          carry: Optional[List[Sent]] = None, record_steps: bool = False,
          run: Optional[Run] = None) -> List[Sent]:
    """Serve ``plan`` (open loop: due offsets in [0, seconds)) or keep
    the closed loop full, for ``seconds`` of host time from ``origin``
    (a ``time.perf_counter()`` reading); times are seconds from it.
    ``carry`` are requests still in flight from before; they keep being
    stamped.  Returns every request touched (carried and new)."""
    live = list(carry or [])
    touched = list(live)
    seen = {id(s): len(s.req.out_tokens) for s in live}
    pending = sorted(plan, key=lambda p: p.due)
    nxt = 0
    uid = uid0
    closed = traffic.loop == "closed"
    clock = time.perf_counter
    t0 = origin
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        with ann("bench.submit"):
            if closed:
                while sum(not s.req.done for s in live) < \
                        traffic.concurrency:
                    s = _submit(engine, traffic.next_closed(), uid, now,
                                now, in_window)
                    uid += 1
                    live.append(s)
                    touched.append(s)
                    seen[id(s)] = 0
            else:
                while nxt < len(pending) and pending[nxt].due <= now:
                    p = pending[nxt]
                    s = _submit(engine, p, uid, p.due, now, in_window)
                    uid += 1
                    nxt += 1
                    live.append(s)
                    touched.append(s)
                    seen[id(s)] = 0
        if not live:
            # nothing in flight: wait for the next arrival
            wait = (pending[nxt].due if nxt < len(pending) else seconds) \
                - (clock() - t0)
            if wait > 0:
                time.sleep(min(wait, 0.05))
            continue
        before = engine.scheduled_tokens
        with ann("bench.step"):
            engine.step()
        t = clock() - t0
        with ann("bench.stamp"):
            for s in live:
                n = len(s.req.out_tokens)
                k = seen[id(s)]
                if n > k:
                    s.times.extend([t] * (n - k))
                    seen[id(s)] = n
            live = [s for s in live if not s.req.done]
            if record_steps and engine.scheduled_tokens > before:
                run.steps.append(_step_record(engine))
    return touched


def _step_record(engine):
    """Positions of the tokens the last step scheduled and each slot's
    context after it, from the engine's host state."""
    sm = engine._last_slot_map
    n_new = (sm >= 0).sum(axis=1)
    ctx = engine.cache_len.astype(np.int64)
    pos = np.concatenate([np.arange(c - k, c) for c, k in zip(ctx, n_new)
                          if k])
    return pos, np.where(n_new > 0, ctx, 0)


def drive(engine, traffic, seconds: float, *, trace_dir=None,
          record_steps: bool = False, on_window_start=None) -> Run:
    """Set-up serving (primer, warm-up) then the measured window.  The
    caller has built the engine and compiled its step; ``run.setup_s``
    is filled by the caller."""
    import jax
    run = Run(cell="", seconds=float(seconds))
    ann = Annotate(trace_dir is not None)
    # primer: whatever the mix keeps warm (shared documents)
    primer = traffic.primer()
    if primer:
        for i, p in enumerate(primer):
            _submit(engine, p, 1_000_000 + i, 0.0, 0.0, False)
        engine.run_until_done()
    warm = traffic.warm_plan()
    carry = []
    t_warm = time.perf_counter()
    if traffic.warm_s > 0:
        if traffic.loop == "open":
            for p in warm:
                p.due += traffic.warm_s
            carry = serve(engine, traffic, traffic.warm_s, warm, ann, False,
                          t_warm, uid0=0)
        else:
            carry = [_submit(engine, p, i, 0.0, 0.0, False)
                     for i, p in enumerate(warm)]
            carry = serve(engine, traffic, traffic.warm_s, [], ann, False,
                          t_warm, uid0=len(warm), carry=carry)
    carry = [s for s in carry if not s.req.done]
    uid0 = 10_000
    plan = traffic.window_plan()
    compiles0, step0 = backend_compiles(), engine.n_step_compiles
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1     # the benchmark's own spans only
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    run.counters0 = engine.stats()
    if on_window_start is not None:
        on_window_start()
    t_win = time.perf_counter()
    # carried requests keep their tokens' clock: move it to the window's
    # (the warm-up's last step may end after warm_s)
    for s in carry:
        s.times = [t - (t_win - t_warm) for t in s.times]
        s.due -= t_win - t_warm
        s.sent -= t_win - t_warm
    with ann("bench.window"):
        touched = serve(engine, traffic, seconds, plan, ann, True, t_win,
                        uid0=uid0, carry=carry, record_steps=record_steps,
                        run=run)
    run.counters1 = engine.stats()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    run.backend_compiles = backend_compiles() - compiles0
    run.step_compiles = engine.n_step_compiles - step0
    run.sent = touched
    return run


def drain(engine, run: Run, tokens: int, cap_s: float) -> float:
    """After the window: keep serving what is in flight, with no new
    arrivals, until finished requests hold ``tokens`` served tokens or
    ``cap_s`` passes.  Long requests outlive a window; these are the
    same requests on the same compiled step.  Returns the seconds."""
    t0 = time.perf_counter()
    while (sum(len(s.req.out_tokens) for s in finished(run)) < tokens
           and time.perf_counter() - t0 < cap_s
           and any(not s.req.done for s in run.sent)):
        engine.step()
    return time.perf_counter() - t0


def finished(run: Run) -> List[Sent]:
    return [s for s in run.sent if s.req.done and s.req.out_tokens]


def percentile(values, q: float) -> Optional[float]:
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def log_err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# window statistics the metric readers share
# ---------------------------------------------------------------------------

def itl_gaps(run: Run) -> List[float]:
    """Seconds between consecutive output tokens of a request, both
    inside the window."""
    out = []
    for s in run.sent:
        ts = [t for t in s.times if 0.0 <= t <= run.seconds]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def ttft_values(run: Run) -> List[float]:
    """First-token time minus due time of every request due in the
    window; one with no first token by the window's end counts at
    end - due."""
    out = []
    for s in run.sent:
        if not s.in_window:
            continue
        first = s.times[0] if s.times else None
        if first is None or first > run.seconds:
            first = run.seconds
        out.append(first - s.due)
    return out


def step_work(run: Run, kernel: str) -> List:
    """Per traced step, the kernel work function's (ops, bytes)."""
    fn = kernel_work(kernel, run.bench_dir)
    return [fn(run.dims, run.cfgfile, pos, ctx) for pos, ctx in run.steps]


def kernel_seconds(run: Run, prefixes) -> float:
    return sum(s for n, s in run.trace["ops"].items()
               if n.startswith(tuple(prefixes)))
