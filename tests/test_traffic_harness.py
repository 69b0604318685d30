"""Traffic-harness + telemetry regression tests (ISSUE-6).

Covers, in order:

  * trace generation determinism (same seed => identical arrivals,
    different seed => different), arrival-process sanity for all three
    processes, and the shared-prefix pool structure;
  * the acceptance criterion: one seeded bursty trace replayed through
    TWO independent engines produces byte-identical TTFT/TPOT digests
    and summaries;
  * the run_until_done bugfixes: an undersized pool with
    ``preempt='none'`` must RAISE the no-progress (livelock) error
    naming the stuck requests instead of spinning, ``max_iters``
    expiry must raise "iteration-capped" instead of silently returning
    a partial ``finished`` list, and a drained engine returns all
    requests;
  * the truncation bugfix: a request stopped by cache capacity (not
    its own ``max_new_tokens``) carries ``truncated=True`` and is
    counted in ``stats()``;
  * serve/metrics unit behavior: counter-vs-gauge handling in
    ``counter_deltas``, the median-window drift detector (sustained
    drift flags, a single spike does not), percentile digests;
  * the chip-constants hoist: engine and roofline read the SAME
    ``repro.sim.chip`` table, keyed by device kind.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.serve import metrics
from repro.serve.engine import Request, ServeEngine, ternarize_model
from repro.sim.traffic import (PROCESSES, TrafficConfig, generate_trace,
                               run_trace)

MAX_LEN = 32
BLOCK_SIZE = 8
CHUNK = 8
SLOTS = 2

_STATE = {}


def _setup():
    if not _STATE:
        cfg = get_config("granite-34b", smoke=True)
        params = ternarize_model(tfm.init(cfg, jax.random.PRNGKey(0)),
                                 cfg)
        _STATE.update(cfg=cfg, params=params, step=None, copy=None)
    return _STATE


def _engine(**kw):
    state = _setup()
    eng = ServeEngine(state["params"], state["cfg"], batch_slots=SLOTS,
                      max_len=MAX_LEN, chunk=CHUNK,
                      block_size=BLOCK_SIZE, **kw)
    # one compiled step across all engines in this module (fixed
    # (slots, chunk) shape; per-pool-shape entries live in jit's cache)
    if state["step"] is None:
        state["step"], state["copy"] = eng._step, eng._copy_step
    else:
        eng._step, eng._copy_step = state["step"], state["copy"]
    return eng


# ---------------------------------------------------------------- trace


def test_trace_deterministic_per_seed():
    cfg = TrafficConfig(seed=3, n_requests=16, process="bursty")
    a, b = generate_trace(cfg), generate_trace(cfg)
    assert [x.time for x in a] == [x.time for x in b]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
    assert [x.pool for x in a] == [x.pool for x in b]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
    c = generate_trace(TrafficConfig(seed=4, n_requests=16,
                                     process="bursty"))
    assert [x.time for x in a] != [x.time for x in c]


@pytest.mark.parametrize("process", PROCESSES)
def test_arrival_process_sanity(process):
    cfg = TrafficConfig(seed=0, n_requests=40, process=process)
    trace = generate_trace(cfg)
    times = [a.time for a in trace]
    assert len(trace) == 40
    assert all(t > 0 for t in times)
    assert times == sorted(times)                 # submit order = uid order
    assert [a.uid for a in trace] == list(range(40))
    lo, hi = cfg.prompt_len
    assert all(lo <= len(a.prompt) <= hi for a in trace)
    assert all(cfg.max_new[0] <= a.max_new_tokens <= cfg.max_new[1]
               for a in trace)


def test_shared_prefix_pools():
    cfg = TrafficConfig(seed=1, n_requests=64, shared_frac=0.7,
                        n_prefix_pools=2, prefix_len=(16, 16),
                        prompt_len=(4, 24))
    trace = generate_trace(cfg)
    pooled = [a for a in trace if a.pool >= 0]
    assert pooled and any(a.pool == -1 for a in trace)
    # every pair in the same pool shares its leading tokens (up to the
    # shorter prompt, minus the fresh tail token)
    for p in (0, 1):
        members = [a for a in trace if a.pool == p]
        for a in members[1:]:
            k = min(len(a.prompt), len(members[0].prompt), 16) - 1
            if k > 0:
                assert np.array_equal(a.prompt[:k],
                                      members[0].prompt[:k])
    # and the last prompt token is always fresh (pools never alias a
    # whole prompt)
    assert all(len(a.prompt) >= 1 for a in pooled)


# ------------------------------------------- acceptance: digest replay


def test_bursty_digest_identical_across_runs():
    # the acceptance profile: small pool (preemption live) + prefix-
    # sharing mix — the most schedule-sensitive configuration must
    # still replay to identical digests
    tcfg = TrafficConfig(seed=5, n_requests=8, process="bursty",
                         rate=0.6, prompt_len=(4, 24), max_new=(1, 4),
                         shared_frac=0.5, prefix_len=(16, 16),
                         vocab_size=_setup()["cfg"].vocab_size)
    trace = generate_trace(tcfg)
    res1 = run_trace(_engine(num_blocks=6, preempt="auto"), trace)
    res2 = run_trace(_engine(num_blocks=6, preempt="auto"), trace)
    assert res1.digest() == res2.digest()
    assert res1.summary() == res2.summary()
    assert res1.steps == res2.steps
    d = res1.digest()
    assert d["requests_finished"] == 8
    assert d["ttft_steps_p50"] >= 1.0


# ----------------------------------------- run_until_done bugfix suite


def test_livelock_raises_instead_of_spinning():
    # 5 blocks = the construction floor; preempt disabled; the token
    # budget is wide enough that BOTH slots prefill full chunks, so two
    # 24-token prompts wedge each other (3 blocks held + 2 held,
    # neither can grow) and no step makes progress — the old loop spun
    # to max_iters and returned [] as if drained.  (At the default
    # budget the scheduler splits the chunk 8+2, which keeps the second
    # slot's footprint small enough to squeak through — disabling
    # preemption only livelocks when the schedule lets both slots bloat.)
    eng = _engine(num_blocks=5, preempt="none", token_budget=16)
    rng = np.random.default_rng(0)
    for uid in range(2):
        eng.submit(Request(
            uid=uid,
            prompt=rng.integers(1, 100, 24).astype(np.int32),
            max_new_tokens=4))
    with pytest.raises(RuntimeError, match="no progress"):
        eng.run_until_done(stall_iters=6)
    # the error names the wedged requests and the pool state
    try:
        eng.run_until_done(stall_iters=2)
    except RuntimeError as e:
        msg = str(e)
        assert "uid" in msg and "blocks" in msg and "preempt" in msg
    else:  # pragma: no cover
        raise AssertionError("expected livelock RuntimeError")


def test_iteration_cap_raises_with_work_remaining():
    eng = _engine()
    eng.submit(Request(uid=0,
                       prompt=np.arange(1, 20, dtype=np.int32),
                       max_new_tokens=6))
    with pytest.raises(RuntimeError, match="iteration-capped"):
        eng.run_until_done(max_iters=2)
    # the engine is still coherent: finishing the drain works
    out = eng.run_until_done()
    assert len(out) == 1 and out[0].done


def test_drained_returns_all_finished():
    eng = _engine()
    rng = np.random.default_rng(7)
    for uid in range(3):
        eng.submit(Request(
            uid=uid,
            prompt=rng.integers(1, 100, 5 + uid).astype(np.int32),
            max_new_tokens=2))
    out = eng.run_until_done()
    assert sorted(r.uid for r in out) == [0, 1, 2]
    assert all(r.done and not r.truncated for r in out)
    assert eng.stats()["truncated_requests"] == 0


def test_traffic_harness_surfaces_livelock():
    # the harness replay uses the same detector as run_until_done
    eng = _engine(num_blocks=5, preempt="none", token_budget=16)
    tcfg = TrafficConfig(seed=2, n_requests=3, process="poisson",
                        rate=2.0, prompt_len=(24, 24), max_new=(4, 4))
    with pytest.raises(RuntimeError, match="no progress"):
        run_trace(eng, generate_trace(tcfg), stall_iters=6)


# ------------------------------------------------- truncation bugfix


def test_cache_full_truncation_flagged():
    eng = _engine()
    req = Request(uid=0, prompt=np.arange(1, 31, dtype=np.int32),
                  max_new_tokens=8)          # 30 + 8 > max_len=32
    eng.submit(req)
    out = eng.run_until_done()
    assert out[0].done and out[0].truncated
    # max_len - plen + 1: the first token rides on the prefill logits
    # without occupying a cache slot, then decode fills 31 and 32
    assert len(out[0].out_tokens) == MAX_LEN - 30 + 1
    assert eng.stats()["truncated_requests"] == 1
    # a request that finishes by its own budget is NOT truncated
    eng2 = _engine()
    req2 = Request(uid=0, prompt=np.arange(1, 11, dtype=np.int32),
                   max_new_tokens=3)
    eng2.submit(req2)
    eng2.run_until_done()
    assert req2.done and not req2.truncated


# ----------------------------------------------------- metrics units


def test_counter_deltas_counters_vs_gauges():
    snaps = [
        {"scheduled_tokens": 10, "blocks_in_use": 4, "step": 1},
        {"scheduled_tokens": 25, "blocks_in_use": 2, "step": 2},
        {"scheduled_tokens": 25, "blocks_in_use": 7, "step": 3},
    ]
    d = metrics.counter_deltas(snaps)
    assert [r["scheduled_tokens"] for r in d] == [10, 15, 0]
    assert [r["blocks_in_use"] for r in d] == [4, 2, 7]   # gauge: raw
    assert [r["step"] for r in d] == [1, 2, 3]            # gauge: raw


def test_counter_deltas_strict_registry():
    # ISSUE-7 satellite: the old code passed any non-int value through
    # as a gauge, so a typo'd or unclassified key silently corrupted
    # the rate streams.  Routing is now strict against the
    # COUNTERS/GAUGES partition.
    with pytest.raises(KeyError, match="neither COUNTERS nor GAUGES"):
        metrics.counter_deltas([{"scheduled_tokenz": 10}])
    # a declared counter carrying a non-integer value is a type error,
    # not a silent pass-through
    with pytest.raises(TypeError, match="non-integer"):
        metrics.counter_deltas([{"scheduled_tokens": 10.5}])
    # the registry is a partition: no key is classified twice, and the
    # engine's stats() keys are all classified
    assert not (metrics.COUNTERS & metrics.GAUGES)
    eng = _engine()
    snap = eng.stats()
    declared = metrics.COUNTERS | metrics.GAUGES
    assert set(snap) <= declared
    d = metrics.counter_deltas([snap, snap])
    assert all(d[1][k] == 0 for k in snap if k in metrics.COUNTERS)
    assert all(d[1][k] == snap[k] for k in snap if k in metrics.GAUGES)


def test_bursty_replay_under_transfer_guard():
    # ISSUE-7 satellite, the runtime complement to the host-sync lint:
    # a bursty shared-prefix replay runs with implicit device->host
    # transfers DISALLOWED.  jax.transfer_guard_device_to_host blocks
    # implicit d2h (e.g. np.asarray over a jax.Array) but exempts
    # explicit jax.device_get — which is exactly the engine's ONE
    # accounted fetch per step — so the guard passing proves every
    # hot-path transfer goes through the accounted fetch.  The default
    # pool keeps preemption idle: the swap-out path's np.asarray fetch
    # is accounted separately (swap_d2h_fetches) but is implicit, so a
    # swap under the guard would (correctly) trip it.
    eng = _engine()
    tcfg = TrafficConfig(seed=9, n_requests=6, process="bursty",
                         rate=0.6, prompt_len=(4, 20), max_new=(1, 4),
                         shared_frac=0.5, prefix_len=(16, 16),
                         vocab_size=_setup()["cfg"].vocab_size)
    trace = generate_trace(tcfg)
    with jax.transfer_guard_device_to_host("disallow"):
        res = run_trace(eng, trace)
    assert res.digest()["requests_finished"] == 6
    # every step's sample readback went through the accounted fetch
    # (idle steps — nothing scheduled yet — skip the fetch entirely)
    snap = eng.stats()
    assert 0 < snap["d2h_fetches"] <= eng.iters
    assert snap["swap_d2h_fetches"] == 0


def test_drift_detector_flags_sustained_not_spike():
    flat = [10.0] * 40
    # a single 5x spike: the trailing MEDIAN never moves
    spike = list(flat)
    spike[25] = 50.0
    assert not metrics.detect_drift(spike, window=8, patience=3).flagged
    # a sustained 2x shift: flags, and the report localizes it
    drift = [10.0] * 20 + [20.0] * 20
    rep = metrics.detect_drift(drift, window=8, patience=3)
    assert rep.flagged and rep.first_flag_index >= 20
    assert rep.baseline_median == 10.0
    assert rep.worst_ratio == pytest.approx(2.0)
    # and a stream shorter than the baseline window never flags
    assert not metrics.detect_drift([1.0] * 4, window=8).flagged


def test_percentile_digest_and_lifecycle_math():
    d = metrics.percentile_digest([1, 2, 3, 4], "x_")
    assert d["x_p50"] == 2.5 and d["x_mean"] == 2.5
    assert metrics.percentile_digest([], "y_")["y_p99"] == -1.0
    req = Request(uid=0, prompt=np.ones(4, np.int32), max_new_tokens=3)
    req.submit_step = 2
    req.token_steps = [5, 6, 9]
    assert metrics.ttft_steps(req) == 4
    assert metrics.tpot_steps(req) == pytest.approx(2.0)
    assert req.first_token_step == 5


def test_degenerate_requests_yield_none_not_nan():
    """ISSUE-9 satellite: 0-token and 1-token lifecycles (a request
    truncated mid first chunk, or still waiting in the queue) must
    surface as ``None`` from ttft/tpot — NOT as NaN/inf samples — so
    ``request_digest`` filters them and emits -1.0 sentinels."""
    zero = Request(uid=0, prompt=np.ones(4, np.int32),
                   max_new_tokens=3)           # never scheduled
    assert metrics.ttft_steps(zero) is None
    assert metrics.tpot_steps(zero) is None
    one = Request(uid=1, prompt=np.ones(4, np.int32), max_new_tokens=1)
    one.submit_step = 0
    one.token_steps = [3]
    assert metrics.ttft_steps(one) == 4
    assert metrics.tpot_steps(one) is None     # < 2 tokens: no gap
    d = metrics.request_digest([zero, one])
    assert d["requests"] == 2
    assert d["ttft_steps_p99"] == 4.0          # the one real sample
    assert d["tpot_steps_p99"] == -1.0         # sentinel, never NaN
    assert all(np.isfinite(v) for v in d.values())


def test_percentile_digest_refuses_non_finite():
    """NaN/inf samples mean a degenerate request leaked past the
    ttft/tpot None-filter; the digest must refuse loudly instead of
    flowing NaN into CSV rows."""
    for bad in ([1.0, float("nan")], [float("inf")], [2.0, -np.inf]):
        with pytest.raises(ValueError, match="non-finite"):
            metrics.percentile_digest(bad, "x_")
    # empty stays the sentinel path, not an error
    assert metrics.percentile_digest([], "x_")["x_mean"] == -1.0


def test_drift_detector_refuses_non_finite():
    """A NaN sample would poison the window medians and silently
    disarm the detector (NaN comparisons are always False) — update()
    must raise instead, and the detector must stay usable after."""
    det = metrics.MedianWindowDetector(window=4, patience=2)
    for v in (1.0, 1.0, 1.0, 1.0):
        det.update(v)
    with pytest.raises(ValueError, match="non-finite"):
        det.update(float("nan"))
    with pytest.raises(ValueError, match="non-finite"):
        det.update(float("inf"))
    # still armed: sustained 3x drift flags as usual
    flagged = [det.update(3.0) for _ in range(4)]
    assert det.flagged and any(flagged)


def test_counter_deltas_covers_sampling_counters():
    """The ISSUE-9 stats() keys are registered as COUNTERS and diff
    like any monotone total (no KeyError, no gauge pass-through)."""
    for k in ("sibling_requests", "beam_forks", "masked_tokens"):
        assert k in metrics.COUNTERS and k not in metrics.GAUGES
    snaps = [{"sibling_requests": 3, "beam_forks": 0,
              "masked_tokens": 4},
             {"sibling_requests": 3, "beam_forks": 2,
              "masked_tokens": 10}]
    d = metrics.counter_deltas(snaps)
    assert d[0] == {"sibling_requests": 3, "beam_forks": 0,
                    "masked_tokens": 4}
    assert d[1] == {"sibling_requests": 0, "beam_forks": 2,
                    "masked_tokens": 6}


# ------------------------------------------------- constants hoist


def test_chip_constants_single_home():
    from benchmarks import roofline
    from repro.serve import engine
    from repro.sim import chip
    assert engine.chip_peaks is chip.chip_peaks
    assert roofline.chip_peaks is chip.chip_peaks
    # the CPU backend prices against the modelled chip, by name
    assert chip.chip_peaks() is chip.CHIPS[chip.MODELLED_KIND]
    assert chip.chip_peaks().kind == "TPU v5 lite"
    with pytest.raises(KeyError, match="TPU v9"):
        chip.chip_peaks("TPU v9")
