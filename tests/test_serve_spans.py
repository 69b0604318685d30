"""The engine's own instrumentation: profiler spans around each phase
of ``ServeEngine.step``, host-clock request stamps, and the scheduler's
``budget_full_steps`` counter.

  * one traced step holds exactly one ``serve.step`` span, every
    ``serve.*`` phase lies inside it, and the phases come in program
    order: admit, schedule, upload, the step's launch, account, the
    sampler's launch, fetch, emit;
  * the speculative step brackets its draft passes in ``serve.draft``;
  * ``t_submit <= t_admit <= t_first`` for every request, and a
    request preempted and resumed keeps its first admission stamp;
  * ``budget_full_steps`` counts a prompt slice cut by the token
    budget and not one cut by the chunk.
"""
import glob
from collections import defaultdict

import jax
import numpy as np

from repro.analysis.base import load_repo, run_all
from repro.configs import get_config
from repro.models import transformer as tfm
from repro.serve import metrics
from repro.serve.engine import Request, ServeEngine, ternarize_model

MAX_LEN, BS, CHUNK = 32, 8, 8

_STATE = {}


def _engine(slots=2, **kw):
    if not _STATE:
        cfg = get_config("granite-34b", smoke=True)
        _STATE["cfg"] = cfg
        _STATE["params"] = ternarize_model(
            tfm.init(cfg, jax.random.PRNGKey(0)), cfg)
    return ServeEngine(_STATE["params"], _STATE["cfg"], batch_slots=slots,
                       max_len=MAX_LEN, chunk=CHUNK, block_size=BS, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 1000, n).astype(np.int32)


def _drain(eng, max_iters=400):
    for _ in range(max_iters):
        if not (eng.queue or eng._active_slots()):
            return {r.uid: r for r in eng.finished}
        eng.step()
        eng.validate()
    raise AssertionError("engine stopped making progress")


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``serve.*`` host spans of the
    trace, name -> sorted (start, end) ns."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return {k: sorted(v) for k, v in spans.items()}


def test_step_phases_nest_in_order(tmp_path):
    eng = _engine()
    eng.submit(Request(uid=0, prompt=_prompt(5), max_new_tokens=4))
    eng.step()                     # compile outside the trace
    spans = _traced(tmp_path, eng.step)
    (s0, s1), = spans.pop("serve.step")
    order = ["serve.admit", "serve.schedule", "serve.upload",
             "serve.launch", "serve.account", "serve.launch",
             "serve.fetch", "serve.emit"]
    assert sorted(spans) == sorted(set(order))
    assert len(spans["serve.launch"]) == 2
    for name, ivs in spans.items():
        assert len(ivs) == order.count(name), name
        assert all(s0 <= a <= b <= s1 for a, b in ivs), name
    # flatten in start order; each phase ends before the next begins
    seq = sorted((a, b, n) for n, ivs in spans.items() for a, b in ivs)
    assert [n for _, _, n in seq] == order
    assert all(b0 <= a1 for (_, b0, _), (a1, _, _) in zip(seq, seq[1:]))


def test_every_step_with_work_has_one_span(tmp_path):
    eng = _engine()
    eng.submit(Request(uid=0, prompt=_prompt(20), max_new_tokens=3))
    eng.step()

    def steps():
        while eng.queue or eng._active_slots():
            eng.step()
    before = eng.iters
    spans = _traced(tmp_path, steps)
    assert len(spans["serve.step"]) == eng.iters - before
    assert len(spans["serve.fetch"]) == eng.iters - before


def test_spec_step_brackets_the_draft(tmp_path):
    eng = _engine(spec_k=2)
    eng.submit(Request(uid=0, prompt=_prompt(6), max_new_tokens=8))
    eng.step()                     # prefill; decodes follow

    def two():
        eng.step()
        eng.step()
    spans = _traced(tmp_path, two)
    assert eng.draft_tokens > 0
    assert len(spans["serve.step"]) == 2
    assert len(spans["serve.draft"]) == 2
    steps = spans["serve.step"]
    for a, b in spans["serve.draft"] + spans["serve.fetch"]:
        assert any(s0 <= a <= b <= s1 for s0, s1 in steps)


def test_request_stamps_survive_preemption():
    eng = _engine(num_blocks=5, preempt="recompute")
    lens, news = (14, 30, 27), (8, 4, 4)
    reqs = [Request(uid=u, prompt=_prompt(n, u), max_new_tokens=m)
            for u, (n, m) in enumerate(zip(lens, news))]
    for r in reqs:
        eng.submit(r)
    first_admit = {}
    preempt = eng._preempt

    def spy(victim):
        req = eng.slot_req[victim]
        first_admit.setdefault(req.uid, req.t_admit)
        return preempt(victim)
    eng._preempt = spy
    done = _drain(eng)
    assert first_admit, "a pool of 5 blocks must preempt"
    for r in reqs:
        assert done[r.uid] is r
        assert r.t_submit <= r.t_admit <= r.t_first
    for uid, t in first_admit.items():
        assert done[uid].t_admit == t


def test_budget_full_steps():
    # a 20-token prompt under a budget of 4: the slice is cut by the
    # budget (4 < chunk 8) on every prefill step
    eng = _engine(token_budget=4)
    eng.submit(Request(uid=0, prompt=_prompt(20), max_new_tokens=2))
    eng.step()
    assert eng.stats()["budget_full_steps"] == 1
    _drain(eng)
    # five slices of 4; the last takes all that is left, so 4 were cut
    assert eng.stats()["budget_full_steps"] == 4
    # the default budget (slots + chunk) only the chunk cuts it
    eng = _engine()
    eng.submit(Request(uid=0, prompt=_prompt(20), max_new_tokens=2))
    _drain(eng)
    assert eng.stats()["budget_full_steps"] == 0
    # decodes spend the budget first: a prompt admitted beside two
    # decoding slots with a budget of 2 waits whole steps
    eng = _engine(slots=3, token_budget=2)
    for u in range(2):
        eng.submit(Request(uid=u, prompt=_prompt(2, u), max_new_tokens=6))
    eng.step()
    eng.submit(Request(uid=2, prompt=_prompt(9, 2), max_new_tokens=1))
    before = eng.stats()["budget_full_steps"]
    eng.step()
    assert eng.stats()["budget_full_steps"] == before + 1
    assert int(eng.slot_fill[2]) == 0


def test_new_counter_is_registered():
    key = "budget_full_steps"
    assert key in metrics.COUNTERS and key not in metrics.GAUGES
    eng = _engine()
    snaps = [eng.stats(), eng.stats()]
    assert metrics.counter_deltas(snaps)[1][key] == 0
    assert run_all(load_repo()) == []
