"""The plain engine step runs its token-wise work on the scheduled
tokens only: ``step_rows`` rows instead of the padded (slots, chunk)
grid, with attention still on the grid.

Against an engine whose step runs the padded grid (the same jitted step
without ``rows``), under one schedule of mixed decodes, 16-token prefill
chunks and a shared-prefix hit:

  * greedy tokens and layer 0's cached K/V are bit-identical, on the
    TiM kernel route (Pallas, interpreted here), the XLA route and
    weight-only serving; on the TiM routes (int8 passes, one f32
    epilogue a row) every layer's K/V is, while weight-only serving's
    float matmuls may round a deeper layer's value by an ulp when the
    row count changes;
  * the step compiles once while the scheduled count runs from 1 to the
    token budget;
  * ``grid_tokens`` grows by ``step_rows`` a step.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.serve.engine import (Request, ServeEngine,
                                make_paged_unified_step, step_rows,
                                ternarize_model)

SLOTS, CHUNK, MAX_LEN, BS = 4, 16, 128, 16


def _cfg(impl: str, act_mode: str):
    cfg = get_config("chatglm3-6b", smoke=True)
    return cfg.replace(ternary=cfg.ternary.replace(
        enabled=True, encoding="asymmetric", act_mode=act_mode, impl=impl))


def _padded(eng: ServeEngine) -> ServeEngine:
    """Swap the engine's step for the padded-grid step (``rows``
    dropped): the oracle of the row layout."""
    step = jax.jit(make_paged_unified_step(eng.cfg), donate_argnums=(2,))
    eng._step = lambda params, batch, caches, *sched: step(
        params, batch, caches, *sched[:-1])
    return eng


def _traffic(vocab: int):
    """step -> requests: one long request alone, then a shared-prefix
    hit beside two fresh prompts, which fill the budget."""
    rng = np.random.default_rng(7)
    system = rng.integers(1, vocab, 2 * BS).astype(np.int32)
    fresh = lambda n: rng.integers(1, vocab, n).astype(np.int32)  # noqa
    return {
        0: [Request(uid=0, prompt=np.concatenate([system, fresh(8)]),
                    max_new_tokens=24)],
        4: [Request(uid=1, prompt=np.concatenate([system, fresh(20)]),
                    max_new_tokens=4),
            Request(uid=2, prompt=fresh(40), max_new_tokens=3),
            Request(uid=3, prompt=fresh(19), max_new_tokens=5)],
    }


def _serve(eng: ServeEngine):
    """Run the traffic to its end; per step the scheduled and the
    launched-row counts."""
    plan = _traffic(eng.cfg.vocab_size)
    sched, grid = [], []
    while plan or eng.queue or eng._active_slots():
        for req in plan.pop(eng.iters, []):
            eng.submit(req)
        s0, g0 = eng.scheduled_tokens, eng.grid_tokens
        eng.step()
        eng.validate()
        if eng.scheduled_tokens > s0:
            sched.append(eng.scheduled_tokens - s0)
            grid.append(eng.grid_tokens - g0)
    return {r.uid: r.out_tokens for r in eng.finished}, sched, grid


@pytest.mark.parametrize("impl,act_mode,layers", [
    ("pallas", "ternary", None), ("xla", "ternary", None),
    ("xla", "none", 1)], ids=["tim-kernel", "tim-xla", "weight-only"])
def test_row_step_matches_padded_grid(impl, act_mode, layers):
    cfg = _cfg(impl, act_mode)
    params = ternarize_model(tfm.init(cfg, jax.random.PRNGKey(0)), cfg)
    kw = dict(batch_slots=SLOTS, max_len=MAX_LEN, chunk=CHUNK,
              block_size=BS)
    eng = ServeEngine(params, cfg, **kw)
    ref = _padded(ServeEngine(params, cfg, **kw))
    out, sched, grid = _serve(eng)
    ref_out, ref_sched, _ = _serve(ref)

    assert eng.step_rows == 32 < SLOTS * CHUNK
    assert out == ref_out and len(out) == 4
    assert sched == ref_sched
    assert min(sched) == 1 and max(sched) == eng.token_budget
    assert eng.prefix_hit_tokens == 2 * BS
    assert eng.n_step_compiles == 1
    assert grid == [eng.step_rows] * len(sched)
    for name in ("k", "v"):
        got = np.asarray(eng.caches["b0"][name][:layers])
        want = np.asarray(ref.caches["b0"][name][:layers])
        assert got.any() and np.array_equal(got, want), name


@pytest.mark.parametrize("slots,chunk,budget,rows", [
    (24, 16, 40, 64),      # the chatglm3-6b cells: 384 grid rows
    (24, 16, 8, 32),       # decodes are never stalled: slots bound it
    (2, 4, 6, 8),          # never past the grid
    (8, 16, 24, 32),
])
def test_step_rows(slots, chunk, budget, rows):
    assert step_rows(slots, chunk, budget) == rows
