"""The one traffic generator: every seed gets the same work on the same
schedule, one seed repeats exactly, each mix kind makes what it says."""
import json

import numpy as np
import pytest

import harness as H
import traffic_gen

MIXES = ["chat", "batch", "docqa-shared"]


def _sizes(reqs):
    return sorted(len(r.prompt) for r in reqs), sorted(r.max_new for r in reqs)


def _plan(mix, seed, seconds=30.0):
    t = traffic_gen.Traffic(H.traffic_file(mix), 65024, seed, seconds)
    if t.loop == "closed":
        return t, t.warm_plan() + [t.next_closed() for _ in range(48)]
    return t, t.warm_plan() + t.window_plan()


@pytest.mark.parametrize("mix", MIXES)
def test_same_work_every_seed(mix):
    _, a = _plan(mix, 1)
    _, b = _plan(mix, 2 ** 31 + 12345)
    assert [(r.due, len(r.prompt), r.max_new, r.doc) for r in a] == \
        [(r.due, len(r.prompt), r.max_new, r.doc) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_one_seed_repeats(mix):
    _, a = _plan(mix, 77)
    _, b = _plan(mix, 77)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_open_loop_arrivals_in_their_spans():
    t, _ = _plan("chat", 5)
    spec = H.traffic_file("chat")
    win = t.window_plan()
    assert len(win) == round(spec["rate_per_s"] * 30)
    assert all(0 <= r.due < 30 for r in win)
    warm = traffic_gen.Traffic(spec, 65024, 5, 30).warm_plan()
    assert all(-spec["warm_s"] <= r.due < 0 for r in warm)


def test_sizes_follow_the_mix():
    spec = H.traffic_file("chat")
    q = traffic_gen._lognormal_quantiles(spec["prompt"], 101)
    assert q.min() >= spec["prompt"]["min"]
    assert q.max() <= spec["prompt"]["max"]
    assert q[50] == spec["prompt"]["median"]


def test_shared_documents_lead_every_prompt():
    t, reqs = _plan("docqa-shared", 9)
    spec = H.traffic_file("docqa-shared")
    assert len(t.docs) == spec["prefix"]["docs"]
    for r in reqs:
        doc = t.docs[r.doc]
        assert np.array_equal(r.prompt[:len(doc)], doc)
        q = len(r.prompt) - len(doc)
        assert spec["prompt"]["min"] <= q <= spec["prompt"]["max"]
    assert len(t.primer()) == spec["prefix"]["docs"]


def test_closed_loop_fills_its_clients():
    t, _ = _plan("batch", 3)
    assert len(traffic_gen.Traffic(H.traffic_file("batch"), 64000, 3,
                                   30).warm_plan()) == t.concurrency
    assert json.loads((H.BENCH / "traffic" / "batch.json").read_text())[
        "loop"] == "closed"
