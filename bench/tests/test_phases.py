"""The engine-phase reduction and the readers of the engine's spans,
stamps and counters: by hand, against ``trace_reduce`` on the same
events, on the recorded chip trace, and on synthetic runs."""
import gzip
import json
import pathlib
import shutil

import pytest

import harness as H
import phases as P
import run_cell
import trace_reduce as T

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _host():
    """A window of 100 ms holding two engine steps under the
    benchmark's spans; the phases nest inside ``serve.step``."""
    return {
        "bench.window": [(0, 100 * MS)],
        "bench.submit": [(0, 2 * MS), (50 * MS, 52 * MS)],
        "bench.step": [(2 * MS, 45 * MS), (52 * MS, 97 * MS)],
        "bench.stamp": [(45 * MS, 47 * MS), (97 * MS, 99 * MS)],
    }


def _serve():
    return {
        "serve.step": [(3 * MS, 44 * MS), (53 * MS, 96 * MS)],
        "serve.admit": [(3 * MS, 4 * MS), (53 * MS, 54 * MS)],
        "serve.schedule": [(4 * MS, 6 * MS), (54 * MS, 55 * MS)],
        "serve.upload": [(6 * MS, 7 * MS), (55 * MS, 56 * MS)],
        "serve.launch": [(7 * MS, 8 * MS), (9 * MS, 10 * MS),
                         (56 * MS, 57 * MS), (58 * MS, 59 * MS)],
        "serve.account": [(8 * MS, 9 * MS), (57 * MS, 58 * MS)],
        "serve.fetch": [(10 * MS, 40 * MS), (59 * MS, 90 * MS)],
        "serve.emit": [(40 * MS, 44 * MS), (90 * MS, 96 * MS)],
    }


def _devices():
    # busy [7.5, 38] and [56.5, 88] ms: idle [0, 7.5], [38, 56.5],
    # [88, 100]
    return [[("tim_matmul_fused.3", int(7.5 * MS), 30 * MS),
             ("fusion.1", 20 * MS, 38 * MS),
             ("while.2", 0, 100 * MS),               # a container
             ("tim_matmul_fused.7", int(56.5 * MS), 88 * MS)]]


def test_idle_split_by_innermost_phase():
    host = {**_host(), **_serve()}
    r = P.reduce_events(host, _devices())
    want = {
        # [0, 2] submit, [2, 3] bench.step, [3, 4] admit, [4, 6]
        # schedule, [6, 7] upload, [7, 7.5] launch
        "bench.submit": 2 + 2, "bench.step": 1 + 1 + 1 + 1,
        "serve.admit": 1 + 1, "serve.schedule": 2 + 1,
        "serve.upload": 1 + 1, "serve.launch": 0.5 + 0.5,
        # [38, 40] fetch, [40, 44] emit, [44, 45] bench.step, [45, 47]
        # stamp, [47, 50] nothing, [50, 52] submit, [52, 53]
        # bench.step, [53, 54] admit, [54, 55] schedule, [55, 56]
        # upload, [56, 56.5] launch
        "serve.fetch": 2 + 2, "serve.emit": 4 + 6, "bench.stamp": 2 + 2,
        # [88, 90] fetch, [90, 96] emit, [96, 97] bench.step, [97, 99]
        # stamp, [99, 100] nothing
        "other": 3 + 1,
    }
    assert r["idle_by_phase"] == {k: pytest.approx(v / 1e3)
                                  for k, v in want.items()}
    old = T.reduce_events(host, _devices())
    assert sum(r["idle_by_phase"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"], abs=1e-6)
    assert r["steps"] == 2
    # the longest gap, [38, 56.5], is mostly under emit (4 + 6 ms)
    assert r["longest"][0] == ["serve.emit", pytest.approx(0.0185)]
    assert [g for _, g in r["longest"]] == sorted(
        (g for _, g in r["longest"]), reverse=True)


def test_innermost_pieces_nest():
    spans = [(0, 10, "outer"), (2, 8, "mid"), (3, 4, "in"), (6, 8, "tail"),
             (12, 14, "alone")]
    assert P.innermost(spans) == [(0, 2, "outer"), (2, 3, "mid"),
                                  (3, 4, "in"), (4, 6, "mid"),
                                  (6, 8, "tail"), (8, 10, "outer"),
                                  (12, 14, "alone")]


def test_old_keys_unchanged_by_serve_spans():
    """The benchmark's own reduction reads the same with the engine's
    spans in the trace as without them."""
    plain = T.reduce_events(_host(), _devices())
    spanned = T.reduce_events({**_host(), **_serve()}, _devices())
    for key in ("window_s", "busy_s", "ops", "idle_by_span", "breakdown"):
        assert json.dumps(spanned[key]) == json.dumps(plain[key])


def test_no_serve_spans_reads_no_steps():
    r = P.reduce_events(_host(), _devices())
    assert r["steps"] == 0
    assert not any(k.startswith("serve.") for k in r["idle_by_phase"])
    with pytest.raises(ValueError):
        P.reduce_events({}, _devices())
    with pytest.raises(ValueError):
        P.reduce_events({"bench.window": [(0, 10)]}, [[("x", 20, 30)]])


@pytest.fixture
def chip_trace(tmp_path):
    """The recorded one-second chip trace, where ``run_cell`` leaves a
    traced window's profile in a checkout at ``tmp_path``."""
    dst = tmp_path / run_cell.TRACE / "plugins/profile/run/t.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(DATA / "chat_1s.xplane.pb.gz") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return dst


def test_chip_trace(chip_trace):
    """The recorded trace predates the engine's spans: the reduction of
    the benchmark's keys is unchanged when the loader keeps ``serve.*``
    events, and the idle split sums to the idle time."""
    assert P.TRACE == run_cell.TRACE
    host, devices = P.load(str(chip_trace))
    old = T.reduce_events(*T.load(str(chip_trace)))
    again = T.reduce_events(host, devices)
    for key in ("window_s", "busy_s", "ops", "idle_by_span", "breakdown"):
        assert json.dumps(again[key]) == json.dumps(old[key])
    r = P.reduce_events(host, devices)
    assert r["steps"] == 0
    assert sum(r["idle_by_phase"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"], abs=1e-6)
    assert set(r["idle_by_phase"]) <= {"bench.step", "bench.stamp",
                                       "bench.submit", "other"}


class _Req:
    def __init__(self, t_submit=None, t_admit=None, t_first=None):
        self.t_submit, self.t_admit, self.t_first = \
            t_submit, t_admit, t_first


def _run_with(stamps, t0=1000.0):
    """A 10 s window opening at host time ``t0``; each request was sent
    at its submit stamp."""
    run = H.Run(cell="c", seconds=10.0)
    for sub, adm, first in stamps:
        req = _Req(t0 + sub, None if adm is None else t0 + adm,
                   None if first is None else t0 + first)
        run.sent.append(H.Sent(req, due=sub, sent=sub, in_window=True))
    return run


def _read(name, run):
    return H.metric_reader(name)(run)


def test_stamp_readers():
    stamps = [(float(i), i + 0.01 * i, i + 2.0) for i in range(8)]
    stamps.append((8.0, 8.5, None))      # no token by the end: 10 - 8.5
    stamps.append((9.0, None, None))     # still queued: 10 - 9 = 1.0
    run = _run_with(stamps)
    waits = [0.01 * i for i in range(8)] + [0.5, 1.0]
    prefill = [2.0 - 0.01 * i for i in range(8)] + [1.5]
    assert _read("queue_wait_p90_s", run) == pytest.approx(
        H.percentile(waits, 90))
    assert _read("prefill_p90_s", run) == pytest.approx(
        H.percentile(prefill, 90))


def test_stamp_readers_need_stamps():
    run = _run_with([(1.0, 1.5, 2.0)])
    run.sent[0].req = object()           # a program that keeps none
    assert _read("queue_wait_p90_s", run) is None
    assert _read("prefill_p90_s", run) is None
    empty = H.Run(cell="c", seconds=10.0)
    assert _read("queue_wait_p90_s", empty) is None


def test_budget_full_pct():
    run = H.Run(cell="c", seconds=10.0,
                counters0={"steps": 10, "budget_full_steps": 4},
                counters1={"steps": 60, "budget_full_steps": 24})
    assert _read("budget_full_pct", run) == pytest.approx(40.0)
    run.counters0, run.counters1 = {"steps": 10}, {"steps": 60}
    assert _read("budget_full_pct", run) is None


def test_engine_host_gap_ms(monkeypatch):
    split = {"serve.admit": 0.01, "serve.schedule": 0.02,
             "serve.upload": 0.03, "serve.launch": 0.04,
             "serve.account": 0.05, "serve.emit": 0.06,
             "serve.fetch": 1.0, "bench.stamp": 2.0, "other": 3.0}
    monkeypatch.setattr(P, "of_run", lambda run: {
        "idle_by_phase": split, "steps": 7, "longest": []})
    run = H.Run(cell="c", seconds=10.0, trace={})
    assert _read("engine_host_gap_ms", run) == pytest.approx(
        1e3 * 0.21 / 7)


def test_engine_host_gap_ms_needs_spans(tmp_path, chip_trace):
    run = H.Run(cell="c", seconds=1.0)
    assert _read("engine_host_gap_ms", run) is None      # untraced
    run.trace = {}
    run.bench_dir = tmp_path / "bench"
    assert _read("engine_host_gap_ms", run) is None      # no serve.step
    run.bench_dir = tmp_path / "elsewhere" / "bench"
    assert _read("engine_host_gap_ms", run) is None      # no profile
