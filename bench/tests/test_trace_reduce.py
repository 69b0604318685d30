"""The trace reduction: on plain events by hand, and on a small trace
recorded on the chip (a one-second traced window of chatglm3-6b.chat)
against a direct recount of its raw events."""
import gzip
import pathlib
import shutil

import pytest

import trace_reduce as T

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_reduction_by_hand():
    ms = 1_000_000
    host = {"bench.window": [(0, 100 * ms)],
            "bench.step": [(0, 40 * ms), (50 * ms, 95 * ms)],
            "bench.stamp": [(40 * ms, 50 * ms)]}
    dev = [[("tim_matmul_fused.3", 5 * ms, 20 * ms),
            ("fusion.1", 15 * ms, 30 * ms),        # overlaps the kernel
            ("tim_matmul_fused.7", 60 * ms, 90 * ms),
            ("copy.2", 95 * ms, 120 * ms)]]        # runs past the window
    r = T.reduce_events(host, dev)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [5, 30] + [60, 90] + [95, 100] ms
    assert r["busy_s"] == pytest.approx(0.060)
    assert r["ops"]["tim_matmul_fused"] == pytest.approx(0.045)
    assert r["ops"]["copy"] == pytest.approx(0.005)
    # idle: [0, 5] under a step; [30, 60] 20 ms under steps, 10 under
    # the stamp; [90, 95] under a step
    assert r["idle_by_span"] == {"bench.step": pytest.approx(0.040)}
    assert r["breakdown"]["idle_gaps"][0] == ["bench.step",
                                              pytest.approx(0.030)]
    assert r["breakdown"]["device_ops"][0][0] == "tim_matmul_fused"


def test_no_window_or_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_events({}, [[("x", 0, 1)]])
    with pytest.raises(ValueError):
        T.reduce_events({"bench.window": [(0, 10)]}, [[("x", 20, 30)]])


def test_chip_trace(tmp_path):
    src = DATA / "chat_1s.xplane.pb.gz"
    dst = tmp_path / "plugins" / "profile" / "run" / "t.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(src) as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    host, devices = T.load(str(dst))
    r = T.reduce_dir(tmp_path)
    (w0, w1), = host["bench.window"]
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert r["devices"] == 1
    # recount: clipped op time per name, and the union by a sweep
    evs = sorted((max(a, w0), min(b, w1), T.op_name(n))
                 for n, a, b in devices[0] if b > w0 and a < w1)
    evs = [e for e in evs if e[2] not in T.CONTAINERS]
    fused = sum(b - a for a, b, n in evs if n == "tim_matmul_fused")
    assert r["ops"].get("tim_matmul_fused", 0.0) == \
        pytest.approx(fused / 1e9)
    busy, end = 0.0, w0
    for a, b, _ in evs:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert fused > 0
    assert any(n.startswith("paged_attention") for n in r["ops"])
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10
