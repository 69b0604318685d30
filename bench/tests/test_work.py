"""Kernel work functions and the readers built on them, against hand
arithmetic at chatglm3-6b's published widths."""
import json

import numpy as np
import pytest

import harness as H
import peaks
from reference import dims_of

CFG = json.loads((H.BENCH / "configs" / "chatglm3-6b.json").read_text())
DIMS = dims_of(CFG)
# per layer: q, o 4096x4096; k, v 4096x256; gate, up, down 4096x13696
PER_LAYER = 2 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 13696
assert PER_LAYER == 203_948_032


def test_tim_matmul_work_at_chatglm_widths():
    work = H.kernel_work("tim_matmul")
    pos, ctx = np.arange(40), np.array([40])
    ops, nbytes = work(DIMS, CFG, pos, ctx)
    assert ops == 2 * 28 * PER_LAYER * 40 == 456_843_591_680
    assert nbytes == 28 * PER_LAYER == 5_710_544_896
    packed = dict(CFG, program=dict(CFG["program"], pack=True))
    assert work(DIMS, packed, pos, ctx)[1] == 5_710_544_896 / 4


def test_paged_attention_work_at_chatglm_widths():
    work = H.kernel_work("paged_attention")
    # two tokens at positions 98, 99 of one slot whose context is 100
    ops, nbytes = work(DIMS, CFG, np.array([98, 99]), np.array([100, 0]))
    assert ops == 4 * 32 * 128 * 28 * (99 + 100) == 91_291_648
    assert nbytes == 2 * 2 * 128 * 2 * 28 * 100 == 2_867_200


def test_peaks_table_refuses_unknown_devices():
    assert peaks.for_device("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        peaks.for_device("cpu")


def _run_with_trace():
    run = H.Run(cell="c", seconds=1.0, dims=DIMS, cfgfile=CFG,
                peaks=peaks.for_device("TPU v5 lite"))
    # one step: 24 decode tokens at context 1000, one 16-token chunk
    pos = np.concatenate([np.full(24, 999), np.arange(100, 116)])
    ctx = np.concatenate([np.full(24, 1000), [116]])
    run.steps = [(pos, ctx)]
    run.trace = {"window_s": 0.5, "busy_s": 0.4,
                 "ops": {"tim_matmul_fused": 0.2, "paged_attention": 0.01,
                         "fusion": 0.1}}
    s = H.Sent(req=None, due=0.0, sent=0.0, in_window=True,
               times=[0.1] * 24)
    run.sent = [s]
    return run, pos, ctx


def test_rooflines_and_mfu_by_hand():
    run, pos, ctx = _run_with_trace()
    p = run.peaks
    lin_ops = 2 * 28 * PER_LAYER * 40
    lin_least = max(lin_ops / p["int8_ops"],
                    28 * PER_LAYER / p["hbm_bytes_per_s"])
    got = H.metric_reader("tim_matmul_roofline")(run)
    assert got == pytest.approx(100 * lin_least / 0.2)
    att_ops = 4 * 32 * 128 * 28 * float((pos + 1).sum())
    att_bytes = 2 * 2 * 128 * 2 * 28 * float(ctx.sum())
    att_least = max(att_ops / p["bf16_flops"],
                    att_bytes / p["hbm_bytes_per_s"])
    got = H.metric_reader("paged_attention_roofline")(run)
    assert got == pytest.approx(100 * att_least / 0.01)
    head = 2 * 4096 * 65024 * 24
    mfu = (lin_ops / p["int8_ops"] + (att_ops + head) / p["bf16_flops"]) / 0.5
    assert H.metric_reader("step_mfu_pct")(run) == pytest.approx(100 * mfu)
    assert H.metric_reader("device_idle_pct")(run) == pytest.approx(20.0)


def test_readers_return_nothing_without_their_source():
    run, _, _ = _run_with_trace()
    run.trace["ops"] = {"fusion": 0.1}
    assert H.metric_reader("tim_matmul_roofline")(run) is None
    assert H.metric_reader("paged_attention_roofline")(run) is None
    run.trace = None
    assert H.metric_reader("step_mfu_pct")(run) is None
    assert H.metric_reader("device_idle_pct")(run) is None
