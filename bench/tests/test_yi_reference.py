"""The yi-class engine against the plain reference, at a test size on
the CPU: ``yi-34b-15l.json`` at yi-34b's smoke widths (7 query heads
over 1 KV head of 16, standard RoPE, 2-bit packed codes, 2 layers),
served through ``Cell.serve`` under a tiny closed loop.

The limits are this test size's, set from its own CPU readings (seeds
21-24, 3 s windows): sound runs read a mean gap, a widest gap and a
first-layer KV gap of 0; the control (the reference in float8 e4m3)
reads mean gaps of 1.89-2.26, widest gaps of 4.36-5.0 and KV gaps of
0.79-0.99; a KV state left unchanged reads 0, 0 and 1.0 (its tokens
hold, its cache does not).
"""
import json

import numpy as np
import pytest

import check
import control
import faults
import harness as H
import run_cell
import tiny
from reference import model_key

LIMITS = {
    # sound 0 on every seed, control >= 1.89: room of ~2x on both sides
    "gap_mean": 0.8,
    # sound 0, control >= 4.36: one logit's spread of room either way
    "gap_max": 2.0,
    # sound 0, control >= 0.79, a KV state left unchanged 1.0
    "kv_gap": 0.4,
}
# the smoke widths of configs/yi_34b.py, under the source's key names
YI_SMOKE = dict(num_hidden_layers=2, hidden_size=112, intermediate_size=128,
                num_attention_heads=7, num_key_value_heads=1, vocab_size=256)
# prompts of 32 or more, so the requests in flight at the window's close
# hold well over the 64 cached positions the KV check needs
MIX = {"loop": "closed", "concurrency": 4, "warm_s": 1, "strata": 8,
       "prompt": {"median": 40, "sigma": 0.4, "min": 32, "max": 64},
       "output": {"median": 24, "sigma": 0.4, "min": 16, "max": 48}}
SECONDS = 3.0


def yi_cfgfile(pack: bool = True) -> dict:
    cfg = json.loads((H.BENCH / "configs" / "yi-34b-15l.json").read_text())
    cfg["model"].update(YI_SMOKE)
    cfg["program"] = {"arch": "yi-34b", "smoke": True, "n_layers": 2,
                      "pack": pack}
    cfg["serving"] = {"slots": 4, "max_len": 128}
    cfg["limits"] = LIMITS
    return cfg


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """A checkout whose cell ``tiny.closed`` serves the yi configuration
    (``tiny.make_root``'s configuration file replaced by it)."""
    root = tiny.make_root(tmp_path_factory.mktemp("yi"), {"closed": MIX})
    (root / "bench/configs/tiny.json").write_text(json.dumps(yi_cfgfile()))
    run_cell.setup_env(root)
    return run_cell.Cell("tiny.closed", root)


@pytest.fixture(scope="module")
def judged(cell):
    """One sound run, judged as served and with the control in the
    program's place."""
    run, sample, kv, _ = cell.serve(21, SECONDS)
    return control.judged(cell.cfgfile, run, 21, sample, kv)


def test_cell_serves_the_yi_class_program(cell):
    cfg = H.program_config(cell.cfgfile)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.hd) == (7, 1, 16)
    assert cfg.rope_variant == "standard" and cfg.ternary.pack


def test_sound_run_is_correct(judged):
    assert judged["served"]["correct"], judged["served"]


def test_control_is_not_correct(judged):
    assert not judged["control"]["correct"], judged["control"]


def test_kv_state_left_unchanged_is_not_correct(cell):
    with faults.broken("state"):
        run, sample, kv, _ = cell.serve(22, SECONDS)
    r = check.compare(cell.cfgfile, run.dims, 22, sample, kv, run)["served"]
    assert not r["kv_gap"]["ok"], r
    assert not all(x["ok"] for x in r.values()), r


def _engine(pack: bool, seed: int):
    from repro.serve.engine import ServeEngine, init_serving
    cfg = H.program_config(yi_cfgfile(pack))
    return ServeEngine(init_serving(cfg, model_key(seed)), cfg,
                       batch_slots=4, max_len=128)


def test_packed_and_int8_codes_serve_alike():
    """The same codes in 2-bit packed and in int8 storage: the same
    tokens, and the same first-layer K and V, bit for bit."""
    from repro.serve.engine import Request
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (37, 50, 21, 64, 44)]
    engines = [_engine(pack, 31) for pack in (True, False)]
    kvs, outs = [], []
    for eng in engines:
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid=uid, prompt=p, max_new_tokens=12))
        for _ in range(8):      # mid-flight: prefills and decodes
            eng.step()
        kvs.append(check.kv_readback(eng))
        outs.append({r.uid: list(r.out_tokens)
                     for r in eng.run_until_done()})
    packed, int8 = kvs
    assert len(packed) == len(int8) >= 2
    for a, b in zip(packed, int8):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["kv"], b["kv"])
    assert outs[0] == outs[1] and len(outs[0]) == len(prompts)


def test_packed_work_at_the_cells_widths():
    """Hand arithmetic at Yi-34B's widths, 15 layers: per layer q and o
    7168 x 7168, k and v 7168 x 1024, gate, up and down 7168 x 20480."""
    cfg = json.loads((H.BENCH / "configs" / "yi-34b-15l.json").read_text())
    from reference import dims_of
    dims = dims_of(cfg)
    per_layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 20480
    assert 15 * per_layer == 8_367_636_480
    pos = np.arange(32)
    ops, nbytes = H.kernel_work("tim_matmul")(dims, cfg, pos, None)
    assert ops == 2 * 8_367_636_480 * 32
    assert nbytes == 2_091_909_120


def test_packed_roofline_by_hand_and_silent_without_its_kernel():
    """The reader counts ``tim_matmul_fused_packed`` alone, and reads
    nothing from a trace without it (a program that names every fused
    kernel ``tim_matmul_fused``)."""
    import peaks
    from reference import dims_of
    cfg = json.loads((H.BENCH / "configs" / "yi-34b-15l.json").read_text())
    run = H.Run(cell="c", seconds=1.0, dims=dims_of(cfg), cfgfile=cfg,
                peaks=peaks.for_device("TPU v5 lite"))
    run.steps = [(np.arange(32), np.full(16, 100))] * 2
    run.trace = {"window_s": 1.0, "busy_s": 0.9,
                 "ops": {"tim_matmul_fused_packed": 0.1,
                         "tim_matmul_fused": 0.3, "paged_attention": 0.2}}
    read = H.metric_reader("tim_matmul_packed_roofline")
    least = 2_091_909_120 / 819e9       # HBM-bound at 32 tokens a step
    assert 2 * 8_367_636_480 * 32 / 393e12 < least
    assert read(run) == pytest.approx(100 * 2 * least / 0.1)
    run.trace["ops"] = {"tim_matmul_fused": 0.4, "paged_attention": 0.2}
    assert read(run) is None
