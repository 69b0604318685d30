"""Dry-run machinery: input specs, variant parsing, and a real one-cell
lower+compile in a 512-device subprocess."""
import json
import os
import subprocess
import sys
import tempfile

import jax
import pytest

from repro.configs import get_config
from repro.configs.base import SHAPES


def test_input_specs_shapes():
    from repro.launch.dryrun import input_specs
    cfg = get_config("granite-34b")
    s = input_specs(cfg, SHAPES["train_4k"])
    assert s["tokens"].shape == (256, 4096)
    assert s["labels"].shape == (256, 4096)
    s = input_specs(cfg, SHAPES["decode_32k"])
    assert s["tokens"].shape == (128, 1)
    # mixed: the unified chunked-prefill step's (slots, chunk) grid
    s = input_specs(cfg, SHAPES["mixed_32k"])
    assert s["tokens"].shape == (128, 64)
    vlm = get_config("llama-3.2-vision-11b")
    s = input_specs(vlm, SHAPES["prefill_32k"])
    assert s["media"].shape == (32, 1601, 1280)
    hub = get_config("hubert-xlarge")
    s = input_specs(hub, SHAPES["train_4k"])
    assert s["frames"].shape == (256, 4096, 512)
    assert "tokens" not in s


def test_param_specs_no_allocation():
    """ShapeDtypeStruct trees only — nothing touches devices."""
    from repro.launch.dryrun import cache_sds, param_specs
    cfg = get_config("llama3-405b")
    sds = param_specs(cfg, serve=False)
    leaves = jax.tree_util.tree_leaves(sds)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    n = sum(int(l.size) for l in leaves)
    assert 400e9 < n < 420e9          # ~405B params
    caches = cache_sds(cfg, 4, 128)
    assert all(isinstance(l, jax.ShapeDtypeStruct)
               for l in jax.tree_util.tree_leaves(caches))


def test_serve_params_packed_are_quarter_size():
    from repro.launch.dryrun import param_specs
    cfg = get_config("chatglm3-6b")
    plain = param_specs(cfg, serve=True)
    packed = param_specs(
        cfg.replace(ternary=cfg.ternary.replace(pack=True)), serve=True)

    def codes_bytes(tree):
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(tree)
                   if l.dtype in (jax.numpy.int8, jax.numpy.uint8))

    assert codes_bytes(packed) * 4 <= codes_bytes(plain) + 1024


def test_xla_flags_preserved_on_import():
    """launch/dryrun must APPEND its device-count flag — overwriting
    XLA_FLAGS silently discards user/CI flags."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = "--xla_cpu_enable_fast_math=false"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import repro.launch.dryrun, os; print(os.environ['XLA_FLAGS'])"],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    flags = proc.stdout.strip().splitlines()[-1]
    assert "--xla_cpu_enable_fast_math=false" in flags, flags
    assert "--xla_force_host_platform_device_count=512" in flags, flags


def test_mixed_shape_registered_and_modeled():
    """The mixed cell exists, gates on decode support, and the roofline
    yardstick counts its scheduled (not grid) tokens."""
    from repro.configs.base import cell_supported
    from benchmarks.roofline import model_flops
    sc = SHAPES["mixed_32k"]
    assert sc.kind == "mixed" and sc.chunk == 64
    ok, _ = cell_supported(get_config("granite-34b"), sc)
    assert ok
    ok, reason = cell_supported(get_config("hubert-xlarge"), sc)
    assert not ok and "decode" in reason
    # canonical fill = (slots - 1) decode tokens + one chunk
    dec = model_flops("granite-34b", "decode_32k", "decode")
    mix = model_flops("granite-34b", "mixed_32k", "mixed")
    per_tok = dec / SHAPES["decode_32k"].global_batch
    assert abs(mix - per_tok * (128 - 1 + 64)) / mix < 1e-9


def test_paged_mixed_shared_shape_modeled():
    """The paged prefix-reuse cell exists and its hit-rate discount
    flows through the model-FLOPs yardstick: hit tokens are served from
    shared blocks, not recomputed."""
    from benchmarks.roofline import model_flops
    sc = SHAPES["mixed_32k_shared"]
    assert sc.kind == "mixed" and sc.block_size == 16
    assert sc.hit_rate == 0.75
    mix = model_flops("granite-34b", "mixed_32k", "mixed")
    shared = model_flops("granite-34b", "mixed_32k_shared", "mixed")
    per_tok = mix / (128 - 1 + 64)
    hit = int(round(64 * 0.75))
    assert abs(shared - per_tok * (128 - 1 + 64 - hit)) / shared < 1e-9


def test_paged_gather_pricing_in_roofline_row():
    """The paged cell prices the in-kernel gather: the XLA route pays
    a 2x KV round trip (copy write + copy read) on top of the memory
    term, the Pallas kernel route pays nothing extra — and both the
    saved bytes and the kernel_bench paged_attn_* ratio agree."""
    from benchmarks.roofline import _kv_write_bytes, roofline_row
    from repro.sim.chip import chip_peaks
    HBM_BW = chip_peaks().hbm_bw
    sc = SHAPES["mixed_32k_shared"]
    cell = {
        "status": "ok", "arch": "granite-34b",
        "shape": "mixed_32k_shared", "mesh": "16x16", "variant":
        "baseline", "n_devices": 256,
        "hlo": {"dot_flops": 1e12, "total_wire_bytes": 1e6},
        "memory": {"argument_size_in_bytes": 10 ** 9,
                   "output_size_in_bytes": 10 ** 8},
        "prefix_hit_rate": sc.hit_rate,
        "prefix_hit_tokens": sc.prefix_hit_tokens,
        "scheduled_tokens": sc.scheduled_mixed_tokens,
        "gather_context_tokens": sc.global_batch * sc.seq_len,
    }
    row = roofline_row(cell)
    want = 2 * _kv_write_bytes("granite-34b",
                               sc.global_batch * sc.seq_len) / 256
    assert row["gather_bytes_saved_per_dev"] == want
    assert abs(row["t_memory_xla_gather_s"]
               - (row["t_memory_s"] + want / HBM_BW)) < 1e-12
    # the analytic kernel-bench rows claim the same 3x-vs-1x shape
    from benchmarks.kernel_bench import paged_attention_rows
    for r in paged_attention_rows():
        assert r["xla_gather_bytes"] == 3 * r["kv_bytes_logical"]
        assert r["gather_bytes_saved"] == 2 * r["kv_bytes_logical"]


def test_mixed_cell_priced_from_scheduled_not_grid_tokens():
    """The roofline row prices a mixed cell's useful work from the
    cell's reported scheduled_tokens — NOT the padded (slots, chunk)
    grid it also reports — and surfaces the padding accounting."""
    from benchmarks.roofline import arch_params, roofline_row
    sc = SHAPES["mixed_32k"]
    sched = sc.global_batch - 1 + sc.chunk
    grid = sc.global_batch * sc.chunk
    cell = {
        "status": "ok", "arch": "granite-34b", "shape": "mixed_32k",
        "mesh": "16x16", "variant": "baseline", "n_devices": 256,
        "hlo": {"dot_flops": 1e12, "total_wire_bytes": 1e6},
        "memory": {"argument_size_in_bytes": 10 ** 9,
                   "output_size_in_bytes": 10 ** 8},
        "grid_tokens": grid,
        "scheduled_tokens": sched,
    }
    row = roofline_row(cell)
    assert row["sched_tokens"] == sched
    assert row["grid_tokens"] == grid
    assert abs(row["padding_efficiency"] - sched / grid) < 1e-12
    act = arch_params("granite-34b")["active"]
    want = 2.0 * act * sched / 256
    assert abs(row["model_flops_per_dev"] - want) / want < 1e-9
    # a cell whose scheduler packed FEWER tokens than the canonical
    # fill must price cheaper useful work — not the grid-sized (or
    # static-shape) constant
    cell2 = dict(cell, scheduled_tokens=sched - 50)
    row2 = roofline_row(cell2)
    want2 = 2.0 * act * (sched - 50) / 256
    assert abs(row2["model_flops_per_dev"] - want2) / want2 < 1e-9
    assert row2["model_flops_per_dev"] < row["model_flops_per_dev"]


def test_spec_draft_pricing_in_roofline_row():
    """A spec-serve cell prices its DRAFT passes at the bit-serial
    rate: each draft token costs bitserial_pass_ratio(draft, target)
    of a target token's passes (the PR-2 act-bits crossover), added to
    the compute term — the verify grid itself is already in the
    lowered HLO (draft tokens are just extra n_new rows)."""
    import pytest

    from benchmarks.roofline import arch_params, roofline_row
    from repro.sim.chip import chip_peaks
    PEAK_FLOPS = chip_peaks().peak_flops
    from repro.kernels.ops import bitserial_pass_ratio

    assert bitserial_pass_ratio(2, 4) == 0.5
    assert bitserial_pass_ratio(3, 4) == 0.75
    assert bitserial_pass_ratio(4, 4) == 1.0
    with pytest.raises(ValueError):
        bitserial_pass_ratio(0, 4)
    with pytest.raises(ValueError):
        bitserial_pass_ratio(2, 0)

    cell = {
        "status": "ok", "arch": "granite-34b", "shape": "mixed_32k",
        "mesh": "16x16", "variant": "spec", "n_devices": 256,
        "hlo": {"dot_flops": 1e12, "total_wire_bytes": 1e6},
        "memory": {"argument_size_in_bytes": 10 ** 9,
                   "output_size_in_bytes": 10 ** 8},
        "scheduled_tokens": 191,
        "draft_tokens": 116, "accepted_tokens": 91,
        "draft_bits": 2, "target_bits": 4,
    }
    row = roofline_row(cell)
    act = arch_params("granite-34b")["active"]
    assert row["draft_cost_ratio"] == 0.5
    want = 2.0 * act * 116 * 0.5 / 256
    assert abs(row["draft_flops_per_dev"] - want) / want < 1e-9
    assert abs(row["t_compute_spec_s"]
               - (row["t_compute_s"] + want / PEAK_FLOPS)) < 1e-12
    assert abs(row["spec_acceptance_rate"] - 91 / 116) < 1e-12
    # draft_bits/target_bits default to the benched int2/int4 pair
    row2 = roofline_row({k: v for k, v in cell.items()
                         if k not in ("draft_bits", "target_bits")})
    assert row2["draft_cost_ratio"] == 0.5
    # non-spec cells carry none of the speculation columns
    row3 = roofline_row({k: v for k, v in cell.items()
                         if not k.startswith(("draft", "accepted"))})
    assert "draft_cost_ratio" not in row3
    assert "t_compute_spec_s" not in row3


def test_weight_stream_summary_math():
    from repro.launch.hlo_analysis import weight_stream_summary
    rep = {"weight_bytes_resident": 1000,
           "weight_bytes_streamed_fused": 4000,
           "weight_bytes_streamed_unfused": 16000}
    s = weight_stream_summary(rep, n_devices=8)
    assert s["weight_bytes_streamed_fused_per_dev"] == 500
    assert s["weight_bytes_streamed_unfused_per_dev"] == 2000
    assert s["fused_traffic_ratio"] == 4.0
    # degenerate (no ternary leaves): ratio defined, no div-by-zero
    z = weight_stream_summary({"weight_bytes_resident": 0,
                               "weight_bytes_streamed_fused": 0,
                               "weight_bytes_streamed_unfused": 0}, 8)
    assert z["fused_traffic_ratio"] == 1.0


def test_weight_stream_report_on_sds_tree():
    """The dry-run walks eval_shape'd (ShapeDtypeStruct) param trees;
    the accounting must work without concrete arrays."""
    import jax
    from repro.launch.dryrun import param_specs
    from repro.serve.engine import weight_stream_report

    cfg = get_config("chatglm3-6b")
    cfg = cfg.replace(ternary=cfg.ternary.replace(
        encoding="asymmetric", act_mode="ternary"))
    sds = param_specs(cfg, serve=True)
    rep = weight_stream_report(sds, cfg, decode_batch=128)
    assert rep["weight_bytes_resident"] > 0
    # asymmetric two-phase serving: the historical route streams 2x
    assert rep["weight_bytes_streamed_unfused"] \
        == 2 * rep["weight_bytes_streamed_fused"]


@pytest.mark.slow
def test_one_cell_compiles_in_subprocess():
    """End-to-end dry-run of the fastest cell on the real 256-dev mesh."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "report.json")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(repo, "src")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "mamba2-1.3b", "--shape", "long_500k",
             "--mesh", "single", "--out", out],
            env=env, capture_output=True, text=True, timeout=580)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.load(open(out))
        assert report[0]["status"] == "ok"
        assert report[0]["hlo"]["dot_flops"] > 0
        # serve cells carry the fused weight-stream accounting
        ws = report[0]["weight_stream"]
        assert ws["weight_bytes_streamed_fused"] > 0
        assert ws["weight_bytes_streamed_unfused"] \
            >= ws["weight_bytes_streamed_fused"]


@pytest.mark.slow
def test_mixed_cell_compiles_with_roofline_numbers():
    """The unified chunked-prefill/decode step lowers + compiles as a
    dry-run cell and produces a roofline row (ISSUE-3 acceptance)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "report.json")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(repo, "src")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "mamba2-1.3b", "--shape", "mixed_32k",
             "--mesh", "single", "--out", out],
            env=env, capture_output=True, text=True, timeout=580)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        cell = json.load(open(out))[0]
        assert cell["status"] == "ok", cell
        assert cell["grid_tokens"] == 128 * 64
        assert cell["scheduled_tokens"] == 128 - 1 + 64
        assert cell["hlo"]["dot_flops"] > 0
        from benchmarks.roofline import roofline_row
        row = roofline_row(cell)
        assert row is not None and row["t_compute_s"] > 0
        assert row["t_memory_s"] > 0
        assert row["model_over_hlo"] > 0
