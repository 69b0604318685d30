"""The harness on the CPU at a tiny size: each traffic kind served
through the engine, the command's refusal of a CPU device, and a cell
whose configuration, mix, metric reader and kernel work function were
added as files alone."""
import json

import numpy as np
import pytest

import harness as H
import run_cell
import tiny
import traffic_gen


@pytest.fixture(scope="module")
def engine_for():
    import jax
    from reference import model_key
    from repro.serve.engine import ServeEngine, init_serving
    import check
    cfgfile = json.loads((H.BENCH / "configs" / "chatglm3-6b.json")
                         .read_text())
    cfgfile["model"].update(tiny.TINY_MODEL)
    cfgfile["program"] = {"arch": "chatglm3-6b", "smoke": True,
                          "n_layers": 2, "pack": False}
    cfg = H.program_config(cfgfile)
    params = init_serving(cfg, model_key(5))

    def make():
        eng = ServeEngine(params, cfg, batch_slots=4, max_len=128)
        check.compile_warm(eng, 256)
        return eng
    yield make
    jax.clear_caches()


@pytest.mark.parametrize("loop,prefix", [("open", False), ("closed", False),
                                         ("open", True)])
def test_harness_records_the_window(engine_for, loop, prefix):
    eng = engine_for()
    traffic = traffic_gen.Traffic(tiny.tiny_mix(loop, prefix), 256, 11, 2.0)
    run = H.drive(eng, traffic, 2.0)
    assert run.step_compiles == 0 and run.backend_compiles == 0
    toks = run.window_tokens()
    assert toks and all(0 <= t <= 2.0 for t in toks)
    for s in run.sent:
        assert s.times == sorted(s.times)
        assert len(s.times) == len(s.req.out_tokens)
        if s.in_window:
            assert 0 <= s.sent - s.due < 1.0
    assert run.counter("scheduled_tokens") <= run.counter("grid_tokens")
    gaps = H.itl_gaps(run)
    assert gaps and min(gaps) > 0
    assert len(H.ttft_values(run)) == sum(s.in_window for s in run.sent)
    if loop == "closed":
        live = [s for s in run.sent if not s.req.done]
        assert len(live) <= traffic.concurrency
    if prefix:
        assert run.counter("prefix_hit_tokens") > 0
        assert H.metric_reader("prefix_hit_pct")(run) > 50.0


def test_command_refuses_a_cpu(capsys):
    rc = run_cell.main(["--workload", "chatglm3-6b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err


def test_cell_from_files_alone(tmp_path, capsys):
    """A config, a mix, a per-layer metric and a kernel work function,
    each a new file, found by name; the run reports the metric."""
    root = tiny.make_root(tmp_path, {"newmix": tiny.tiny_mix("open")},
                          limits={"gap_share_over_1": 1.0})
    (root / "bench/kernels/new_kernel.py").write_text(
        "def work(dims, cfgfile, positions, contexts):\n"
        "    return 2 * dims.d * len(positions), dims.d\n")
    (root / "bench/metrics/new_metric.py").write_text(
        "import harness as H\n\n\n"
        "def read(run):\n"
        "    return float(H.kernel_work('new_kernel', run.bench_dir)("
        "run.dims, run.cfgfile, [0, 1, 2], [3])[0])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "new_metric", "unit": "ops",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny.newmix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = run_cell.main(["--workload", "tiny.newmix", "--seed", "4",
                        "--seconds", "2", "--trace", "0"],
                       require_tpu=False, root=root)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["metrics"]["new_metric"]["value"] == 2 * 64 * 3
    for name in ("setup_s", "itl_p50_ms", "itl_p95_ms", "output_tok_per_s"):
        assert res["metrics"][name]["value"] > 0
    assert "ttft_p90_s" not in res["metrics"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert np.isfinite(res["checks"]["gap_share_over_1"]["value"])
