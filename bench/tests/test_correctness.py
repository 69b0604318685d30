"""The comparison that decides ``correct``, at a test size on the CPU.

The control (the reference in float8 e4m3 in the program's place) must
come out not correct where a sound run is correct, and a run with the
timed path broken underneath (a served token altered where it is
produced, a step that leaves its KV state unchanged, half of the slots
left out of the step) must come out ``correct: false``; the two KV
faults by the KV check alone, which is what sees them at the cells'
long contexts.

The limits here are this test size's, set from its own CPU readings
(2 layers at smoke widths, closed loop): sound runs read a mean gap, a
widest gap and a first-layer KV gap of 0 (seeds 21-34); the control
reads mean gaps of 1.95-2.21, widest gaps of 3.7-5.8 and a KV gap of
0.79; an altered token 2.46-2.58, 4.5-5.2 and 0; a KV state left
unchanged 0.17-0.84, 3.2-4.4 and 1.0; half of the slots left out
0.17-0.55, 2.9-4.5 and 1.73.
"""
import json

import pytest

import control
import faults
import run_cell
import tiny

LIMITS = {"gap_mean": 0.2, "gap_max": 1.0, "kv_gap": 0.4}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("c"),
                          {"closed": tiny.tiny_mix("closed")},
                          limits=LIMITS)


@pytest.fixture(scope="module")
def kv_only_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("k"),
                          {"closed": tiny.tiny_mix("closed")},
                          limits={"kv_gap": LIMITS["kv_gap"]})


def _run(root, seed, capsys):
    rc = run_cell.main(["--workload", "tiny.closed", "--seed", str(seed),
                        "--seconds", "2", "--trace", "0"],
                       require_tpu=False, root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(root, capsys):
    res = _run(root, 21, capsys)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("kind", ["token", "state", "half"])
def test_broken_timed_path_is_not_correct(root, capsys, kind):
    with faults.broken(kind):
        res = _run(root, 22, capsys)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("kind", ["state", "half"])
def test_kv_check_alone_catches_a_broken_cache(kv_only_root, capsys, kind):
    with faults.broken(kind):
        res = _run(kv_only_root, 24, capsys)
    assert not res["checks"]["kv_gap"]["value"] <= LIMITS["kv_gap"], res
    assert not res["correct"], res["checks"]


def test_control_fails_the_limits(root):
    run_cell.setup_env(root)
    c = run_cell.Cell("tiny.closed", root)
    run, sample, kv, _ = c.serve(23, 2.0)
    r = control.judged(c.cfgfile, run, 23, sample, kv)
    assert r["served"]["correct"], r["served"]
    assert not r["control"]["correct"], r["control"]
