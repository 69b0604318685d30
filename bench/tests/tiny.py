"""A throwaway checkout for CPU runs: a copy of bench/ with a tiny
configuration (chatglm3-6b's smoke widths) and a tiny traffic mix,
added from files alone, registered in its own BENCHMARK.json."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_MODEL = dict(num_layers=2, hidden_size=64, ffn_hidden_size=96,
                  num_attention_heads=4, multi_query_group_num=2,
                  kv_channels=16, padded_vocab_size=256)


def tiny_mix(loop: str = "open", prefix: bool = False) -> dict:
    mix = {"loop": loop, "rate_per_s": 3.0, "concurrency": 4, "warm_s": 1,
           "strata": 8,
           "prompt": {"median": 24, "sigma": 0.5, "min": 4, "max": 60},
           "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 24}}
    if prefix:
        mix["prefix"] = {"docs": 2, "doc_len": 32}
        mix["prompt"] = {"median": 6, "sigma": 0.5, "min": 2, "max": 16}
    return mix


def make_root(tmp: pathlib.Path, mixes: dict, limits: dict = None
              ) -> pathlib.Path:
    """A checkout under ``tmp`` holding bench/ plus the tiny files; one
    cell ``tiny.<mix>`` per mix."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((BENCH / "configs" / "chatglm3-6b.json").read_text())
    cfg["model"].update(TINY_MODEL)
    cfg["program"] = {"arch": "chatglm3-6b", "smoke": True, "n_layers": 2,
                      "pack": False}
    cfg["serving"] = {"slots": 4, "max_len": 128}
    if limits is not None:
        cfg["limits"] = limits
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "CPU test size"})
    for name, mix in mixes.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
        bench["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                                   "traffic": name, "chips": 1,
                                   "why": "CPU test size"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
