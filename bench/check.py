"""What decides ``correct``: the served tokens of a sample of finished
requests and the KV cache of every request in flight at the window's
close, against the plain reference, plus the window's own hygiene.

For every served token the reference (``reference.py``, rebuilt from
the seed, run once over the prompt and the served tokens) gives its
logits at that position; the gap is how far the served token's logit
lies below the reference's best.  Greedy serving puts the program's
best first, so a sound program reads gaps of rounding size, and a
program that computes another model reads gaps of the logits' spread.

The KV cache is read back through each in-flight request's block
table (blocks shared by prefix hits included) and its first layer held
against the reference's: per position, the distance of the cached K
and V from the reference's over the reference's norm.  That sees every
write of the window (chunked prefill, decode, shared documents) at the
cell's own context lengths, where attention's output lies under the
activation threshold and leaves the logits alone.

The limits live in the configuration file (``limits``), each set from
the readings that ``PERF.md`` lists.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

import harness as H
from reference import forward_logits, layer0_kv

SAMPLE_TOKENS = 256       # served tokens compared per run, at least
SAMPLE_MAX = 8            # requests compared per run, at most
DRAIN_CAP_S = 60.0        # serving after the window until that many finish


def compile_warm(engine, vocab: int) -> None:
    """Compile the engine's shapes before any timed traffic: a short
    request through prefill and decode (step, sampler, table rows), then
    the same prompt again, whose partial last block is matched
    copy-on-write (the block copy)."""
    from repro.serve.engine import Request
    prompt = np.random.default_rng(0).integers(
        0, vocab, engine.block_size + 3).astype(np.int32)
    for uid in (-1, -2):
        engine.submit(Request(uid=uid, prompt=prompt, max_new_tokens=3))
        engine.run_until_done()


def sample(run: "H.Run", seed: int) -> List[Dict]:
    """Finished requests drawn from the seed, the longest among them,
    until ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX`` requests."""
    done = H.finished(run)
    if not done:
        return []
    done.sort(key=lambda s: (len(s.req.out_tokens), len(s.req.prompt),
                             s.req.uid), reverse=True)
    rng = np.random.default_rng([seed, 7])
    order = [0] + (1 + rng.permutation(len(done) - 1)).tolist()
    out, tokens = [], 0
    for i in order:
        if tokens >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        r = done[i].req
        out.append({"prompt": np.asarray(r.prompt, np.int32),
                    "served": np.asarray(r.out_tokens, np.int32)})
        tokens += len(r.out_tokens)
    return out


def kv_readback(engine) -> List[Dict]:
    """Every in-flight request's tokens whose KV the pool holds, with the
    first layer's cached K and V read back through its block table:
    (len, 2, kv heads, head_dim) f32."""
    pool = engine.caches["b0"]
    bs = engine.block_size
    out = []
    for i, req in enumerate(engine.slot_req):
        n = int(engine.cache_len[i])
        if req is None or n == 0:
            continue
        bids = engine.block_tables[i, :-(-n // bs)]
        kv = [np.asarray(pool[x][0, bids].astype(np.float32))
              .reshape(-1, *pool[x].shape[-2:])[:n] for x in ("k", "v")]
        tokens = np.concatenate([np.asarray(req.prompt, np.int32),
                                 np.asarray(req.out_tokens, np.int32)])[:n]
        out.append({"tokens": tokens, "kv": np.stack(kv, 1)})
    return out


def kv_gap(got: List[np.ndarray], want: List[np.ndarray]) -> float:
    """Widest per-position distance of the cached K and V from the
    reference's, over the reference's norm at that position."""
    worst = 0.0
    for g, w in zip(got, want):
        d = np.linalg.norm((g - w).reshape(len(w), -1), axis=1)
        ref = np.linalg.norm(w.reshape(len(w), -1), axis=1)
        worst = max(worst, float((d / np.maximum(ref, 1e-30)).max()))
    return worst


def reference_logits(dims, seed: int, sample_: List[Dict], max_len: int,
                     precisions=("served",)):
    seqs = [np.concatenate([s["prompt"], s["served"]]) for s in sample_]
    starts = [len(s["prompt"]) - 1 for s in sample_]
    return seqs, forward_logits(dims, seed, seqs, starts, max_len,
                                precisions)


def gaps_of(logits: List[np.ndarray], tokens: List[np.ndarray]
            ) -> np.ndarray:
    """Per position: best logit minus the logit of the given token."""
    out = []
    for lg, tok in zip(logits, tokens):
        best = lg.max(axis=-1)
        out.append(best - lg[np.arange(len(tok)), tok])
    return np.concatenate(out) if out else np.zeros(0)


def readings(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers a limit can hold: the widest gap and the mean gap
    over every compared token (and the share of gaps over 1, read for
    the record)."""
    if gaps.size == 0:
        return {"gap_max": math.inf, "gap_mean": math.inf,
                "gap_share_over_1": 1.0}
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "gap_share_over_1": float((gaps > 1.0).mean())}


def compare(cfgfile: Dict, dims, seed: int, sample_: List[Dict],
            kv: List[Dict], run: "H.Run", judge=("served",)
            ) -> Dict[str, Dict[str, Dict]]:
    """The checks of each judged side: ``served`` judges the program's
    tokens and KV; ``control`` puts the control (the reference in float8
    e4m3) in the program's place: the token it puts first at each
    served position, and its own first-layer KV."""
    limits = cfgfile["limits"]
    max_len = int(cfgfile["serving"]["max_len"])
    precisions = ("served",) + tuple(j for j in judge if j != "served")
    _, lg = reference_logits(dims, seed, sample_, max_len, precisions)
    ref_kv = layer0_kv(dims, seed, [r["tokens"] for r in kv], max_len,
                       precisions)
    out = {}
    for side in judge:
        if side == "served":
            tokens = [s["served"] for s in sample_]
            got_kv = [r["kv"] for r in kv]
        else:
            tokens = [c.argmax(axis=-1) for c in lg[side]]
            got_kv = ref_kv[side]
        out[side] = _checks(limits, lg["served"], tokens,
                            kv_gap(got_kv, ref_kv["served"]),
                            sum(len(r["tokens"]) for r in kv), run, side)
    return out


def _checks(limits, ref_logits, tokens, kvg: float, kv_positions: int,
            run: "H.Run", side: str) -> Dict[str, Dict]:
    gaps = gaps_of(ref_logits, tokens)
    read = readings(gaps)
    read["kv_gap"] = kvg
    at = 0
    for t in tokens:
        g = gaps[at:at + len(t)]
        at += len(t)
        off = np.flatnonzero(g > 0.0)
        H.log_err(f"  {side}: request served {len(t)}: gaps > 0 at "
                  f"{off[:8].tolist()} ({off.size}), max "
                  f"{float(g.max()) if g.size else 0.0!r}")
    H.log_err(f"{side}: drained {run.drain_s:.1f} s after the window; "
              f"compared {gaps.size} served tokens of {len(tokens)} "
              f"requests; gap median "
              f"{float(np.median(gaps)) if gaps.size else math.nan!r}, "
              f"p99 {H.percentile(gaps, 99)!r}, widest "
              f"{read['gap_max']!r}, share > 1 "
              f"{read['gap_share_over_1']!r}; first-layer KV of "
              f"{kv_positions} cached positions, widest gap {kvg!r}")
    checks = {}
    for name, limit in limits.items():
        v = read[name]
        checks[name] = {"value": v, "limit": limit, "ok": v <= limit}
    compiles = run.backend_compiles + run.step_compiles
    checks["window_compiles"] = {"value": compiles, "limit": 0,
                                 "ok": compiles == 0}
    # floors, not ceilings: too few tokens or positions compares nothing
    checks["compared_tokens"] = {"value": int(gaps.size), "limit": 64,
                                 "ok": gaps.size >= 64}
    checks["kv_positions"] = {"value": int(kv_positions), "limit": 64,
                              "ok": kv_positions >= 64}
    return checks
