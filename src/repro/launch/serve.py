"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b \
        --smoke --requests 8 --slots 4 [--ckpt /tmp/run1] [--pack]

Loads trained master weights from a checkpoint (or random-inits),
converts them to TiM ternary codes, and serves a synthetic request wave
through the continuous-batching engine.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk width of the unified step")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="max real tokens scheduled per engine "
                         "iteration (default: slots + chunk)")
    ap.add_argument("--pack", action="store_true",
                    help="2-bit packed weights (TPC density)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import transformer as tfm
    from repro.serve.engine import (Request, ServeEngine, init_serving,
                                    ternarize_model)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if not cfg.supports_decode:
        raise SystemExit(f"{args.arch} is encoder-only")
    if args.pack:
        cfg = cfg.replace(ternary=cfg.ternary.replace(pack=True))

    key = jax.random.PRNGKey(0)
    if args.ckpt:
        from repro.train.checkpoint import restore_pytree
        state, step = restore_pytree(
            {"params": tfm.init(cfg, key), "opt": None}, args.ckpt)
        print(f"[serve] loaded checkpoint step {step}")
        sparams = ternarize_model(state["params"], cfg)
    else:
        # random weights: codes built period by period, so no fp32
        # master tree is ever whole on the device
        sparams = init_serving(cfg, key)

    engine = ServeEngine(sparams, cfg, batch_slots=args.slots,
                         max_len=args.max_len, chunk=args.chunk,
                         token_budget=args.token_budget)
    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        media = None
        if cfg.n_media_tokens:
            media = rng.normal(size=(cfg.n_media_tokens, cfg.media_dim)
                               ).astype(np.float32)
        # chunked prefill admits anything up to max_len — mix in long
        # prompts that the pre-chunking engine had to reject
        plen = int(rng.integers(4, 24)) if uid % 4 else args.max_len
        engine.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.max_new, media=media))
    t0 = time.perf_counter()
    done = engine.run_until_done()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {len(done)} requests, {toks} tokens, "
          f"{toks / dt:.1f} tok/s on {jax.devices()[0].device_kind} "
          f"(wall clock, compile included)")


if __name__ == "__main__":
    main()
