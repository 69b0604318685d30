"""Serve a ternary model with chunked-prefill continuous batching.

Builds a smoke-size model, converts it to TiM serving codes (int8 or
2-bit packed), and submits a wave of variable-length requests —
including one prompt of the full ``max_len`` (the pre-chunking engine
rejected anything past ``max_len - 1``) — through the token-budget
scheduler.  Every engine iteration runs ONE jitted (slots, chunk) step
mixing decode tokens with prefill chunks, so the long prompt streams
through the shared cache without ever stalling running decodes.

Run:  PYTHONPATH=src python examples/serve_ternary.py [--arch NAME]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.serve.engine import Request, ServeEngine, init_serving


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk width of the unified step")
    ap.add_argument("--pack", action="store_true",
                    help="2-bit packed weights (TPC storage density)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch, smoke=True)
    if not cfg.supports_decode:
        raise SystemExit(f"{args.arch} is encoder-only; pick a decoder")
    if args.pack:
        cfg = cfg.replace(ternary=cfg.ternary.replace(pack=True))

    sparams = init_serving(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(sparams, cfg, batch_slots=args.slots,
                         max_len=args.max_len, chunk=args.chunk)

    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        # uid 0 exercises the chunked-prefill path with a prompt of the
        # full cache length — longer than the old max_len - 1 limit
        plen = args.max_len if uid == 0 else int(rng.integers(4, 24))
        media = None
        if cfg.n_media_tokens:
            media = rng.normal(size=(cfg.n_media_tokens,
                                     cfg.media_dim)).astype(np.float32)
        engine.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.max_new, media=media))

    t0 = time.perf_counter()
    done = engine.run_until_done()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out_tokens) for r in done)
    assert len(done) == args.requests, (len(done), args.requests)
    assert engine.n_step_compiles == 1, engine.n_step_compiles
    long_req = next(r for r in done if r.uid == 0)
    assert len(long_req.prompt) == args.max_len
    print(f"arch={cfg.name} pack={args.pack} chunk={args.chunk} "
          f"budget={engine.token_budget} step_compiles="
          f"{engine.n_step_compiles}")
    print(f"served {len(done)} requests / {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens / dt:.1f} tok/s wall clock on "
          f"{jax.devices()[0].platform}, compile included); "
          f"longest prompt {args.max_len} prefilled in "
          f"{-(-args.max_len // args.chunk)} chunks")
    for r in done[:3]:
        print(f"  req {r.uid}: prompt_len={len(r.prompt)} "
              f"prompt[:6]={r.prompt[:6].tolist()} -> "
              f"out[:8]={r.out_tokens[:8]}")


if __name__ == "__main__":
    main()
