"""Chip smoke test: the serving main path on one TPU, at full published
width, in one process.

    python chip_smoke.py [--seed N]

Run from the root of a checkout on a machine with a TPU.  Phases:

  1. every Pallas kernel variant on the serving path, once at
     chatglm3-6b widths, against its XLA route (``impl='xla'``);
  2. chatglm3-6b (28 layers, d_model 4096, 32 heads / 2 KV heads,
     d_ff 13696, vocab 65024) with random weights from the seed, built
     as TiM serving codes one period at a time (``init_serving``) under
     the paper's two-phase policy (asymmetric weights, ternary
     activations);
  3. one prefill chunk through the engine's step from the same
     1120-token cache, with the TiM matmuls on their kernels and on
     ``impl='xla'``: logits compared;
  4. ``ServeEngine`` with 8 slots and a 2048-token cache serves 8
     requests of 1100-1600 prompt tokens and 16 new tokens each; the
     engine's invariants hold, its pool drains, and its compiled step
     holds the TiM matmul and paged-attention kernels.

Wall times printed on the way are smoke timings of a single cold run,
compile included: not benchmark results.  On success the last line is
``{"ok": true, "device": {...}}``; any failed check exits non-zero
without it.  Off a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SLOTS, MAX_LEN, NEW_TOKENS = 8, 2048, 16
PROMPT_LENS = (1100, 1600)
CHECK_CONTEXT = 1120          # cache built before the compared chunk

# Tolerances, each with its reason.
#
# TiM matmuls: both routes accumulate the int8 products exactly in
# int32; only the f32 epilogue (scale, combine, subtract the phases)
# can round differently (FMA contraction), a few ulp of the largest
# output.  A single wrong code moves an output by a whole weight scale,
# about 1/64 of the largest output at K = 4096, far above this bound.
TIM_RTOL = 1e-5
# Paged attention: f32 queries, both routes at full f32 matmul
# precision; what remains is reduction order and the vector unit's exp,
# ~1e-6 of O(1) scores.  The outputs are averages of O(1) values; one
# wrongly gathered or masked 16-token block moves them by ~1e-2.
ATTN_ATOL = 1e-4
# Engine logits: both steps run from the same cache through the same
# attention kernel and are compiled to round to bf16 wherever the
# program says (see logits_phase); their TiM matmuls accumulate the
# same integers and apply the same f32 epilogue, which the kernel phase
# shows bit-identical at these widths.  So the logits should agree bit
# for bit.  A looser bound would test nothing: ternary activations are
# a step function, a one-ulp difference that lands on a code threshold
# flips the code, and a few flips over 28 random-weight layers move a
# slot's logits by ~100% relative L2 (measured on the chip).  The limit
# leaves room for last-layer rounding only.
LOGIT_REL_L2 = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    def __init__(self):
        self.failed = []

    def check(self, name: str, ok: bool, detail: str) -> None:
        log(f"[{'pass' if ok else 'FAIL'}] {name}: {detail}")
        if not ok:
            self.failed.append(name)


@contextlib.contextmanager
def smoke_timer(label: str):
    t0 = time.perf_counter()
    yield
    log(f"[smoke timing] {label}: {time.perf_counter() - t0:.1f} s "
        f"(one cold run, compile included; not a benchmark)")


def tim_cfg(cfg, impl: str):
    return cfg.replace(ternary=cfg.ternary.replace(
        enabled=True, encoding="asymmetric", act_mode="ternary",
        impl=impl))


def kernel_phase(checks: Checks, cfg, rng) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.ternary import TernaryScales
    from repro.core.weights import ternarize_weight
    from repro.kernels import ops as kops
    from repro.nn import attention as attn

    def rel_err(a, b):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))

    d, ff = cfg.d_model, cfg.d_ff
    w_real = jnp.asarray(rng.normal(size=(d, ff)) * 0.02, jnp.float32)
    weights = {pack: ternarize_weight(w_real, "asymmetric", pack=pack)
               for pack in (False, True)}
    one = jnp.ones((), jnp.float32)
    act = TernaryScales(one, one, sym=True)
    x_tern = jnp.asarray(rng.integers(-1, 2, size=(SLOTS * 16, d)),
                         jnp.int8)
    cases = [("two-phase int8", lambda impl: kops.tim_matmul(
                  x_tern, weights[False], act, impl=impl)),
             ("two-phase packed", lambda impl: kops.tim_matmul(
                  x_tern, weights[True], act, impl=impl))]
    for bits, pack in ((2, False), (4, False), (2, True)):
        codes = jnp.asarray(rng.integers(0, 1 << bits, size=(SLOTS, d)),
                            jnp.int8)
        step = jnp.asarray(1.0 / ((1 << bits) - 1), jnp.float32)
        cases.append((
            f"bit-serial int{bits}{' packed' if pack else ''}",
            lambda impl, c=codes, s=step, b=bits, p=pack:
                kops.tim_matmul_bitserial(c, s, weights[p], b, impl=impl)))
    w_nmax = ternarize_weight(w_real[:, :d], "asymmetric")
    cases.append(("two-phase n_max=7", lambda impl: kops.tim_matmul(
        x_tern[:32], w_nmax, act, n_max=7, impl=impl)))
    for name, fn in cases:
        got, want = fn("pallas"), fn("xla")
        err = rel_err(got, want)
        checks.check(f"kernel {name}", bool(err <= TIM_RTOL),
                     f"shape {tuple(got.shape)}, max |pallas - xla| / "
                     f"max |xla| = {err:.3g} (limit {TIM_RTOL:g})")

    # paged attention at the model's attention widths, 2048-token
    # tables, lengths past one 1024-token KV chunk
    h, hk, hd, bs = cfg.n_heads, cfg.n_kv_heads, cfg.hd, 16
    nblk = MAX_LEN // bs
    nb = SLOTS * nblk + SLOTS
    tables = jnp.asarray(rng.permutation(nb)[:SLOTS * nblk]
                         .reshape(SLOTS, nblk), jnp.int32)
    lens = jnp.asarray(rng.integers(*PROMPT_LENS, size=SLOTS), jnp.int32)
    pool = {k: jnp.asarray(rng.normal(size=(nb, bs, hk, hd)),
                           jnp.bfloat16) for k in "kv"}
    pool8 = {k: jnp.asarray(rng.integers(-127, 128, size=(nb, bs, hk, hd)),
                            jnp.int8) for k in "kv"}
    scale8 = {k: jnp.asarray(rng.uniform(0.005, 0.02, size=(nb, bs, hk)),
                             jnp.bfloat16) for k in "kv"}
    for sq in (1, 16):
        q = jnp.asarray(rng.normal(size=(SLOTS, sq, h, hd)), jnp.float32)
        for kv_name in ("bf16", "int8"):
            if kv_name == "bf16":
                kw = dict(k_cache=pool["k"], v_cache=pool["v"])
            else:
                kw = dict(k_cache=pool8["k"], v_cache=pool8["v"],
                          k_scale=scale8["k"], v_scale=scale8["v"])
            outs = {}
            for impl in ("pallas", "xla"):
                with jax.default_matmul_precision("highest"):
                    outs[impl] = attn.mixed_attention(
                        q, kv_valid_len=lens, q_offset=lens - sq,
                        block_tables=tables, impl=impl, **kw)
            err = float(np.abs(np.asarray(outs["pallas"])
                               - np.asarray(outs["xla"])).max())
            checks.check(f"kernel paged attention Sq={sq} {kv_name} pool",
                         err <= ATTN_ATOL,
                         f"max |pallas - xla| = {err:.3g} "
                         f"(limit {ATTN_ATOL:g})")
    # token-packed layout: one query per token, tables indexed per slot
    t = SLOTS + 16
    seg = jnp.asarray(rng.integers(0, SLOTS, size=t), jnp.int32)
    qoff = lens[seg] - 1 - jnp.asarray(rng.integers(0, 16, size=t),
                                       jnp.int32)
    q = jnp.asarray(rng.normal(size=(t, 1, h, hd)), jnp.float32)
    outs = {}
    for impl in ("pallas", "xla"):
        with jax.default_matmul_precision("highest"):
            outs[impl] = attn.packed_mixed_attention(
                q, pool["k"], pool["v"], seg, qoff + 1, qoff,
                block_tables=tables, impl=impl)
    err = float(np.abs(np.asarray(outs["pallas"])
                       - np.asarray(outs["xla"])).max())
    checks.check("kernel paged attention, token-packed", err <= ATTN_ATOL,
                 f"max |pallas - xla| = {err:.3g} (limit {ATTN_ATOL:g})")


def logits_phase(checks: Checks, params, cfg, rng) -> None:
    """One prefill chunk through the engine's step with the TiM matmuls
    on their kernels and on ``impl='xla'``, from one cache built on the
    kernel route."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as tfm
    from repro.serve.block_pool import default_num_blocks
    from repro.serve.engine import make_paged_unified_step

    chunk, bs = 16, 16
    nblk = MAX_LEN // bs
    caches = tfm.init_paged_caches(
        cfg, SLOTS, default_num_blocks(SLOTS, MAX_LEN, bs), bs)
    tables = np.arange(SLOTS * nblk, dtype=np.int32).reshape(SLOTS, nblk)
    tokens = rng.integers(0, cfg.vocab_size,
                          size=(SLOTS, CHECK_CONTEXT + chunk)).astype(
                              np.int32)
    n_new = jnp.full((SLOTS,), chunk, jnp.int32)
    tables_dev = jnp.asarray(tables)

    def args_at(start):
        pos = start + np.arange(chunk)
        smap = tables[:, pos // bs] * bs + pos % bs
        return ({"tokens": jnp.asarray(tokens[:, start:start + chunk])},
                jnp.full((SLOTS,), start, jnp.int32), n_new, tables_dev,
                jnp.asarray(smap, jnp.int32))

    # XLA may keep an f32 value where the program rounds it to bf16
    # (excess precision), and only on its own route: a kernel's bf16
    # output is rounded.  Both steps are compiled to round where the
    # program says, so the routes compute the same numbers.
    exact = {"xla_allow_excess_precision": False}
    args = args_at(0)
    steps = {impl: jax.jit(make_paged_unified_step(tim_cfg(cfg, impl)))
             .lower(params, args[0], caches, *args[1:])
             .compile(compiler_options=exact)
             for impl in ("pallas", "xla")}
    with smoke_timer(f"build a {CHECK_CONTEXT}-token cache on the kernel "
                     f"route ({CHECK_CONTEXT // chunk} steps)"):
        for start in range(0, CHECK_CONTEXT, chunk):
            b, cl, nn, tb, sm = args_at(start)
            _, caches = steps["pallas"](params, b, caches, cl, nn, tb, sm)
        jax.block_until_ready(caches)
    b, cl, nn, tb, sm = args_at(CHECK_CONTEXT)
    lg = {impl: step(params, b, caches, cl, nn, tb, sm)[0]
          for impl, step in steps.items()}
    got = np.asarray(lg["pallas"], np.float64)
    want = np.asarray(lg["xla"], np.float64)
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))
    finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
    checks.check(
        "engine step logits, TiM kernels vs impl='xla'",
        finite and bool((rel <= LOGIT_REL_L2).all()),
        f"chunk at position {CHECK_CONTEXT}, logits {got.shape}, finite="
        f"{finite}, per-slot relative L2 error "
        f"{[float(f'{r:.3g}') for r in rel]} (limit {LOGIT_REL_L2:g}), "
        f"{int((got != want).sum())} logits differ; argmax agrees in "
        f"{int((got.argmax(-1) == want.argmax(-1)).sum())}/{SLOTS}")


def serve_phase(checks: Checks, params, cfg, rng) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.hlo_analysis import tpu_kernel_calls
    from repro.serve.engine import Request, ServeEngine

    engine = ServeEngine(params, cfg, batch_slots=SLOTS, max_len=MAX_LEN)
    lens = rng.integers(*PROMPT_LENS, size=SLOTS, endpoint=True)
    for uid, plen in enumerate(lens):
        engine.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=NEW_TOKENS))
    log(f"[serve] {SLOTS} requests, prompt lengths {lens.tolist()}, "
        f"{NEW_TOKENS} new tokens each, chunk {engine.chunk}, token "
        f"budget {engine.token_budget}")
    with smoke_timer("run_until_done"):
        done = engine.run_until_done()
    st = engine.stats()
    log(f"[serve] steps {st['steps']}, scheduled tokens "
        f"{st['scheduled_tokens']}, output tokens {st['output_tokens']}, "
        f"step compiles {engine.n_step_compiles}")
    outs = {r.uid: len(r.out_tokens) for r in done}
    checks.check("serve: every request returns its tokens",
                 sorted(outs) == list(range(SLOTS))
                 and all(n == NEW_TOKENS for n in outs.values()),
                 f"tokens per request {outs}")
    try:
        engine.validate()
        ok, detail = True, "engine.validate() holds"
    except AssertionError as e:
        ok, detail = False, f"engine.validate() failed: {e!r}"
    checks.check("serve: engine invariants", ok, detail)
    checks.check("serve: pool drained", engine.pool.blocks_in_use == 0,
                 f"blocks in use {engine.pool.blocks_in_use}, cached "
                 f"{engine.pool.blocks_cached}")

    # the engine's own jitted step, lowered at its serving shapes
    tokens = jnp.zeros((SLOTS, engine.chunk), jnp.int32)
    vec = jnp.zeros((SLOTS,), jnp.int32)
    compiled = engine._step.lower(
        engine.params, {"tokens": tokens}, engine.caches, vec, vec,
        engine._tables_dev, tokens,
        jnp.zeros((engine.step_rows,), jnp.int32)).compile()
    calls = tpu_kernel_calls(compiled.as_text())
    checks.check("serve: compiled step runs the kernels",
                 calls.get("tim_matmul_fused", 0) > 0
                 and calls.get("paged_attention", 0) > 0,
                 f"tpu_custom_call sites by kernel {calls}")
    jax.block_until_ready(engine.caches)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        log(f"chip_smoke: the repository's src/ is not beside this "
            f"script ({e}); run it from a checkout")
        return 2
    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: needs a TPU, and JAX found {dev.platform!r} "
            f"devices {devices}; nothing was run")
        return 1
    use_compile_cache()
    log(f"[device] {devices}")
    log(f"[device] platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(devices)}")
    log(f"[versions] jax {jax.__version__}, jaxlib "
        f"{importlib.metadata.version('jaxlib')}, libtpu "
        f"{importlib.metadata.version('libtpu')}, python "
        f"{sys.version.split()[0]}")

    from repro.configs import get_config
    from repro.serve.engine import init_serving

    checks = Checks()
    rng = np.random.default_rng(args.seed)
    cfg = tim_cfg(get_config("chatglm3-6b"), "auto")
    with smoke_timer("kernel phase"):
        kernel_phase(checks, cfg, rng)

    with smoke_timer("build chatglm3-6b serving params, period by period"):
        params = init_serving(cfg, jax.random.PRNGKey(args.seed))
        jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"[model] {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; serving params "
        f"{nbytes / 2 ** 30:.2f} GiB")

    with smoke_timer("logits phase"):
        logits_phase(checks, params, cfg, rng)
    with smoke_timer("serve phase"):
        serve_phase(checks, params, cfg, rng)

    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[device] peak memory in use "
            f"{stats['peak_bytes_in_use'] / 2 ** 30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2 ** 30:.2f} GiB")
    if checks.failed:
        log(f"chip_smoke: {len(checks.failed)} check(s) failed: "
            f"{checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
