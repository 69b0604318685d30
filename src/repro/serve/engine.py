"""Serving engine: ternarized weights, token-budget continuous batching.

``ternarize_model`` converts trained (or random) master weights into
TiM serving form — every TernaryDense weight becomes int8 codes (+
optional 2-bit packing), exactly what the paper's tiles store.  Ternary
matmuls dispatch through kernels/ops with ``policy.fused=True`` by
default, so asymmetric (two-phase) and bit-serial layers execute as a
*single* kernel launch per matmul — one HBM weight stream instead of
2–4 (``weight_stream_report`` quantifies the saving for a converted
model).

The engine itself is a chunked-prefill continuous-batching scheduler
(the Sarathi / vLLM discipline, single-host version) built around ONE
jitted step function of fixed shape:

  unified_step : tokens (slots, chunk), per-slot cache_len write
                 offsets, per-slot n_new valid counts, per-slot block
                 tables (slots, max_blocks), slot_map (slots, chunk)
              -> next-token logits (slots, vocab), updated caches

Every engine iteration fills that fixed token grid with a mix of work:
each actively *decoding* slot contributes its 1 next token, and slots
still *prefilling* stream their prompt through the shared cache in
up-to-``chunk``-token slices.  A ``token_budget`` caps the real
(non-padding) tokens scheduled per iteration — decodes are always
scheduled first (admission and prefill never stall a running decode),
the leftover budget goes to prefill chunks.  Because prefill is
incremental, arbitrarily long prompts (up to ``max_len``) are
admissible, there is no per-bucket jit cache, no per-request mini
cache, and no prefill-sized latency spike for running decodes.

The KV cache is **block-paged** (serve/block_pool): one global
(num_blocks, block_size, ...) pool per layer-period instead of a
per-slot (slots, max_len, ...) slab.  Each slot's logical positions
resolve through a host-side block table; writes target physical
``block * block_size + offset`` positions via a per-step ``slot_map``.
Paging buys **cross-request prefix reuse**: at admission the new
prompt's full blocks are chain-hashed and any block an earlier request
already pushed through the cache is re-referenced instead of
recomputed — the prompt cursor jumps to the first non-shared token
(capped at plen - 1 so the last token always produces logits), and a
partially-filled tail block match is deep-copied (copy-on-write)
before the newcomer writes into it.  This is the paper's in-memory
amortization discipline applied to activations: one KV write serves
every request that shares the prefix, exactly as one TiM weight load
serves the whole ternary VMM.

Undersized pools are survivable (docs/serving.md §preemption): when
``BlockPool.try_allocate`` comes up empty the scheduler preempts the
youngest prefilling slot (decode requesters may fall back to decoding
victims), swapping its exclusively-owned blocks to a host-side numpy
arena or dropping them for recompute — whichever the roofline
crossover estimates cheaper — and resumes the request from the queue
front with bit-identical output (chunked recompute of the same token
history is exact; swap restores exact bytes).

All scheduler state (slot occupancy, lengths, prompt cursors, block
tables, refcounts, hashes) lives host-side in numpy: a step issues NO
device->host sync beyond the one explicit fetch of the sampled tokens
(see ``d2h_fetches``; swap d2h fetches are counted separately in
``swap_d2h_fetches``).

This is what the paper's throughput-per-watt story needs above the
fused Pallas kernels: decode steps are weight-stream-bound, so the
extra grid columns that carry prefill chunks ride the same single
weight stream the decode batch already pays for — and shared-prefix
admission skips the prefill FLOPs entirely.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.configs.base import ArchConfig
from repro.models import transformer as tfm
from repro.nn.linear import TernaryPolicy
from repro.serve.block_pool import (ROOT_HASH, BlockPool, chain_hash,
                                    default_num_blocks)
from repro.sim.chip import chip_peaks


# ---------------------------------------------------------------------------
# weight conversion (QAT/fp32 master -> TiM codes)
# ---------------------------------------------------------------------------

_TERNARY_LAYER_KEYS = {"q", "k", "v", "o", "gate", "up", "down", "z_proj",
                       "x_proj", "bc_proj", "dt_proj", "out_proj"}


def ternarize_model(params: Dict[str, Any], cfg: ArchConfig
                    ) -> Dict[str, Any]:
    """Walk the param tree; convert every ternary-dense subtree into
    serving codes.  MoE expert stacks ternarize per expert (axis 1 is
    the contraction dim of each (E, d_in, d_out) stack)."""
    pol = cfg.ternary
    if not pol.enabled:
        return params

    def convert(tree, path=()):
        if isinstance(tree, dict):
            if "w" in tree and hasattr(tree["w"], "ndim") \
                    and tree["w"].ndim >= 2 \
                    and (path and path[-1] in _TERNARY_LAYER_KEYS):
                new = dict(tree)
                new["w"] = _ternarize_stack(tree["w"], pol)
                new.pop("wp", None)  # learned TTQ scales folded below
                new.pop("wn", None)
                if "wp" in tree:
                    from repro.core.ternary import TernaryScales, ternarize
                    # per-layer threshold (match QAT, which quantizes
                    # each scan-sliced (K, N) with a per-tensor stat):
                    # reduce over the last two dims of the stack
                    w_ = tree["w"].astype(jnp.bfloat16)
                    q, _ = ternarize(w_, "unweighted",
                                     axis=(w_.ndim - 2, w_.ndim - 1))
                    new["w"] = _pack_maybe(
                        q, TernaryScales(jnp.abs(tree["wp"]),
                                         jnp.abs(tree["wn"]), False),
                        tree["w"].shape[-2], pol)
                return new
            return {k: convert(v, path + (k,)) for k, v in tree.items()}
        return tree

    out = convert(params)

    # MoE expert stacks: (E, d_in, d_out) leaves named gate/up/down under
    # an 'ffn' that has a router
    def convert_moe(tree):
        if isinstance(tree, dict):
            if "router" in tree:
                new = dict(tree)
                for k in ("gate", "up", "down"):
                    if k in tree and hasattr(tree[k], "ndim") \
                            and tree[k].ndim >= 3:
                        new[k] = _ternarize_stack(tree[k], pol)
                return new
            return {k: convert_moe(v) for k, v in tree.items()}
        return tree

    return convert_moe(out)


def _ternarize_stack(w, pol: TernaryPolicy):
    """(Possibly stacked) weights (..., d_in, d_out) -> TernaryWeight
    with per-(stack, out_channel) scales; optional 2-bit packing.

    Stats are computed on the bf16-cast master — the SAME view the QAT
    forward pass quantizes (nn/linear._quantize_master) — so serving
    codes match training bit-for-bit.
    """
    import jax.numpy as jnp
    from repro.core.ternary import ternarize
    q, scales = ternarize(w.astype(jnp.bfloat16), pol.encoding,
                          axis=w.ndim - 2)
    return _pack_maybe(q, scales, w.shape[-2], pol)


def _pack_maybe(q, scales, k_dim: int, pol: TernaryPolicy):
    from repro.core.packing import CODES_PER_BYTE, pack2b
    from repro.core.weights import TernaryWeight
    if not pol.pack:
        return TernaryWeight(q, scales, False, k_dim)
    ax = q.ndim - 2
    pad = (-k_dim) % CODES_PER_BYTE
    if pad:
        widths = [(0, 0)] * q.ndim
        widths[ax] = (0, pad)
        q = jnp.pad(q, widths)
    return TernaryWeight(pack2b(q, axis=ax), scales, True, k_dim)


@functools.partial(jax.jit, donate_argnums=0)
def _put_period(stack, one, i):
    return jax.tree_util.tree_map(lambda s, x: s.at[i].set(x), stack, one)


def init_serving(cfg: ArchConfig, key: jax.Array) -> Dict[str, Any]:
    """Serving params from a seed without the whole fp32 master tree:
    ``ternarize_model(tfm.init(cfg, key), cfg)``, built one period at a
    time.  Each period's masters become codes before the next period's
    exist, and the codes land in a preallocated stack (donated, so no
    second copy), so the device holds the codes, the embedding/head and
    one period of masters at once."""
    if not cfg.ternary.enabled:
        return tfm.init(cfg, key)
    period_codes = jax.jit(
        lambda kp: ternarize_model(tfm.init_period(cfg, kp), cfg))
    layers = None
    for i in range(cfg.n_periods):
        codes = period_codes(tfm.period_key(key, i))
        if layers is None:
            layers = jax.tree_util.tree_map(
                lambda x: jnp.zeros((cfg.n_periods,) + x.shape, x.dtype),
                codes)
        layers = _put_period(layers, codes, i)
        del codes
    return tfm.init(cfg, key, layers=layers)


def weight_stream_report(params: Dict[str, Any], cfg: ArchConfig,
                         decode_batch: int = 1) -> Dict[str, int]:
    """Aggregate HBM weight-byte traffic for one forward pass.

    Walks the converted param tree and sums, over every TernaryWeight
    leaf, the analytic per-matmul weight stream (kernels/ops.
    weight_stream_stats) for the fused single-launch route vs the
    historical multi-launch route.  The ratio is the serving-side HBM
    win of the fused kernels: 2x on two-phase asymmetric layers, bits x
    on bit-serial ones — any ``act_mode='int<bits>'``, e.g. 2x for int2
    and 4x for int4 (2 * bits x when the weights are also asymmetric,
    since each plane historically paid both phases) — and 1x for
    weight-only serving, which never launches a TiM kernel.
    """
    from repro.core.weights import TernaryWeight
    from repro.kernels.ops import weight_stream_stats

    pol = cfg.ternary
    # weight-only serving (act_mode 'none') never runs a TiM launch:
    # the dense matmul streams W exactly once either way
    bits = pol.act_bits
    tim_serving = pol.act_mode == "ternary" or bits is not None
    fused_bytes = unfused_bytes = resident = 0

    def visit(tree):
        nonlocal fused_bytes, unfused_bytes, resident
        if isinstance(tree, TernaryWeight):
            resident += tree.nbytes_hbm
            f = weight_stream_stats(decode_batch, tree, None, bits=bits,
                                    fused=True)
            u = weight_stream_stats(decode_batch, tree, None, bits=bits,
                                    fused=False) if tim_serving else f
            fused_bytes += f["weight_bytes_streamed"]
            unfused_bytes += u["weight_bytes_streamed"]
        elif isinstance(tree, dict):
            for v in tree.values():
                visit(v)

    visit(params)
    return {
        "weight_bytes_resident": resident,
        "weight_bytes_streamed_fused": fused_bytes,
        "weight_bytes_streamed_unfused": unfused_bytes,
    }


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ArchConfig):
    """Whole-prompt batch prefill (dry-run prefill cells / references)."""
    def prefill_step(params, batch, caches):
        b = next(iter(batch.values())).shape[0]
        hidden, caches, _ = tfm.forward(
            params, cfg, batch, mode="prefill", caches=caches,
            cache_len=jnp.zeros((b,), jnp.int32))
        lg = tfm.logits(params, cfg, hidden[:, -1:])
        return lg[:, 0], caches
    return prefill_step


def make_decode_step(cfg: ArchConfig):
    """One-token decode (the unified step's chunk == 1 special case;
    kept for the dry-run decode cells)."""
    def decode_step(params, batch, caches, cache_len):
        hidden, caches, _ = tfm.forward(
            params, cfg, batch, mode="decode", caches=caches,
            cache_len=cache_len)
        lg = tfm.logits(params, cfg, hidden[:, -1:])
        return lg[:, 0], caches
    return decode_step


def make_unified_step(cfg: ArchConfig):
    """The contiguous-cache unified step: a fixed (slots, chunk) token
    grid mixing decode tokens (n_new == 1) and prefill chunks (n_new in
    [0, chunk]), each slot appending at its own ``cache_len`` offset
    into the shared batch cache.  Returns per-slot logits at each
    slot's last valid token (n_new[b] - 1).  (Kept as the unpaged
    reference / dry-run shape; the engine itself runs the paged step.)
    """
    def unified_step(params, batch, caches, cache_len, n_new):
        hidden, caches, _ = tfm.forward(
            params, cfg, batch, mode="mixed", caches=caches,
            cache_len=cache_len, n_new=n_new)
        last = jnp.take_along_axis(
            hidden, jnp.maximum(n_new - 1, 0)[:, None, None], axis=1)
        lg = tfm.logits(params, cfg, last)
        return lg[:, 0], caches
    return unified_step


def make_paged_unified_step(cfg: ArchConfig):
    """THE engine step: the unified mixed prefill/decode step against a
    block-paged KV pool.  ``block_tables`` (slots, max_blocks) resolves
    logical reads; ``slot_map`` (slots, chunk) gives each new token's
    physical write position (block * block_size + offset)."""
    def paged_step(params, batch, caches, cache_len, n_new,
                   block_tables, slot_map):
        hidden, caches, _ = tfm.forward(
            params, cfg, batch, mode="mixed", caches=caches,
            cache_len=cache_len, n_new=n_new,
            block_tables=block_tables, slot_map=slot_map)
        last = jnp.take_along_axis(
            hidden, jnp.maximum(n_new - 1, 0)[:, None, None], axis=1)
        lg = tfm.logits(params, cfg, last)
        return lg[:, 0], caches
    return paged_step


def make_packed_unified_step(cfg: ArchConfig):
    """The token-packed engine step: the unified mixed prefill/decode
    step expressed over a flat ``(total_tokens, 1)`` buffer instead of
    the padded ``(slots, chunk)`` grid.

    ``positions``/``n_new`` are per-TOKEN (T,) arrays (the token's
    cache write offset and its 1/0 real-or-padding flag), ``seg_ids``
    (T,) names each token's slot, ``slot_map`` (T, 1) its physical
    write position, and ``last_idx`` (slots,) the flat index of each
    slot's LAST scheduled token — the step gathers those rows
    device-side so the returned logits keep the padded step's
    (slots, vocab) shape and the host bookkeeping (one d2h fetch of
    ``slots`` sampled tokens) is unchanged.  Rows of slots that
    scheduled nothing point at index 0; the host ignores them.

    Per-token math is the padded grid's exactly (docs/serving.md
    §token-packed), so greedy outputs are token-for-token identical —
    the padded step stays on as the parity oracle.
    """
    def packed_step(params, batch, caches, positions, n_new, seg_ids,
                    block_tables, slot_map, last_idx):
        fwd_batch = {"tokens": batch["tokens"]}
        if "media" in batch:
            # cross-attention needs per-ROW media: gather each token's
            # slot media device-side (padding rows read slot 0 and are
            # discarded by the last_idx gather)
            nslots = block_tables.shape[0]
            fwd_batch["media"] = batch["media"][
                jnp.clip(seg_ids, 0, nslots - 1)]
        hidden, caches, _ = tfm.forward(
            params, cfg, fwd_batch, mode="mixed", caches=caches,
            cache_len=positions, n_new=n_new,
            block_tables=block_tables, slot_map=slot_map,
            seg_ids=seg_ids)
        last = hidden[last_idx]                     # (slots, 1, d)
        lg = tfm.logits(params, cfg, last)
        return lg[:, 0], caches
    return packed_step


# ---------------------------------------------------------------------------
# speculative decoding steps (docs/serving.md §speculative)
# ---------------------------------------------------------------------------

def make_draft_step(cfg: ArchConfig):
    """The speculative DRAFT step: the paged unified step at chunk == 1,
    built from the cheap-encoding draft config (the target's weights
    read through ``TernaryPolicy.draft`` — e.g. int2 bit-serial
    activations against an int4 target).  Proposals are the masked
    greedy argmax, fused device-side so the host fetches one token per
    slot per draft pass: a DETERMINISTIC proposal distribution
    (q = delta at the argmax), which reduces exact rejection sampling
    to a plain accept-with-probability-p(d) test in the verify step."""
    def draft_step(params, batch, caches, cache_len, n_new,
                   block_tables, slot_map, mask):
        hidden, caches, _ = tfm.forward(
            params, cfg, batch, mode="mixed", caches=caches,
            cache_len=cache_len, n_new=n_new,
            block_tables=block_tables, slot_map=slot_map)
        lg = tfm.logits(params, cfg, hidden[:, :1])[:, 0]
        toks = greedy_token(apply_token_masks(lg, mask))
        return toks, caches
    return draft_step


def make_paged_spec_step(cfg: ArchConfig):
    """The padded VERIFY step: identical to ``make_paged_unified_step``
    except it returns the logits of EVERY grid position — row j of a
    decode slot's (slots, chunk) lane predicts position cache_len+j+1,
    which is exactly what acceptance needs to judge draft token j+1.
    Draft tokens ride the grid as ordinary extra ``n_new`` (the mixed
    step already supports multi-token decode rows), and the verify
    forward overwrites the draft pass's cheap-encoding KV with target
    KV at every scheduled position."""
    def paged_spec_step(params, batch, caches, cache_len, n_new,
                        block_tables, slot_map):
        hidden, caches, _ = tfm.forward(
            params, cfg, batch, mode="mixed", caches=caches,
            cache_len=cache_len, n_new=n_new,
            block_tables=block_tables, slot_map=slot_map)
        lg = tfm.logits(params, cfg, hidden)     # (slots, chunk, vocab)
        return lg, caches
    return paged_spec_step


def make_packed_spec_step(cfg: ArchConfig):
    """The token-packed VERIFY step: flat layout, all-position logits.
    ``row_idx`` (slots, chunk) holds the flat index of each slot's j-th
    scheduled token (rows past ``n_new`` point at 0 and are never read)
    so the gathered logits keep the padded verify step's
    (slots, chunk, vocab) shape and the SAME accept function serves
    both layouts — the parity contract extends to speculative runs."""
    def packed_spec_step(params, batch, caches, positions, n_new,
                         seg_ids, block_tables, slot_map, row_idx):
        hidden, caches, _ = tfm.forward(
            params, cfg, {"tokens": batch["tokens"]}, mode="mixed",
            caches=caches, cache_len=positions, n_new=n_new,
            block_tables=block_tables, slot_map=slot_map,
            seg_ids=seg_ids)
        s, c = row_idx.shape
        rows = hidden[row_idx.reshape(-1), 0]              # (s*c, d)
        lg = tfm.logits(params, cfg, rows.reshape(s, c, -1))
        return lg, caches
    return packed_spec_step


def copy_kv_block(caches, src, dst):
    """Copy one physical KV block (every layer-period, K and V and any
    scales) — the copy-on-write primitive behind partial-tail prefix
    sharing.  Pure function of the cache pytree; jitted at module scope
    (``_copy_kv_block_jit``) with donation so it is an in-place
    dynamic-update on device and the compile is shared by every engine
    in the process."""
    def walk(tree):
        if isinstance(tree, dict):
            return {k: (v.at[:, dst].set(v[:, src])
                        if k in ("k", "v", "k_scale", "v_scale")
                        and hasattr(v, "at") else walk(v))
                    for k, v in tree.items()}
        return tree
    return walk(caches)


_copy_kv_block_jit = jax.jit(copy_kv_block, donate_argnums=(0,))


def fetch_kv_blocks(caches, bids: np.ndarray) -> Dict[str, Any]:
    """Device -> host copy of the given physical KV blocks (every
    layer-period, K/V and any scales): the swap-OUT half of preemption.
    Returns a nested dict mirroring the cache pytree whose KV leaves
    are (periods, len(bids), block_size, ...) numpy arrays."""
    idx = jnp.asarray(bids, jnp.int32)

    def walk(tree):
        if isinstance(tree, dict):
            # timcheck: allow[d2h] accounted swap-out fetch (swap_d2h_fetches)
            return {k: (np.asarray(v[:, idx])
                        if k in ("k", "v", "k_scale", "v_scale")
                        and hasattr(v, "at") else walk(v))
                    for k, v in tree.items() if isinstance(v, dict)
                    or k in ("k", "v", "k_scale", "v_scale")}
        return tree
    return walk(caches)


def write_kv_block(caches, dst, values):
    """Host -> device restore of ONE physical KV block from a
    ``fetch_kv_blocks``-shaped values tree (sliced to one block): the
    swap-IN half.  Jitted at module scope with donation
    (``_write_kv_block_jit``) so restores are in-place on device."""
    def walk(tree, vals):
        if isinstance(tree, dict):
            return {k: (v.at[:, dst].set(vals[k].astype(v.dtype))
                        if k in ("k", "v", "k_scale", "v_scale")
                        and hasattr(v, "at") else walk(v, vals.get(k, {})))
                    for k, v in tree.items()}
        return tree
    return walk(caches, values)


_write_kv_block_jit = jax.jit(write_kv_block, donate_argnums=(0,))

# Swap-vs-recompute crossover (the roofline estimate): recompute
# replays the dropped tokens through the model at the chip's peak
# FLOP/s; swap round-trips the blocks' KV bytes over the host link.
# Both come from repro.sim.chip's row for the device the engine runs
# on — the ONE home shared with benchmarks/roofline.py.

# row-wise update of the device-resident block-table mirror (module
# scope: one compile per table shape, shared across engines)
_set_table_row_jit = jax.jit(lambda t, i, r: t.at[i].set(r),
                             donate_argnums=(0,))


def greedy_token(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_token(logits: jax.Array, key=None, temperature: float = 1.0
                 ) -> jax.Array:
    """Sample (or argmax) the next token.  Key consumption is EXPLICIT
    and identical across code paths: greedy routing (``temperature <=
    0``) takes ``key=None`` and consumes nothing, sampling requires a
    key — passing a key that would be silently dropped (the old
    callsite split the engine stream per step even on the greedy path)
    raises instead of desynchronizing the caller's stream."""
    if temperature <= 0:
        if key is not None:
            raise ValueError(
                "sample_token with temperature <= 0 is greedy and "
                "consumes no PRNG key; pass key=None — key consumption "
                "must be explicit and identical across code paths")
        return greedy_token(logits)
    if key is None:
        raise ValueError(
            "sample_token with temperature > 0 draws from the PRNG "
            "stream and requires a key")
    return jax.random.categorical(
        key, logits.astype(jnp.float32) / temperature, axis=-1
    ).astype(jnp.int32)


def derive_sample_key(base_key, uid, sample_index, token_index):
    """The per-request counter-based PRNG stream (the ISSUE-9 headline
    bugfix): every sampled token draws from
    ``fold_in(fold_in(fold_in(base, uid), sample_index), token_index)``
    — a pure function of request identity and position, NOT of slot
    occupancy, step count, or scheduling order.  Sampled rollouts are
    therefore bit-replayable: the same seed reproduces the same
    continuation whether the request runs alone or in a full batch,
    across preemption/resume, and across the padded and token-packed
    engines (which produce bit-identical logits)."""
    k = jax.random.fold_in(base_key, uid)
    k = jax.random.fold_in(k, sample_index)
    return jax.random.fold_in(k, token_index)


def apply_token_masks(logits: jax.Array, mask: jax.Array) -> jax.Array:
    """Guided decoding: constrain per-slot logits to a COMPACT
    allowed-token buffer.  ``mask`` is (slots, mask_width) int32 of
    allowed token ids padded with -1; a row of all -1 means
    unconstrained.  Nothing of shape (slots, vocab) is ever shipped
    host->device — the scatter to vocab width happens device-side."""
    vocab = logits.shape[-1]

    def row(lg_row, mask_row):
        valid = mask_row >= 0
        ids = jnp.clip(mask_row, 0, vocab - 1)
        # .max accumulates safely over the duplicate index the clip of
        # the -1 padding creates (its False can never hide a True)
        keep = jnp.zeros((vocab,), bool).at[ids].max(valid)
        masked = jnp.where(keep, lg_row, jnp.float32(-1e30))
        return jnp.where(valid.any(), masked, lg_row)

    return jax.vmap(row)(logits.astype(jnp.float32), mask)


def make_sample_fn(temperature: float, topk: int):
    """Build the jitted per-slot sampling tail: compact-mask
    application, per-request ``derive_sample_key`` streams, categorical
    (or argmax) selection, and — when ``topk`` > 0 — the top-k
    log-prob candidates the host-side beam bookkeeping consumes.
    Everything runs device-side off the step's (slots, vocab) logits;
    the host fetches the result in the step's ONE accounted d2h."""
    def sample_fn(lg, base_key, ids, mask):
        lgm = apply_token_masks(lg, mask)
        if temperature <= 0:
            toks = sample_token(lgm, None, temperature)
        else:
            keys = jax.vmap(derive_sample_key,
                            in_axes=(None, 0, 0, 0))(
                base_key, ids[:, 0], ids[:, 1], ids[:, 2])
            toks = jax.vmap(
                lambda k, l: sample_token(l, k, temperature))(keys, lgm)
        if topk:
            lp = jax.nn.log_softmax(lgm, axis=-1)
            cand_lp, cand_ids = jax.lax.top_k(lp, topk)
            return toks, cand_ids.astype(jnp.int32), cand_lp
        return toks
    return sample_fn


# one compiled sampler per (temperature, topk) shared across every
# engine in the process (same discipline as _copy_kv_block_jit)
_SAMPLER_JITS: Dict[Tuple[float, int], Any] = {}


def _get_sampler(temperature: float, topk: int):
    key = (float(temperature), int(topk))
    if key not in _SAMPLER_JITS:
        _SAMPLER_JITS[key] = jax.jit(make_sample_fn(*key))
    return _SAMPLER_JITS[key]


# sub-stream tags for the acceptance test and the rejection resample:
# folded onto the position's derived key so the BONUS draw (the j == k
# emission) consumes the RAW derive_sample_key(base, uid, si, t0+j) —
# which makes a spec engine at k == 0 bit-identical to the non-spec
# sampled path, position by position
_SPEC_ACCEPT_TAG = 1
_SPEC_RESAMPLE_TAG = 2


def make_spec_accept_fn(temperature: float, chunk: int):
    """Device-side speculative acceptance over the verify step's
    all-position logits (docs/serving.md §speculative).

    Per slot: grid row ``start + j`` scores emission j (token_index
    ``ids[:, 2] + j``); draft token j+1 sits at grid column
    ``start + j + 1``.  Greedy engines accept while the masked argmax
    chain reproduces the draft; sampled engines run EXACT rejection
    sampling against the deterministic draft proposal — accept d with
    probability p(d) (uniform from the ACCEPT sub-key), else draw the
    correction from p with d banned (renormalized, RESAMPLE sub-key),
    so the emitted marginal is exactly p.  The final emission (first
    rejection's correction or the all-accepted bonus) and every
    acceptance decision are keyed on the per-request counter streams:
    the same seed yields the same tokens whatever k, the layout, or
    the scheduling history.  Returns (emitted (slots, chunk), n_emit
    (slots,)); rows past n_emit are garbage the host never reads."""
    def accept_row(lg_row, tok_row, start, k, id3, mask_rows, base_key):
        vocab = lg_row.shape[-1]
        es, accs = [], []
        for j in range(chunk):
            lgm = apply_token_masks(
                lg_row[jnp.clip(start + j, 0, chunk - 1)][None],
                mask_rows[j][None])[0]
            d_next = tok_row[jnp.clip(start + j + 1, 0, chunk - 1)]
            in_draft = jnp.asarray(j) < k
            if temperature <= 0:
                e = jnp.argmax(lgm).astype(jnp.int32)
                acc = in_draft & (e == d_next)
            else:
                key = derive_sample_key(base_key, id3[0], id3[1],
                                        id3[2] + jnp.uint32(j))
                scaled = lgm / temperature
                u = jax.random.uniform(
                    jax.random.fold_in(key, _SPEC_ACCEPT_TAG))
                acc = in_draft & (u < jax.nn.softmax(scaled)[d_next])
                banned = jnp.where(jnp.arange(vocab) == d_next,
                                   -jnp.inf, lgm)
                resample = jax.random.categorical(
                    jax.random.fold_in(key, _SPEC_RESAMPLE_TAG),
                    banned / temperature).astype(jnp.int32)
                bonus = jax.random.categorical(key, scaled) \
                    .astype(jnp.int32)
                e = jnp.where(acc, d_next,
                              jnp.where(in_draft, resample, bonus))
            es.append(e)
            accs.append(acc)
        cont = jnp.stack(accs).astype(jnp.int32)
        a = jnp.cumprod(cont).sum()          # leading accepted run
        return jnp.stack(es), (a + 1).astype(jnp.int32)

    def accept_fn(lg, toks, start, n_draft, base_key, ids, masks):
        return jax.vmap(accept_row, in_axes=(0, 0, 0, 0, 0, 0, None))(
            lg, toks, start, n_draft, ids, masks, base_key)
    return accept_fn


# module-scope jit caches for the speculative step/accept functions —
# the _copy_kv_block_jit discipline: keyed on the (hashable, frozen)
# config so every engine in the process shares one compile per shape
_DRAFT_STEP_JITS: Dict[Any, Any] = {}
_SPEC_STEP_JITS: Dict[Tuple[Any, bool], Any] = {}
_SPEC_ACCEPT_JITS: Dict[Tuple[float, int], Any] = {}


def _get_draft_step(cfg: ArchConfig):
    if cfg not in _DRAFT_STEP_JITS:
        _DRAFT_STEP_JITS[cfg] = jax.jit(make_draft_step(cfg),
                                        donate_argnums=(2,))
    return _DRAFT_STEP_JITS[cfg]


def _get_spec_step(cfg: ArchConfig, packed: bool):
    key = (cfg, bool(packed))
    if key not in _SPEC_STEP_JITS:
        inner = make_packed_spec_step(cfg) if packed \
            else make_paged_spec_step(cfg)
        _SPEC_STEP_JITS[key] = jax.jit(inner, donate_argnums=(2,))
    return _SPEC_STEP_JITS[key]


def _get_spec_accept(temperature: float, chunk: int):
    key = (float(temperature), int(chunk))
    if key not in _SPEC_ACCEPT_JITS:
        _SPEC_ACCEPT_JITS[key] = jax.jit(make_spec_accept_fn(*key))
    return _SPEC_ACCEPT_JITS[key]


# ---------------------------------------------------------------------------
# token-budget continuous-batching scheduler
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (len,) int32
    max_new_tokens: int
    media: Optional[np.ndarray] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    prefix_hit_tokens: int = 0   # prompt tokens served from shared blocks
    # request finished because the cache filled (cache_len hit max_len)
    # BEFORE max_new_tokens was produced — a shortened answer the caller
    # previously could not distinguish from a complete one
    truncated: bool = False
    # lifecycle instrumentation (engine-step indices, the engine's
    # virtual clock): when the request was submitted and at which step
    # each output token was emitted — token_steps[j] is the step index
    # that produced out_tokens[j] (the two lists stay aligned, across
    # preemption/resume too).  serve/metrics.py derives TTFT/TPOT from
    # these; -1 / empty until the events happen.
    submit_step: int = -1
    token_steps: List[int] = dataclasses.field(default_factory=list)
    # the same lifecycle on the host clock (time.perf_counter(), the
    # clock a caller stamps delivered tokens with): submission, first
    # admission to a slot (a resume after preemption keeps it) and the
    # first output token; None until the event happens
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    # parallel sampling: submit with n > 1 and the engine expands the
    # request into n sibling sequences sharing the same uid (and all
    # full prompt blocks, by refcount — ONE prefill serves all n).
    # ``sample_mode='independent'`` draws each sibling from its own
    # counter-based PRNG stream (keyed by sample_index);
    # ``sample_mode='beam'`` runs width-n beam search with host-side
    # bookkeeping over the same CoW fork mechanism (cum_logprob is the
    # running hypothesis score).  The submitted parent never enters the
    # queue itself — its expanded children are linked in ``siblings``
    # and finish independently (per-sibling out_tokens / token_steps /
    # truncated).
    n: int = 1
    sample_mode: str = "independent"
    sample_index: int = 0
    siblings: Optional[List["Request"]] = None
    cum_logprob: float = 0.0
    # guided decoding: callback(out_tokens) -> allowed token ids for
    # the NEXT sampled position (None/absent = unconstrained).  Applied
    # device-side via a compact (slots, mask_width) buffer — never a
    # (slots, vocab) host->device ship.
    allowed_tokens: Optional[Callable[[List[int]], Optional[Sequence[int]]]] \
        = None

    @property
    def first_token_step(self) -> int:
        """Step index of the first emitted token (-1 before it exists)."""
        return self.token_steps[0] if self.token_steps else -1


def _stamp_first(reqs: List[Request], now: float) -> None:
    """Stamp ``t_first`` on each request that got its first token."""
    for r in reqs:
        if r.t_first is None and r.out_tokens:
            r.t_first = now


class ServeEngine:
    """Chunked-prefill continuous batching over a block-paged KV pool.

    One jitted step of fixed shape (``batch_slots``, ``chunk``) serves
    both prefill and decode: the scheduler fills the grid each
    iteration with 1 token per decoding slot plus up-to-``chunk``-token
    prompt slices for slots still prefilling, bounded by
    ``token_budget`` real tokens per iteration (decodes first — they
    never stall; leftover budget streams prefills).

    The KV cache is a global pool of ``num_blocks`` x ``block_size``
    token blocks (serve/block_pool) addressed through per-slot block
    tables.  With ``prefix_reuse`` (default 'auto': on for pure
    attention stacks without media — recurrent SSM state and
    media-conditioned hidden states make token-hash sharing unsound),
    admission chain-hashes the prompt's full blocks and re-references
    any block already resident; the prompt cursor jumps to the first
    non-shared token.  A partial tail-block match (including the
    degenerate whole-prompt hit, which must still compute its last
    token for logits) is served copy-on-write: the shared block is
    deep-copied into a freshly owned block before this slot's first
    write.  ``prefix_hit_tokens`` / ``scheduled_prefill_tokens`` /
    ``stats()`` expose the accounting; ``validate()`` asserts the
    pool/table invariants (used by the property suite after every
    step).

    ``oversize`` controls prompts longer than ``max_len`` (chunked
    prefill admits anything that fits the cache; a prompt of exactly
    ``max_len`` yields exactly one token): ``'error'`` rejects them at
    ``submit`` with a ValueError, ``'truncate'`` keeps the most recent
    ``max_len`` tokens.

    ``preempt`` picks the resume policy for pools smaller than the
    full-batch floor, where allocation can fail: ``'swap'`` round-trips
    the victim's owned blocks through a host arena (bit-identical
    restore), ``'recompute'`` replays the token history (bit-identical
    by the chunked-parity guarantee), ``'auto'`` chooses per victim by
    the roofline crossover.  Victims are the youngest prefilling slots
    first; preempted requests resume from the queue front and always
    complete (tests/test_preemption.py and the small-pool property
    profile).  Recurrent/media stacks always recompute.  ``'none'``
    disables preemption entirely — allocation failures shrink or skip
    the requester's chunk, which on an undersized pool can LIVELOCK;
    ``run_until_done`` detects the no-progress spin and raises instead
    of burning host CPU.

    Per-request lifecycle is instrumented on the engine's virtual
    clock (``iters``, +1 per ``step()`` call): ``Request.submit_step``
    and ``Request.token_steps`` record when the request arrived and at
    which step each output token was emitted — serve/metrics.py turns
    these into TTFT/TPOT/goodput digests; ``Request.t_submit`` /
    ``t_admit`` / ``t_first`` stamp the same lifecycle on the host
    clock, each phase of ``step()`` runs under a profiler span, and
    ``stats()`` exposes the cumulative counters (plus occupancy
    gauges) a per-step telemetry stream diffs (docs/serving.md
    §telemetry).

    Scheduler state is host-side numpy; the only device->host transfer
    per step is the explicit fetch of the sampled tokens
    (``d2h_fetches`` counts them, tests pin it to one per step).
    """

    def __init__(self, params, cfg: ArchConfig, batch_slots: int,
                 max_len: int, greedy: bool = True, seed: int = 0,
                 oversize: str = "error", chunk: int = 16,
                 token_budget: Optional[int] = None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefix_reuse: Any = "auto", preempt: str = "auto",
                 packed: bool = False, temperature: float = 1.0,
                 mask_width: int = 8, spec_k: int = 0,
                 draft_act_mode: str = "int2"):
        assert oversize in ("error", "truncate"), oversize
        assert chunk >= 1, chunk
        assert preempt in ("auto", "swap", "recompute", "none"), preempt
        self.params = params
        self.cfg = cfg
        self.slots = batch_slots
        self.max_len = max_len
        self.greedy = greedy
        self.oversize = oversize
        self.chunk = min(chunk, max_len)
        self.token_budget = (batch_slots + self.chunk
                             if token_budget is None else token_budget)
        assert self.token_budget >= 1, token_budget
        assert temperature > 0 or greedy, (
            "temperature <= 0 is spelled greedy=True", temperature)
        self.temperature = float(temperature)
        assert mask_width >= 1, mask_width
        self.mask_width = int(mask_width)
        # per-request counter-based PRNG: sampling derives every key as
        # fold_in(base, uid, sample_index, token_index) — no engine
        # stream state exists, so sampled outputs are independent of
        # slot occupancy, scheduling order, and preemption history
        self._base_key = jax.random.PRNGKey(seed)

        # NOT clamped to max_len: a block larger than the cache just
        # leaves its tail unused, whereas silently shrinking block_size
        # could break the attn_chunk_kv divisibility the caller chose
        self.block_size = max(1, block_size)
        self.max_blocks = -(-max_len // self.block_size)
        if num_blocks is None:
            # every slot can hold a full max_len sequence, plus one
            # spare block per slot so prefix-cached blocks survive a
            # little churn before eviction
            num_blocks = default_num_blocks(batch_slots, max_len,
                                            self.block_size)
        # Sizing regimes: at the default sizing (>= a full batch plus
        # one transient copy-on-write block per the PR-4 floor)
        # allocation can never fail.  SMALLER pools are now survivable
        # via preemption — the hard floor is one full sequence plus a
        # spare block, which guarantees a lone active slot always
        # completes (so preemption always converges; docs/serving.md
        # §preemption).
        assert num_blocks >= self.max_blocks + 1, (
            "pool must hold at least ceil(max_len / block_size) + 1 "
            "blocks: one full sequence plus a spare — below that even "
            "a single request cannot complete", num_blocks,
            self.max_blocks)
        self.preemptable = num_blocks < batch_slots * self.max_blocks + 1
        assert cfg.attn_chunk_kv % self.block_size == 0, (
            "block_size must divide attn_chunk_kv — paged attention "
            "chunks the scan in whole blocks, and bit-exact parity "
            "with the contiguous path needs identical chunk boundaries",
            cfg.attn_chunk_kv, self.block_size)
        reuse_sound = (all(s.mixer == "attn" for s in cfg.layout)
                       and not cfg.n_media_tokens)
        if prefix_reuse == "auto":
            prefix_reuse = reuse_sound
        elif prefix_reuse and not reuse_sound:
            raise ValueError(
                "prefix_reuse requires a pure-attention stack without "
                "media: recurrent SSM/conv state cannot jump over "
                "skipped tokens, and media-conditioned hidden states "
                "make token-only chain hashes unsound — construct with "
                "prefix_reuse='auto' (or False) for this architecture")
        self.prefix_reuse = bool(prefix_reuse)
        # swap restores KV blocks only: recurrent SSM/conv state cannot
        # be swapped at a mid-history cut (a partial resume would leave
        # state ahead of the restored cache), and media re-uploads are
        # already admission work — such stacks always recompute
        swap_sound = (all(s.mixer == "attn" for s in cfg.layout)
                      and not cfg.n_media_tokens)
        if preempt == "swap" and not swap_sound:
            raise ValueError(
                "preempt='swap' requires a pure-attention stack "
                "without media: recurrent SSM/conv state cannot be "
                "restored at a partial-coverage resume point — use "
                "preempt='auto' (or 'recompute') for this architecture")
        # 'none' disables preemption entirely (allocation failures just
        # shrink/skip the requester's chunk): the regime where an
        # undersized pool can genuinely LIVELOCK — run_until_done's
        # no-progress detector raises instead of spinning there
        self.preempt = preempt if (swap_sound or preempt == "none") \
            else "recompute"
        self.pool = BlockPool(num_blocks, self.block_size)

        self.caches = tfm.init_paged_caches(cfg, batch_slots, num_blocks,
                                            self.block_size)
        # host-side scheduler state: no device sync ever needed to
        # schedule, admit, or detect completion
        self.cache_len = np.zeros((batch_slots,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_prompt: List[Optional[np.ndarray]] = [None] * batch_slots
        self.slot_fill = np.zeros((batch_slots,), np.int64)  # prompt cursor
        self.block_tables = np.full((batch_slots, self.max_blocks), -1,
                                    np.int32)
        self.slot_nblocks = np.zeros((batch_slots,), np.int64)
        # full token history per slot (== what the cache holds, position
        # by position) and the chain digest per completed block — what
        # admission matches against and registration extends
        self.slot_hist: List[List[int]] = [[] for _ in range(batch_slots)]
        self.slot_chain: List[List[bytes]] = [[] for _ in range(batch_slots)]
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        # the engine's virtual clock: count of step() calls (no-op
        # iterations included) — the step index every lifecycle event
        # (submit/token emission) is stamped with
        self.iters = 0
        self.truncated_requests = 0
        self.d2h_fetches = 0
        self.n_step_compiles = 0
        self.prefix_hit_tokens = 0
        self.scheduled_prefill_tokens = 0
        self.scheduled_tokens = 0
        # _schedule() calls that left prompt tokens waiting because the
        # token budget ran out (a chunk-limited slice does not count)
        self.budget_full_steps = 0
        # device-grid rows actually launched (padded: slots*chunk per
        # step; packed: the power-of-two token bucket) — the
        # denominator of metrics.summarize()'s padding_efficiency
        self.grid_tokens = 0
        # finished-request partial-tail donations (satellite of the
        # token-packed PR): bid -> (chain tuple, tail-token tuple).
        # Each entry holds one pool reference so the block survives
        # release and future admissions can copy-on-write from it;
        # entries are dropped (oldest first) under pool pressure.
        self._tail_cache: Dict[int, Tuple[tuple, tuple]] = {}
        # preemption/swap state: admission order (victim choice is
        # youngest first), the host-side swap arena (uid -> saved KV
        # blocks + resume prompt), and the per-slot first-sample
        # suppression flag for resumed-mid-decode refills
        self._admit_seq = 0
        self.slot_seq = np.zeros((batch_slots,), np.int64)
        # keyed by (uid, sample_index): siblings share uid but preempt
        # and resume independently
        self._resume: Dict[Tuple[int, int], Dict[str, Any]] = {}
        self._skip_sample = np.zeros((batch_slots,), bool)
        self.preemptions = 0
        self.swapped_out_blocks = 0
        self.swapped_in_blocks = 0
        self.swapped_in_tokens = 0
        self.recompute_tokens = 0
        self.admitted_prompt_tokens = 0
        self.swap_d2h_fetches = 0
        # parallel sampling / guided decoding telemetry
        self.sibling_requests = 0    # sample_index>0 admissions
        self.beam_forks = 0          # beam hypothesis adoptions (CoW)
        self.masked_tokens = 0       # sampled positions with a mask row
        # speculative-decoding accounting (always present so the
        # telemetry registry sees one stable key set; all zero when
        # spec_k == 0): draft_tokens == accepted + rejected holds after
        # every step, and each verify emits its accepted run plus ONE
        # more token — the first rejection's correction, or the bonus
        # (counted in bonus_tokens) when every draft survived
        self.draft_tokens = 0
        self.accepted_tokens = 0
        self.rejected_tokens = 0
        self.bonus_tokens = 0
        self.draft_d2h_fetches = 0   # one per draft pass (k per step max)
        # live beam groups: uid -> the n sibling Requests (host-side
        # beam bookkeeping; removed when every sibling finishes)
        self._beam_groups: Dict[int, List[Request]] = {}
        # roofline crossover inputs: ~2*N FLOPs per recomputed token vs
        # a host-link round trip of the blocks' KV bytes (total, not
        # MoE-active, params — conservative toward swapping)
        self._n_params = sum(
            int(np.prod(l.shape)) for l in
            jax.tree_util.tree_leaves(params) if hasattr(l, "shape"))
        kv_bytes = sum(
            l.size * l.dtype.itemsize for l in
            jax.tree_util.tree_leaves(self.caches) if l.ndim >= 2
            and l.shape[1] == num_blocks)
        self._block_bytes = kv_bytes / max(num_blocks, 1)
        self._last_slot_map: Optional[np.ndarray] = None
        # device mirror of the block tables, updated ROW-wise when a
        # slot's table changes (admission / block allocation / release)
        # — decode steady state ships the small slot_map plus at most a
        # few (max_blocks,) rows, never the whole (slots, max_blocks)
        # table
        self._tables_dev = None
        self._dirty_slots: set = set(range(batch_slots))
        # per-slot media is constant for a request's lifetime: keep one
        # device-resident batch, re-uploaded only when admission changes
        # a slot (never in decode steady state)
        self._media_dev = None
        self._media_dirty = cfg.n_media_tokens > 0
        if cfg.n_media_tokens:
            self._media_host = np.zeros(
                (batch_slots, cfg.n_media_tokens, cfg.media_dim),
                np.float32)

        self.packed = bool(packed)
        # one step fn per layout; the wrapper signature is shared (the
        # layout-specific operands ride in *sched, after the donated
        # caches at position 2)
        inner = (make_packed_unified_step(cfg) if self.packed
                 else make_paged_unified_step(cfg))

        def _counted(params, batch, caches, *sched):
            # timcheck: allow[impure] trace-time shape-count telemetry
            self.n_step_compiles += 1      # trace-time: counts shapes
            return inner(params, batch, caches, *sched)

        self._step = jax.jit(_counted, donate_argnums=(2,))
        self._copy_step = _copy_kv_block_jit
        self._set_table_row = _set_table_row_jit
        self._write_block = _write_kv_block_jit

        # self-speculative decoding (docs/serving.md §speculative): a
        # draft pass over the SAME weights through the cheap encoding
        # proposes up to spec_k tokens per decoding slot; the target
        # verifies all k+1 positions in one mixed step.  Rejected
        # suffixes roll back by retreating cache_len and releasing the
        # over-allocated tail blocks — sound only for pure-attention
        # stacks (recurrent SSM/conv state advanced by rejected tokens
        # cannot rewind, and media-conditioned reuse is gated anyway).
        self.spec_k = int(spec_k)
        assert self.spec_k >= 0, spec_k
        self.draft_act_mode = draft_act_mode
        if self.spec_k:
            if not (all(s.mixer == "attn" for s in cfg.layout)
                    and not cfg.n_media_tokens):
                raise ValueError(
                    "spec_k > 0 requires a pure-attention stack "
                    "without media: a rejected draft suffix rolls back "
                    "by retreating cache_len, which cannot rewind "
                    "recurrent SSM/conv state — construct with "
                    "spec_k=0 for this architecture")
            self._draft_cfg = cfg.replace(
                ternary=cfg.ternary.draft(draft_act_mode))
            self._draft_step = _get_draft_step(self._draft_cfg)
            self._spec_step = _get_spec_step(cfg, self.packed)
            self._accept = _get_spec_accept(
                0.0 if greedy else self.temperature, self.chunk)

    def submit(self, req: Request):
        plen = len(req.prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if plen > self.max_len and self.oversize != "truncate":
            raise ValueError(
                f"prompt of {plen} tokens exceeds the engine's cache "
                f"capacity max_len={self.max_len}; resubmit a shorter "
                f"prompt or construct the engine with "
                f"oversize='truncate'")
        if req.sample_mode not in ("independent", "beam"):
            raise ValueError(f"unknown sample_mode {req.sample_mode!r}")
        if req.n < 1:
            raise ValueError(f"Request.n must be >= 1, got {req.n}")
        if req.sample_mode == "beam" and self.spec_k:
            raise ValueError(
                "speculative decoding (spec_k > 0) does not compose "
                "with beam search: beam expansion consumes per-slot "
                "top-k candidates, not an accept/reject chain — submit "
                "sample_mode='independent' or construct the engine "
                "with spec_k=0")
        if req.sample_mode == "beam":
            if self.greedy and req.n > 1:
                raise ValueError(
                    "beam search scores log-probs from the sampler — "
                    "construct the engine with greedy=False")
            if req.n > self.slots:
                raise ValueError(
                    f"beam width {req.n} exceeds batch_slots="
                    f"{self.slots}: every live hypothesis needs a slot "
                    f"for synchronized expansion")
        req.t_submit = time.perf_counter()   # siblings inherit it
        if req.n > 1:
            # expand into n sibling sequences sharing the uid; the
            # parent itself never enters the queue — callers read
            # results off req.siblings
            kids = [dataclasses.replace(
                req, sample_index=s, siblings=None,
                out_tokens=[], token_steps=[]) for s in range(req.n)]
            req.siblings = kids
            if req.sample_mode == "beam":
                self._beam_groups[req.uid] = kids
            for kid in kids:
                kid.submit_step = self.iters
                self.queue.append(kid)
            return
        req.submit_step = self.iters     # lifecycle: arrival stamp
        self.queue.append(req)

    def _active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def _reset_slot_state(self, slot: int):
        """Zero the slot's *recurrent* cache state (mamba conv/ssm).

        KV entries need no reset — attention masks everything past the
        slot's valid length and prefill overwrites from position 0 —
        but SSM blocks read their state unconditionally as h0, so a
        recycled slot would otherwise inherit the previous occupant's
        recurrence."""
        def walk(tree):
            if isinstance(tree, dict):
                return {k: (v.at[:, slot].set(0)
                            if k in ("conv", "ssm") and hasattr(v, "at")
                            else walk(v))
                        for k, v in tree.items()}
            return tree
        self.caches = walk(self.caches)

    # -- prefix matching ----------------------------------------------------

    def _match_full_blocks(self, tokens: np.ndarray):
        """Chain-hash the prompt's full blocks against the pool.
        Returns (matched_tokens, hit_bids, chain) with every hit block's
        refcount already bumped."""
        bs = self.block_size
        hits: List[int] = []
        chain: List[bytes] = []
        prev = ROOT_HASH
        matched = 0
        for jb in range(len(tokens) // bs):
            h = chain_hash(prev, tokens[jb * bs:(jb + 1) * bs])
            bid = self.pool.lookup(h)
            if bid is None:
                break
            hits.append(bid)
            chain.append(h)
            prev = h
            matched += bs
        return matched, hits, chain

    def _match_partial_tail(self, chain: List[bytes], tokens: np.ndarray,
                            matched: int):
        """Extend a full-block match into a partially filled tail block
        — a LIVE slot's current tail, or a tail a finished request
        donated to ``_tail_cache`` on release.  Returns (src_bid,
        n_tokens, donated): the physical block to copy-on-write from,
        how many of its leading tokens match (0 = no match), and
        whether the winner is a donated tail — in which case it has
        been revived out of the pool's free queue (a transient
        reference the caller must drop once the copy lands)."""
        bs = self.block_size
        jb = matched // bs
        limit = len(tokens) - 1 - matched   # last token must be computed
        if limit <= 0:
            return -1, 0, False

        def overlap(tail):
            l = 0
            for a, b in zip(tokens[matched:matched + limit], tail):
                if int(a) != int(b):
                    break
                l += 1
            return l

        best_bid, best_l, best_donated = -1, 0, False
        for s in self._active_slots():
            f = len(self.slot_hist[s])
            if f // bs != jb or f % bs == 0:
                continue                     # no partial tail at block jb
            if self.slot_chain[s] != chain:
                continue                     # different history below jb
            l = overlap(self.slot_hist[s][jb * bs:f])
            if l > best_l:
                best_bid, best_l = int(self.block_tables[s, jb]), l
                best_donated = False
        # donated tails from finished requests: tuple equality of the
        # full-block chain implies the donor's tail sits at the same
        # block index jb, so only the token overlap needs checking
        for bid, (tchain, tail) in self._tail_cache.items():
            if tchain != tuple(chain):
                continue
            l = overlap(tail)
            if l > best_l:
                best_bid, best_l, best_donated = bid, l, True
        if best_donated and not self.pool.revive(best_bid):
            # recycled under us (defensive: _alloc_block invalidates
            # entries eagerly, so this should be unreachable)
            self._tail_cache.pop(best_bid, None)
            return -1, 0, False
        return best_bid, best_l, best_donated

    def _donate_tail(self, i: int):
        """Record a finishing slot's partially filled tail block as a
        copy-on-write donor.  Full blocks stay matchable through the
        pool's hash cache after release, but a partial tail has no
        chain hash — without donation its tokens are always recomputed
        by the next identical prompt.  Donations are METADATA ONLY: no
        pool reference is held, the block is released exactly as
        before, and the entry dies the moment the pool recycles its
        block (``_alloc_block``) — so the cache never perturbs
        allocation order, occupancy, eviction, or preemption.  A
        matched entry is revived out of the free queue only for the
        duration of the copy-on-write (``BlockPool.revive``).  Bounded:
        oldest entries are dropped at the cap (pure bookkeeping — no
        block is freed or retained either way)."""
        cl = int(self.cache_len[i])
        if cl % self.block_size == 0:
            return                           # no partial tail
        bid = int(self.block_tables[i, cl // self.block_size])
        self._tail_cache.pop(bid, None)      # re-donation replaces
        while len(self._tail_cache) >= max(2 * self.slots, 2):
            del self._tail_cache[next(iter(self._tail_cache))]
        self._tail_cache[bid] = (
            tuple(self.slot_chain[i]),
            tuple(self.slot_hist[i][(cl // self.block_size)
                                    * self.block_size:cl]))

    def _alloc_block(self) -> Optional[int]:
        """``pool.try_allocate`` + tail-cache invalidation: recycling a
        block makes any donation riding on it stale (its KV is about
        to be overwritten), so the entry dies with the allocation.
        Allocation behavior itself is untouched — donations hold no
        references."""
        bid = self.pool.try_allocate()
        if bid is not None:
            self._tail_cache.pop(bid, None)
        return bid

    def _cow_block(self, slot: int, jb: int, src: int) -> int:
        """Copy-on-write: deep-copy physical block ``src`` into a
        freshly owned block installed at this slot's table entry ``jb``.
        The copy happens BEFORE this slot's first write — sharing the
        block in place would let the newcomer's writes corrupt the
        donor's later reads (the regression test in
        tests/test_prefix_reuse.py).  Returns -1 (no copy, the tokens
        are simply recomputed) when an undersized pool has no block to
        spare — admission never preempts for a mere optimization."""
        dst = self._alloc_block()
        if dst is None:
            return -1
        self.caches = self._copy_step(self.caches, np.int32(src),
                                      np.int32(dst))
        self.block_tables[slot, jb] = dst
        self.slot_nblocks[slot] = jb + 1
        self._dirty_slots.add(slot)
        return dst

    def _admit(self):
        """Assign queued requests to free slots.  Nearly free — no
        forward pass happens here (the prompt streams through
        subsequent unified steps chunk by chunk); prefix matching jumps
        the prompt cursor over blocks the pool already holds, a
        partial-tail hit costs one block copy, and the slot's recurrent
        state is zeroed.

        Preempted requests re-enter from the queue FRONT with their
        *effective* prompt (original prompt + tokens generated before
        preemption): hash matching re-attaches any still-resident
        shared blocks, swapped-out blocks upload from the host arena
        (bit-identical restore), and whatever remains is recomputed —
        chunked recompute of the same token history writes bit-
        identical KV, so resumed rollouts stay exact.  A minimal
        admission gate (at least one allocatable block while other
        slots are active) keeps admission from thrashing straight back
        into preemption.
        """
        for slot in range(self.slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            head = self.queue[0]
            res = self._resume.get((head.uid, head.sample_index))
            # sibling deferral (Request(n>1)): a sibling waits until
            # its leader (the same-uid slot admitted first) finishes
            # prefilling and registers the prompt's full blocks — then
            # THIS sibling's admission finds them all via the normal
            # chain-hash match and shares them by refcount, so the
            # prompt is prefilled exactly once.  FIFO order preserved:
            # we stall admission rather than skip over the sibling.
            if head.sample_index > 0 and res is None and any(
                    self.slot_req[s] is not None
                    and self.slot_req[s].uid == head.uid
                    and self.slot_fill[s] < len(self.slot_prompt[s])
                    for s in range(self.slots)):
                break
            # admission gate: one allocatable block is enough to make
            # progress (a chunk shrinks to the blocks it can get);
            # admitting into a zero-free pool would only preempt
            # whoever owns the last block — churn, not progress.  With
            # no active slot there is nothing to wait for: admit and
            # rely on the lone-slot completion guarantee.
            if self.pool.blocks_free < 1 and self._active_slots():
                break     # wait for a block instead of thrashing; FIFO
            req = self.queue.pop(0)
            if req.t_admit is None:
                req.t_admit = time.perf_counter()
            if res is not None:
                del self._resume[(req.uid, req.sample_index)]
                tokens_in = res["prompt"]     # <= max_len by invariant
            else:
                if req.sample_index > 0:
                    self.sibling_requests += 1
                tokens_in = req.prompt
                if len(tokens_in) > self.max_len:
                    # oversize == 'truncate' (submit rejected it
                    # otherwise): keep the most recent context, WITHOUT
                    # mutating the caller's Request
                    tokens_in = tokens_in[len(tokens_in) - self.max_len:]
            tokens_in = np.asarray(tokens_in, np.int32)
            plen = len(tokens_in)
            resumed_dec = bool(res and res["decoding"])
            self.admitted_prompt_tokens += plen

            matched, hits, chain = (
                self._match_full_blocks(tokens_in) if self.prefix_reuse
                else (0, [], []))
            cow_src, cow_take, cow_release = -1, 0, -1
            if matched >= plen and resumed_dec:
                # a resumed mid-decode request needs no fresh logits
                # from its refill — full coverage goes straight back to
                # decoding (the pending token is out_tokens[-1])
                matched = plen
            elif matched >= plen:
                # whole-prompt hit: the last block must be re-owned so
                # its final position can be recomputed for logits —
                # drop the full-block credit, CoW all but the last
                # token.  The lookup's reference on the source keeps it
                # safe from eviction until the copy lands.
                cow_src = hits.pop()
                chain.pop()
                matched -= self.block_size
                cow_take, cow_release = self.block_size - 1, cow_src
            elif self.prefix_reuse and res is None:
                # a live donor slot's own reference protects the
                # source; a donated tail arrives revived — queue its
                # transient reference for release after the copy
                # (resumed requests restore from the arena instead)
                cow_src, cow_take, donated = self._match_partial_tail(
                    chain, tokens_in, matched)
                if donated:
                    cow_release = cow_src

            self.slot_req[slot] = req
            self.slot_prompt[slot] = tokens_in
            self.block_tables[slot].fill(-1)
            for jb, bid in enumerate(hits):
                self.block_tables[slot, jb] = bid
            self.slot_nblocks[slot] = len(hits)
            self._dirty_slots.add(slot)
            self.slot_chain[slot] = list(chain)
            if cow_src >= 0 and cow_take > 0 and \
                    self._cow_block(slot, len(hits), cow_src) >= 0:
                matched += cow_take
            if cow_release >= 0:
                self.pool.decref(cow_release)
            req.prefix_hit_tokens = matched
            self.prefix_hit_tokens += matched

            if res is not None:
                matched = self._swap_in(slot, res, tokens_in, matched,
                                        plen if resumed_dec
                                        else plen - 1)
                self.recompute_tokens += max(0,
                                             res["covered"] - matched)

            self.slot_hist[slot] = [int(t) for t in tokens_in[:matched]]
            self.slot_fill[slot] = matched
            self.cache_len[slot] = matched
            self.slot_seq[slot] = self._admit_seq
            self._admit_seq += 1
            self._skip_sample[slot] = resumed_dec and matched < plen
            self._reset_slot_state(slot)
            if self.cfg.n_media_tokens:
                self._media_host[slot] = \
                    req.media if req.media is not None else 0.0
                self._media_dirty = True

    def _swap_in(self, slot: int, res: Dict[str, Any],
                 tokens_in: np.ndarray, matched: int, cap: int) -> int:
        """Upload a resumed request's swapped-out blocks from the host
        arena into freshly owned pool blocks, contiguously extending
        the hash-matched prefix.  Full restored blocks are re-registered
        under their chain hashes; the restore is bit-identical (the
        regression test compares bytes).  Returns the new matched
        length."""
        bs = self.block_size
        covered = int(res["covered"])
        swap = res["swap"]
        jb = int(self.slot_nblocks[slot])
        while jb in swap and matched == jb * bs:
            take = min(covered, (jb + 1) * bs) - jb * bs
            if take <= 0 or matched + take > cap:
                break
            bid = self._alloc_block()
            if bid is None:
                break                 # recompute the rest instead
            vals = jax.tree_util.tree_map(jnp.asarray, swap.pop(jb))
            self.caches = self._write_block(self.caches, np.int32(bid),
                                            vals)
            self.block_tables[slot, jb] = bid
            self.slot_nblocks[slot] = jb + 1
            self._dirty_slots.add(slot)
            if take == bs and self.prefix_reuse:
                prev = self.slot_chain[slot][-1] if self.slot_chain[slot] \
                    else ROOT_HASH
                h = chain_hash(prev, tokens_in[jb * bs:(jb + 1) * bs])
                self.slot_chain[slot].append(h)
                self.pool.register(bid, h)
            matched += take
            self.swapped_in_blocks += 1
            self.swapped_in_tokens += take
            jb += 1
        return matched

    # -- preemption / swap --------------------------------------------------

    def _pick_victim(self, requester: int,
                     allow_decode: bool) -> Optional[int]:
        """Victim choice when allocation fails: the YOUNGEST (most
        recently admitted) prefilling slot first — it has the least
        sunk work and frees exclusively-owned blocks immediately.  A
        decode requester may fall back to the youngest *decoding* slot
        (decodes hold whole sequences; without this fallback an all-
        decode batch could deadlock) and, as a last resort, itself.  A
        prefill requester never preempts decodes or older prefills —
        it just takes a smaller (possibly empty) chunk this iteration.
        """
        def youngest(cands):
            return max(cands, key=lambda s: self.slot_seq[s], default=None)
        active = self._active_slots()
        prefilling = [s for s in active if s != requester
                      and self.slot_fill[s] < len(self.slot_prompt[s])]
        if not allow_decode:
            prefilling = [s for s in prefilling
                          if self.slot_seq[s] > self.slot_seq[requester]]
        v = youngest(prefilling)
        if v is not None or not allow_decode:
            return v
        v = youngest([s for s in active if s != requester])
        if v is not None:
            return v
        return requester if requester in active else None

    def _preempt(self, victim: int):
        """Evict a running slot to make blocks available: swap its
        exclusively-owned KV blocks to the host arena (or drop them for
        recompute when the roofline estimate says replaying the tokens
        is cheaper), release every block reference, and requeue the
        request at the FRONT of the queue with its effective prompt
        (original prompt + generated-so-far) so it resumes exactly
        where it stopped.  Shared (refcount > 1) blocks are never
        copied — they stay pool-resident and re-attach by chain hash at
        resume."""
        req = self.slot_req[victim]
        covered = int(self.cache_len[victim])
        out = req.out_tokens
        # the resume prompt: still-prefilling victims keep their (full)
        # prompt — which for an already-resumed slot is its previous
        # effective prompt, never re-extended; decoding victims resume
        # from exactly the cache contents (slot_hist == prompt +
        # generated-and-written), with out_tokens[-1] the pending input
        if self.slot_fill[victim] < len(self.slot_prompt[victim]):
            eff = np.asarray(self.slot_prompt[victim], np.int32)
        else:
            eff = np.asarray(self.slot_hist[victim], np.int32)
        own = [(jb, int(self.block_tables[victim, jb]))
               for jb in range(int(self.slot_nblocks[victim]))
               if self.pool.refcount[int(self.block_tables[victim, jb])]
               == 1]
        mode = self.preempt
        if mode == "auto":
            own_tokens = min(covered, len(own) * self.block_size)
            chip = chip_peaks()
            t_recompute = 2.0 * self._n_params * own_tokens \
                / chip.peak_flops
            t_swap = 2.0 * len(own) * self._block_bytes / chip.host_link_bw
            mode = "swap" if t_swap < t_recompute else "recompute"
        swap: Dict[int, Any] = {}
        if mode == "swap" and own:
            bids = np.asarray([bid for _, bid in own], np.int64)
            fetched = fetch_kv_blocks(self.caches, bids)
            self.swap_d2h_fetches += 1
            for pos, (jb, _) in enumerate(own):
                swap[jb] = jax.tree_util.tree_map(
                    lambda a, p=pos: a[:, p], fetched)
            self.swapped_out_blocks += len(own)
        self._resume[(req.uid, req.sample_index)] = {
            "prompt": eff, "decoding": bool(out), "covered": covered,
            "swap": swap,
        }
        # token accounting: the admission episode ends early, so the
        # never-scheduled prompt remainder leaves the admitted count
        # (the re-admission will count the resume prompt in full) —
        # keeps `scheduled_prefill + prefix_hit + swapped_in ==
        # admitted_prompt_tokens` exact under preemption
        self.admitted_prompt_tokens -= max(
            0, len(self.slot_prompt[victim]) - int(self.slot_fill[victim]))
        self.preemptions += 1
        self.slot_req[victim] = None
        self.slot_prompt[victim] = None
        self.slot_fill[victim] = 0
        self.cache_len[victim] = 0
        self._skip_sample[victim] = False
        self._release_slot(victim)
        self.queue.insert(0, req)

    def _ensure_blocks(self, i: int, upto_len: int,
                       allow_decode_victims: bool = True,
                       on_preempt=None) -> bool:
        """Allocate physical blocks so slot i can hold ``upto_len``
        cache positions, preempting other slots if the pool is
        exhausted.  Returns False when slot i cannot be (fully) grown —
        either it preempted itself (last-resort victim) or, for a
        prefill requester, no eligible victim remained."""
        need = -(-upto_len // self.block_size)
        while self.slot_nblocks[i] < need:
            bid = self._alloc_block()
            if bid is None:
                if self.preempt == "none":
                    return False      # never evict anyone; caller shrinks
                victim = self._pick_victim(i, allow_decode_victims)
                if victim is None:
                    return False
                self._preempt(victim)
                if on_preempt is not None:
                    on_preempt(victim)
                if victim == i:
                    return False
                continue
            self.block_tables[i, self.slot_nblocks[i]] = bid
            self.slot_nblocks[i] += 1
            self._dirty_slots.add(i)
        return True

    def _schedule(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 List[int], List[int]]:
        """Fill the (slots, chunk) grid: decodes first (always), then
        prompt slices under the remaining token budget.  Also builds
        the physical write map (slot_map) and allocates the blocks the
        scheduled tokens land in; on an undersized pool an allocation
        failure preempts a victim slot (decode requesters take the
        youngest prefilling slot regardless of relative age, falling
        back to the youngest other decode; prefill requesters only
        ever preempt prefills younger than themselves — they otherwise
        just take a smaller chunk), and a victim already scheduled this
        iteration is unscheduled — its grid rows cleared and its budget
        tokens refunded — before the step runs.
        """
        tokens = np.zeros((self.slots, self.chunk), np.int32)
        n_new = np.zeros((self.slots,), np.int32)
        oob = self.pool.num_blocks * self.block_size
        slot_map = np.full((self.slots, self.chunk), oob, np.int32)
        decode_slots: List[int] = []
        finishing_prefill: List[int] = []

        def unschedule(v):
            nonlocal budget
            budget += int(n_new[v])     # refund the victim's tokens
            tokens[v] = 0
            n_new[v] = 0
            slot_map[v] = oob
            if v in decode_slots:
                decode_slots.remove(v)
            if v in finishing_prefill:
                finishing_prefill.remove(v)

        def write_map(i, t):
            cl = int(self.cache_len[i])
            pos = cl + np.arange(t)
            blk = self.block_tables[i, pos // self.block_size]
            slot_map[i, :t] = blk * self.block_size + pos % self.block_size

        budget = self.token_budget
        starved = False     # a prompt left waiting by the budget alone
        for i in self._active_slots():
            if self.slot_req[i] is None:
                continue            # preempted earlier in this pass
            if self.slot_fill[i] >= len(self.slot_prompt[i]):
                if not self._ensure_blocks(i, int(self.cache_len[i]) + 1,
                                           on_preempt=unschedule):
                    continue        # last-resort self-preemption
                tokens[i, 0] = self.slot_req[i].out_tokens[-1]
                n_new[i] = 1
                write_map(i, 1)
                decode_slots.append(i)
                budget -= 1   # decode is never stalled, even if < 0
        for i in self._active_slots():
            if self.slot_req[i] is None:
                continue            # preempted by a later decode pass
            plen = len(self.slot_prompt[i])
            fill = int(self.slot_fill[i])
            if fill >= plen:
                continue
            starved |= budget < min(self.chunk, plen - fill)
            if budget <= 0:
                continue
            take = min(self.chunk, plen - fill, budget)
            cl = int(self.cache_len[i])
            if not self._ensure_blocks(i, cl + take,
                                       allow_decode_victims=False,
                                       on_preempt=unschedule):
                # shrink the chunk to the blocks this slot already owns
                take = min(take,
                           int(self.slot_nblocks[i]) * self.block_size
                           - cl)
                if take <= 0:
                    continue
            tokens[i, :take] = self.slot_prompt[i][fill:fill + take]
            n_new[i] = take
            write_map(i, take)
            budget -= take
            if fill + take >= plen:
                finishing_prefill.append(i)
        self.budget_full_steps += starved
        return tokens, n_new, slot_map, decode_slots, finishing_prefill

    def _release_slot(self, i: int):
        """Return every block the slot references to the pool (shared
        blocks decref; completed hashed blocks stay matchable until
        evicted)."""
        for jb in range(int(self.slot_nblocks[i])):
            self.pool.decref(int(self.block_tables[i, jb]))
        self.block_tables[i].fill(-1)
        self.slot_nblocks[i] = 0
        self.slot_hist[i] = []
        self.slot_chain[i] = []
        self._dirty_slots.add(i)

    def _finish_check(self, i: int):
        req = self.slot_req[i]
        # the next decode writes its input token at cache_len: room for
        # it exists iff cache_len < max_len
        if len(req.out_tokens) >= req.max_new_tokens or \
                int(self.cache_len[i]) >= self.max_len:
            # cache-full finish BEFORE the requested budget is a
            # truncation — flagged on the request and counted in
            # stats() so callers can tell a shortened answer from a
            # complete one
            if len(req.out_tokens) < req.max_new_tokens:
                req.truncated = True
                self.truncated_requests += 1
            req.done = True
            self.finished.append(req)
            self.slot_req[i] = None
            self.slot_prompt[i] = None
            if self.prefix_reuse:
                # before release: reads the slot's table/history state
                self._donate_tail(i)
            self._release_slot(i)
            group = self._beam_groups.get(req.uid)
            if group is not None and all(k.done for k in group):
                del self._beam_groups[req.uid]

    def _register_completed(self, i: int, old_len: int, new_len: int):
        """Publish the chain hash of every block slot i completed this
        step, making it matchable by future admissions."""
        bs = self.block_size
        for jb in range(old_len // bs, new_len // bs):
            prev = self.slot_chain[i][-1] if self.slot_chain[i] \
                else ROOT_HASH
            h = chain_hash(prev, self.slot_hist[i][jb * bs:(jb + 1) * bs])
            self.slot_chain[i].append(h)
            self.pool.register(int(self.block_tables[i, jb]), h)

    def step(self):
        """One engine iteration: admit -> one unified mixed step.

        Every call advances the virtual clock ``iters`` by one —
        including no-op iterations where nothing could be scheduled —
        so lifecycle stamps (``Request.submit_step`` /
        ``token_steps``) live on one monotone step axis.

        Each phase runs under a ``jax.profiler.TraceAnnotation``, so a
        profiler trace puts every host second (and every device-idle
        gap) of the call down to one: ``serve.step`` around the call;
        inside it ``serve.admit``, ``serve.schedule``, ``serve.upload``
        (host->device state and inputs), ``serve.launch`` (one per
        dispatch: the step, then the sampler), ``serve.account`` (host
        bookkeeping while the device runs), ``serve.fetch`` (the one
        blocking fetch) and ``serve.emit``.  With the profiler off a
        span costs about a microsecond.
        """
        with _span("serve.step"):
            this_step = self.iters
            self.iters += 1
            with _span("serve.admit"):
                self._admit()
            with _span("serve.schedule"):
                tokens, n_new, slot_map, decode_slots, finishing = \
                    self._schedule()
            if not n_new.any():
                return
            if self.spec_k:
                self._step_spec(this_step, tokens, n_new, slot_map,
                                decode_slots, finishing)
            else:
                self._step_plain(this_step, tokens, n_new, slot_map,
                                 decode_slots, finishing)

    def _step_plain(self, this_step: int, tokens: np.ndarray,
                    n_new: np.ndarray, slot_map: np.ndarray,
                    decode_slots: List[int], finishing: List[int]):
        """The non-speculative tail of ``step()``: upload, launch the
        step, account, launch the sampler, fetch, emit."""
        if self.packed:
            with _span("serve.schedule"):
                (flat, seg, pos, nn, smap, last_idx, bucket) = \
                    self._flatten_grid(tokens, n_new, slot_map)
        with _span("serve.upload"):
            self._sync_device_state()
            if self.packed:
                batch = {"tokens": jnp.asarray(flat)}
                sched = (jnp.asarray(pos), jnp.asarray(nn),
                         jnp.asarray(seg), self._tables_dev,
                         jnp.asarray(smap), jnp.asarray(last_idx))
            else:
                bucket = self.slots * self.chunk
                batch = {"tokens": jnp.asarray(tokens)}
                sched = (jnp.asarray(self.cache_len.copy()),
                         jnp.asarray(n_new),
                         self._tables_dev, jnp.asarray(slot_map))
            if self.cfg.n_media_tokens:
                batch["media"] = self._media_dev
        with _span("serve.launch"):
            lg, self.caches = self._step(self.params, batch, self.caches,
                                         *sched)
        with _span("serve.account"):
            self.grid_tokens += bucket
            # host-side bookkeeping: lengths advance by exactly what
            # was scheduled — no device round-trip
            old_len = self.cache_len.copy()
            self.cache_len += n_new
            self.scheduled_tokens += int(n_new.sum())
            self._last_slot_map = np.where(
                np.arange(self.chunk)[None, :] < n_new[:, None],
                slot_map, -1)
            for i in range(self.slots):
                t = int(n_new[i])
                if not t:
                    continue
                if i not in decode_slots:
                    self.slot_fill[i] += t               # prompt cursor
                    self.scheduled_prefill_tokens += t
                self.slot_hist[i].extend(int(x) for x in tokens[i, :t])
                if self.prefix_reuse:
                    self._register_completed(i, int(old_len[i]),
                                             int(old_len[i]) + t)
            # rows that consume a token this step (token_index for the
            # per-request PRNG stream is len(out_tokens) BEFORE any
            # append)
            sample_rows = decode_slots + [i for i in finishing
                                          if not self._skip_sample[i]]
            sampled = [self.slot_req[i] for i in sample_rows]
            beam_rows = [i for i in sample_rows
                         if self.slot_req[i].sample_mode == "beam"]
            use_sampler = ((not self.greedy) or bool(beam_rows) or any(
                r.allowed_tokens is not None for r in sampled))
        if use_sampler:
            with _span("serve.upload"):
                ids, mask = self._sample_inputs(sample_rows)
                topk = max((self.slot_req[i].n for i in beam_rows),
                           default=0)
                sampler = _get_sampler(
                    0.0 if self.greedy else self.temperature, topk)
                ids, mask = jnp.asarray(ids), jnp.asarray(mask)
            with _span("serve.launch"):
                out_dev = sampler(lg, self._base_key, ids, mask)
        else:
            with _span("serve.launch"):
                out_dev = greedy_token(lg)
        with _span("serve.fetch"):
            # timcheck: allow[d2h] the ONE accounted fetch per step (d2h_fetches)
            fetched = jax.device_get(out_dev)         # the ONE d2h fetch
            self.d2h_fetches += 1
        with _span("serve.emit"):
            now = time.perf_counter()
            cand_ids = cand_lps = None
            if isinstance(fetched, tuple):
                toks, cand_ids, cand_lps = (np.asarray(a) for a in fetched)
            else:
                toks = np.asarray(fetched)
            beam_decode = [i for i in decode_slots if i in beam_rows]
            for i in decode_slots:
                if i in beam_decode:
                    continue
                req = self.slot_req[i]
                req.out_tokens.append(int(toks[i]))
                req.token_steps.append(this_step)
                self._finish_check(i)
            if beam_decode:
                self._beam_decode(beam_decode, cand_ids, cand_lps,
                                  this_step)
            for i in finishing:
                if self._skip_sample[i]:
                    # resumed-mid-decode refill: the "first generated"
                    # token already exists — out_tokens[-1] is the
                    # pending decode input; appending the
                    # (greedy-identical) re-sample would duplicate it
                    self._skip_sample[i] = False
                    continue
                req = self.slot_req[i]
                if req.sample_mode == "beam":
                    # beam root expansion: sibling s seeds its
                    # hypothesis with the s-th best first token
                    # (identical prompt => identical logits across
                    # siblings, so this IS the joint top-n of the root)
                    req.out_tokens.append(
                        int(cand_ids[i, req.sample_index]))
                    req.cum_logprob += float(cand_lps[i, req.sample_index])
                else:
                    req.out_tokens.append(int(toks[i]))  # first generated
                req.token_steps.append(this_step)
                self._finish_check(i)
            _stamp_first(sampled, now)

    def _step_spec(self, this_step: int, tokens: np.ndarray,
                   n_new: np.ndarray, slot_map: np.ndarray,
                   decode_slots: List[int], finishing: List[int]):
        """The speculative tail of ``step()`` (docs/serving.md
        §speculative): extend each scheduled decode row with up to
        ``spec_k`` draft tokens funded by the LEFTOVER token budget
        (decodes and prefill chunks keep strict priority — speculation
        only spends budget nothing else claimed), run k cheap-encoding
        draft passes to propose them, verify all k+1 positions in ONE
        mixed step of the engine's own layout, and accept/roll back.

        Rollback contract: the verify forward wrote target KV at
        positions [cache_len, cache_len+k]; acceptance of ``a`` drafts
        commits coverage cache_len+1+a, so the suffix beyond it is
        abandoned by retreating ``cache_len`` (never re-read: attention
        masks by length, later writes overwrite) and any block past the
        accepted coverage is released back to the pool.  Chain-hash
        registration is DEFERRED to accepted coverage so a block
        containing rejected-draft KV is never matchable.
        ``BlockPool.validate()`` holds after every rollback."""
        oob = self.pool.num_blocks * self.block_size
        bs = self.block_size
        # -- plan: grant draft extensions from the leftover budget ----------
        with _span("serve.schedule"):
            leftover = max(0, self.token_budget - int(n_new.sum()))
            k_of: Dict[int, int] = {}
            for i in decode_slots:
                if leftover <= 0:
                    break
                req = self.slot_req[i]
                cl = int(self.cache_len[i])
                k = min(self.spec_k, self.chunk - 1, leftover,
                        self.max_len - 1 - cl,
                        req.max_new_tokens - len(req.out_tokens) - 1)
                if k <= 0:
                    continue
                # grow the table WITHOUT preemption — speculation is an
                # optimization, never worth evicting anyone; shrink k to
                # the blocks actually obtained
                while int(self.slot_nblocks[i]) * bs < cl + 1 + k:
                    bid = self._alloc_block()
                    if bid is None:
                        break
                    self.block_tables[i, self.slot_nblocks[i]] = bid
                    self.slot_nblocks[i] += 1
                    self._dirty_slots.add(i)
                k = min(k, int(self.slot_nblocks[i]) * bs - cl - 1)
                if k <= 0:
                    continue
                pos = cl + 1 + np.arange(k)
                blk = self.block_tables[i, pos // bs]
                slot_map[i, 1:1 + k] = blk * bs + pos % bs
                n_new[i] = 1 + k
                k_of[i] = k
                leftover -= k
        # -- sample-row operands: mask row j constrains emission j ----------
        with _span("serve.upload"):
            self._sync_device_state()
            sample_rows = decode_slots + [i for i in finishing
                                          if not self._skip_sample[i]]
            sampled = [self.slot_req[i] for i in sample_rows]
            ids = np.zeros((self.slots, 3), np.uint32)
            masks = np.full((self.slots, self.chunk, self.mask_width), -1,
                            np.int32)
            had_mask = np.zeros((self.slots, self.chunk), bool)
            for i in sample_rows:
                req = self.slot_req[i]
                ids[i] = (req.uid, req.sample_index, len(req.out_tokens))
                row = self._mask_row(req, req.out_tokens)
                if row is not None:
                    masks[i, 0, :len(row)] = row
                    had_mask[i, 0] = True
        # -- draft loop: k cheap-encoding passes propose the tokens ---------
        # (pass j consumes grid token j and proposes token j+1 under
        # emission j's mask, so a masked token can never be proposed)
        with _span("serve.draft"):
            max_k = max(k_of.values(), default=0)
            for j in range(max_k):
                active = [i for i, k in k_of.items() if k > j]
                d_tok = np.zeros((self.slots, 1), np.int32)
                d_cl = np.zeros((self.slots,), np.int32)
                d_nn = np.zeros((self.slots,), np.int32)
                d_map = np.full((self.slots, 1), oob, np.int32)
                for i in active:
                    d_tok[i, 0] = tokens[i, j]
                    d_cl[i] = int(self.cache_len[i]) + j
                    d_nn[i] = 1
                    d_map[i, 0] = slot_map[i, j]
                toks_d, self.caches = self._draft_step(
                    self.params, {"tokens": jnp.asarray(d_tok)},
                    self.caches, jnp.asarray(d_cl), jnp.asarray(d_nn),
                    self._tables_dev, jnp.asarray(d_map),
                    jnp.asarray(masks[:, j]))
                # timcheck: allow[d2h] accounted draft fetch (draft_d2h_fetches)
                d_host = jax.device_get(toks_d)
                self.draft_d2h_fetches += 1
                for i in active:
                    tokens[i, 1 + j] = int(d_host[i])
                    req = self.slot_req[i]
                    row = self._mask_row(
                        req, list(req.out_tokens)
                        + [int(t) for t in tokens[i, 1:2 + j]])
                    if row is not None:
                        masks[i, j + 1, :len(row)] = row
                        had_mask[i, j + 1] = True
        # -- verify: ONE mixed step over all k+1 positions per slot ---------
        with _span("serve.upload"):
            if self.packed:
                (flat, seg, pos, nn_, smap, row_idx, bucket) = \
                    self._flatten_spec_grid(tokens, n_new, slot_map)
                batch = {"tokens": jnp.asarray(flat)}
                sched = (jnp.asarray(pos), jnp.asarray(nn_),
                         jnp.asarray(seg), self._tables_dev,
                         jnp.asarray(smap), jnp.asarray(row_idx))
            else:
                bucket = self.slots * self.chunk
                batch = {"tokens": jnp.asarray(tokens)}
                sched = (jnp.asarray(self.cache_len.copy()),
                         jnp.asarray(n_new),
                         self._tables_dev, jnp.asarray(slot_map))
            start = np.zeros((self.slots,), np.int32)
            n_draft = np.zeros((self.slots,), np.int32)
            for i in range(self.slots):
                if i in decode_slots:
                    n_draft[i] = k_of.get(i, 0)
                elif n_new[i]:
                    start[i] = int(n_new[i]) - 1
            accept_in = (jnp.asarray(tokens), jnp.asarray(start),
                         jnp.asarray(n_draft), self._base_key,
                         jnp.asarray(ids), jnp.asarray(masks))
            self.grid_tokens += bucket
        with _span("serve.launch"):
            lg, self.caches = self._spec_step(self.params, batch,
                                              self.caches, *sched)
        with _span("serve.launch"):
            out_dev = self._accept(lg, *accept_in)
        with _span("serve.fetch"):
            # timcheck: allow[d2h] the ONE accounted fetch per step (d2h_fetches)
            fetched = jax.device_get(out_dev)
            self.d2h_fetches += 1
        with _span("serve.emit"):
            now = time.perf_counter()
            emitted, n_emit = (np.asarray(a) for a in fetched)
            # -- host bookkeeping: prefill rows exactly as the plain step
            old_len = self.cache_len.copy()
            self.scheduled_tokens += int(n_new.sum())
            self._last_slot_map = np.where(
                np.arange(self.chunk)[None, :] < n_new[:, None],
                slot_map, -1)
            for i in range(self.slots):
                t = int(n_new[i])
                if not t or i in decode_slots:
                    continue
                self.cache_len[i] += t
                self.slot_fill[i] += t
                self.scheduled_prefill_tokens += t
                self.slot_hist[i].extend(int(x) for x in tokens[i, :t])
                if self.prefix_reuse:
                    self._register_completed(i, int(old_len[i]),
                                             int(old_len[i]) + t)
            # -- decode rows: acceptance accounting, rollback, emission
            for i in decode_slots:
                req = self.slot_req[i]
                k = k_of.get(i, 0)
                a = int(n_emit[i]) - 1
                assert 0 <= a <= k, (a, k)
                self.draft_tokens += k
                self.accepted_tokens += a
                self.rejected_tokens += k - a
                if k and a == k:
                    self.bonus_tokens += 1
                new_cl = int(old_len[i]) + 1 + a
                self.cache_len[i] = new_cl
                self.slot_hist[i].append(int(tokens[i, 0]))
                self.slot_hist[i].extend(int(emitted[i, j])
                                         for j in range(a))
                # rollback: release speculative tail blocks beyond the
                # accepted coverage (cache_len already retreated past
                # them)
                need = -(-new_cl // bs)
                while int(self.slot_nblocks[i]) > need:
                    nb = int(self.slot_nblocks[i]) - 1
                    self.pool.decref(int(self.block_tables[i, nb]))
                    self.block_tables[i, nb] = -1
                    self.slot_nblocks[i] = nb
                    self._dirty_slots.add(i)
                if self.prefix_reuse:
                    self._register_completed(i, int(old_len[i]), new_cl)
                for j in range(a + 1):
                    if had_mask[i, j]:
                        self.masked_tokens += 1
                    req.out_tokens.append(int(emitted[i, j]))
                    req.token_steps.append(this_step)
                self._finish_check(i)
            for i in finishing:
                if self._skip_sample[i]:
                    self._skip_sample[i] = False
                    continue
                req = self.slot_req[i]
                if had_mask[i, 0]:
                    self.masked_tokens += 1
                req.out_tokens.append(int(emitted[i, 0]))
                req.token_steps.append(this_step)
                self._finish_check(i)
            _stamp_first(sampled, now)

    def _flatten_spec_grid(self, tokens: np.ndarray, n_new: np.ndarray,
                           slot_map: np.ndarray):
        """``_flatten_grid`` plus the (slots, chunk) flat-row index map
        the packed verify step gathers all-position logits through
        (rows past a slot's ``n_new`` point at flat row 0; the accept
        function never reads them)."""
        flat, seg, pos, nn, smap, _last_idx, bucket = \
            self._flatten_grid(tokens, n_new, slot_map)
        row_idx = np.zeros((self.slots, self.chunk), np.int32)
        t = 0
        for i in range(self.slots):
            k = int(n_new[i])
            if k:
                row_idx[i, :k] = t + np.arange(k)
                t += k
        return flat, seg, pos, nn, smap, row_idx, bucket

    def _sync_device_state(self):
        """Upload whatever host-side state changed since the last step:
        the per-slot media batch and the dirty rows of the device
        block-table mirror (whole-table refresh when most rows moved).

        Host arrays the engine later mutates in place (``cache_len``,
        ``block_tables``, the media batch) are uploaded from a fresh
        numpy copy that nothing else holds: the transfer may read its
        host buffer after the call returns (the CPU backend may alias
        it; ``jnp.array`` of a numpy array takes no copy first), and the
        asynchronously dispatched step would then read values the host
        has already advanced."""
        if self.cfg.n_media_tokens and self._media_dirty:
            self._media_dev = jnp.asarray(self._media_host.copy())
            self._media_dirty = False
        if self._dirty_slots:
            if self._tables_dev is None or \
                    len(self._dirty_slots) > self.slots // 2:
                self._tables_dev = jnp.asarray(self.block_tables.copy())
            else:
                for i in sorted(self._dirty_slots):
                    self._tables_dev = self._set_table_row(
                        self._tables_dev, np.int32(i),
                        jnp.asarray(self.block_tables[i].copy()))
            self._dirty_slots.clear()

    def _sample_inputs(self, sample_rows: List[int]
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side operands of the jitted sampler: per-slot PRNG
        stream coordinates (uid, sample_index, token_index) and the
        compact guided-decoding mask rows (-1-padded allowed token ids;
        an all--1 row means unconstrained).  Rows not sampling this
        step keep zeros/-1 — their lane's output is never read."""
        ids = np.zeros((self.slots, 3), np.uint32)
        mask = np.full((self.slots, self.mask_width), -1, np.int32)
        for i in sample_rows:
            req = self.slot_req[i]
            ids[i] = (req.uid, req.sample_index, len(req.out_tokens))
            allowed = self._mask_row(req, req.out_tokens)
            if allowed is None:
                continue
            mask[i, :len(allowed)] = allowed
            self.masked_tokens += 1
        return ids, mask

    def _mask_row(self, req: Request,
                  out_prefix: Sequence[int]) -> Optional[List[int]]:
        """Evaluate + validate one guided-decoding mask row: the
        allowed ids for the position that FOLLOWS ``out_prefix`` (None
        = unconstrained).  The speculative path calls this with
        hypothetical draft-extended prefixes, so masks constrain draft
        proposals and verification emissions identically — a masked
        token can never be proposed, and never accepted."""
        if req.allowed_tokens is None:
            return None
        allowed = req.allowed_tokens(list(out_prefix))
        if allowed is None:
            return None
        allowed = list(allowed)
        if not allowed:
            raise ValueError(
                f"allowed_tokens for uid={req.uid} returned an "
                f"empty set at position {len(out_prefix)} — "
                f"every continuation is forbidden; return None for "
                f"an unconstrained position instead")
        if len(allowed) > self.mask_width:
            raise ValueError(
                f"allowed_tokens returned {len(allowed)} ids > "
                f"mask_width={self.mask_width}; construct the "
                f"engine with a larger mask_width")
        return allowed

    # -- beam search (host-side bookkeeping over the CoW fork path) ---------

    def _beam_decode(self, beam_slots: List[int], cand_ids: np.ndarray,
                     cand_lps: np.ndarray, this_step: int):
        """Advance every beam hypothesis that decoded this step.  A
        group whose live siblings are ALL present expands jointly
        (top-n over the union of candidates, slots reassigned to the
        winners via refcount adoption + tail CoW); a partially present
        group — siblings still queued, prefilling, or preempted —
        self-extends each member with its own best token (still a
        valid hypothesis; joint pruning resumes at the next
        fully-present step)."""
        by_uid: Dict[int, List[int]] = {}
        for i in beam_slots:
            by_uid.setdefault(self.slot_req[i].uid, []).append(i)
        for uid, slots_ in by_uid.items():
            group = self._beam_groups.get(uid)
            live = [k for k in (group or []) if not k.done]
            synced = group is not None and live and all(
                any(self.slot_req[s] is k for s in slots_) for k in live)
            if synced:
                self._beam_expand(sorted(slots_), cand_ids, cand_lps,
                                  this_step)
            else:
                self._beam_self_extend(slots_, cand_ids, cand_lps,
                                       this_step)

    def _beam_self_extend(self, slots_: List[int], cand_ids: np.ndarray,
                          cand_lps: np.ndarray, this_step: int):
        """Degraded (but always-correct) beam step: each present
        hypothesis takes its own top-1 continuation, no cross-slot
        reassignment."""
        for i in slots_:
            req = self.slot_req[i]
            req.out_tokens.append(int(cand_ids[i, 0]))
            req.cum_logprob += float(cand_lps[i, 0])
            req.token_steps.append(this_step)
            self._finish_check(i)

    def _beam_expand(self, slots_: List[int], cand_ids: np.ndarray,
                     cand_lps: np.ndarray, this_step: int):
        """Synchronized joint expansion: rank the union of every live
        hypothesis's top-n continuations by cumulative log-prob
        (deduped by (hypothesis, token) signature — vital right after
        root expansion, when clones would flood the pool with
        duplicates) and reassign the group's slots to the winners.
        Adoption reuses the prefix-sharing fork mechanism: the child
        increfs the parent's full (immutable) blocks and deep-copies
        only its partial tail block before either sequence writes
        again — exactly ``_cow_block``'s donor-protection discipline.
        """
        k = len(slots_)
        bs = self.block_size
        # snapshot BEFORE any mutation: winners may adopt any parent
        snap = {}
        for i in slots_:
            req = self.slot_req[i]
            snap[i] = {
                "out": list(req.out_tokens),
                "steps": list(req.token_steps),
                "lp": req.cum_logprob,
                "hist": list(self.slot_hist[i]),
                "chain": list(self.slot_chain[i]),
                "cl": int(self.cache_len[i]),
                "table": self.block_tables[i].copy(),
                "nb": int(self.slot_nblocks[i]),
            }
        best: Dict[tuple, tuple] = {}
        for i in slots_:
            req = self.slot_req[i]
            for j in range(req.n):
                score = req.cum_logprob + float(cand_lps[i, j])
                sig = (tuple(req.out_tokens), int(cand_ids[i, j]))
                cur = best.get(sig)
                if cur is None or score > cur[0] or \
                        (score == cur[0] and (i, j) < (cur[1], cur[2])):
                    best[sig] = (score, i, j, int(cand_ids[i, j]))
        ranked = sorted(best.values(),
                        key=lambda c: (-c[0], c[1], c[2]))[:k]
        # a single parent already contributes n >= k distinct tokens,
        # so ranked always covers the k live slots
        assert len(ranked) == k, (len(ranked), k)
        need = sum(1 for (score, p, j, tok), c in zip(ranked, slots_)
                   if p != c and snap[p]["cl"] % bs)
        if self.pool.blocks_free < need:
            # not enough spare blocks for the tail copies: degrade to
            # self-extension rather than preempting for an optimization
            self._beam_self_extend(slots_, cand_ids, cand_lps, this_step)
            return
        # phase 1 — build every winner's table while ALL parents' own
        # references are still live (a parent that loses its slot may
        # itself be another winner's ancestor)
        new_tables: Dict[int, Tuple[np.ndarray, int]] = {}
        for (score, p, j, tok), c in zip(ranked, slots_):
            if p == c:
                continue
            nfull = snap[p]["cl"] // bs
            tail = snap[p]["cl"] % bs
            table = np.full((self.max_blocks,), -1, np.int32)
            table[:nfull] = snap[p]["table"][:nfull]
            self.pool.incref_all([int(b) for b in table[:nfull]])
            nb = nfull
            if tail:
                src = int(snap[p]["table"][nfull])
                dst = self._alloc_block()
                assert dst is not None    # pre-checked blocks_free
                self.caches = self._copy_step(self.caches, np.int32(src),
                                              np.int32(dst))
                table[nfull] = dst
                nb += 1
            new_tables[c] = (table, nb)
            self.beam_forks += 1
        # phase 2 — release the losers' old references and install the
        # winners' state
        for (score, p, j, tok), c in zip(ranked, slots_):
            if c in new_tables:
                for jb in range(snap[c]["nb"]):
                    self.pool.decref(int(snap[c]["table"][jb]))
                table, nb = new_tables[c]
                self.block_tables[c] = table
                self.slot_nblocks[c] = nb
                self._dirty_slots.add(c)
                self.cache_len[c] = snap[p]["cl"]
                self.slot_hist[c] = list(snap[p]["hist"])
                self.slot_chain[c] = list(snap[p]["chain"])
            req = self.slot_req[c]
            req.out_tokens = snap[p]["out"] + [tok]
            req.token_steps = snap[p]["steps"] + [this_step]
            req.cum_logprob = score
        for c in slots_:
            self._finish_check(c)

    def _flatten_grid(self, tokens: np.ndarray, n_new: np.ndarray,
                      slot_map: np.ndarray):
        """Flatten ``_schedule()``'s padded (slots, chunk) grid into the
        token-packed layout: scheduled tokens concatenated slot-major
        into a (T, 1) buffer with per-token segment ids, cache
        positions, 1/0 validity, and physical write targets, plus the
        flat index of each slot's last scheduled token (for the
        device-side logits gather).  T is bucketed up to the next power
        of two so the jit zoo stays at most log2(slots * chunk) + 1
        entries per engine; padding rows carry seg -1 / n_new 0 /
        position 0 and write to the out-of-bounds sentinel (dropped by
        the scatter, masked by the attention's validity lengths).
        """
        total = int(n_new.sum())
        bucket = 1 << max(0, total - 1).bit_length()
        oob = self.pool.num_blocks * self.block_size
        flat = np.zeros((bucket, 1), np.int32)
        seg = np.full((bucket,), -1, np.int32)
        pos = np.zeros((bucket,), np.int32)
        nn = np.zeros((bucket,), np.int32)
        smap = np.full((bucket, 1), oob, np.int32)
        last_idx = np.zeros((self.slots,), np.int32)
        t = 0
        for i in range(self.slots):
            k = int(n_new[i])
            if not k:
                continue      # unscheduled slot: last_idx 0, ignored
            flat[t:t + k, 0] = tokens[i, :k]
            seg[t:t + k] = i
            pos[t:t + k] = int(self.cache_len[i]) + np.arange(k)
            nn[t:t + k] = 1
            smap[t:t + k, 0] = slot_map[i, :k]
            last_idx[i] = t + k - 1
            t += k
        return flat, seg, pos, nn, smap, last_idx, bucket

    def _progress_signature(self) -> Tuple[int, ...]:
        """Monotone counters that MUST move if an iteration did real
        work: scheduling tokens, finishing requests, preempting a
        victim, or admitting/restoring prompt tokens.  Two identical
        consecutive signatures mean the step was a pure spin."""
        return (self.scheduled_tokens, len(self.finished),
                self.preemptions, self.admitted_prompt_tokens,
                self.prefix_hit_tokens, self.swapped_in_tokens)

    def _pending_report(self) -> str:
        """Human-readable stuck-state summary for drain-loop errors:
        which requests are queued / mid-flight and what the pool holds."""
        queued = [r.uid for r in self.queue]
        active = {
            self.slot_req[i].uid:
                f"slot {i}: fill {int(self.slot_fill[i])}/"
                f"{len(self.slot_prompt[i])}, cache_len "
                f"{int(self.cache_len[i])}, blocks "
                f"{int(self.slot_nblocks[i])}"
            for i in self._active_slots()}
        return (f"queued uids={queued}, active={active}, pool: "
                f"{self.pool.blocks_free} free / "
                f"{self.pool.blocks_in_use} in use / "
                f"{self.pool.blocks_cached} cached of "
                f"{self.pool.num_blocks} blocks, preempt="
                f"{self.preempt!r}")

    def run_until_done(self, max_iters: int = 10000,
                       stall_iters: int = 8) -> List[Request]:
        """Drive ``step()`` until every submitted request finishes.

        Returns ``finished`` only when the engine actually DRAINED
        (empty queue, no active slots).  The two failure modes that
        used to be silent are now loud:

        * **iteration cap** — work remains after ``max_iters`` steps:
          raises instead of returning a partial ``finished`` list the
          caller cannot distinguish from a complete one;
        * **livelock** — ``stall_iters`` consecutive iterations make no
          progress (nothing scheduled, admitted, finished, preempted,
          or swapped in — e.g. an undersized pool with
          ``preempt='none'``): raises naming the stuck requests and the
          pool state instead of spinning host CPU forever.

        Progress is read from the engine's monotone counters
        (``_progress_signature``), so a no-op ``step()`` is detected
        without any device sync.
        """
        it = 0
        stalled = 0
        sig = self._progress_signature()
        while self.queue or self._active_slots():
            if it >= max_iters:
                raise RuntimeError(
                    f"run_until_done: iteration-capped — work remains "
                    f"after {it} iterations ({len(self.finished)} "
                    f"requests finished); raise max_iters or inspect "
                    f"the backlog: " + self._pending_report())
            self.step()
            it += 1
            new_sig = self._progress_signature()
            stalled = stalled + 1 if new_sig == sig else 0
            sig = new_sig
            if stalled >= stall_iters:
                raise RuntimeError(
                    f"run_until_done: no progress for {stalled} "
                    f"consecutive iterations (livelock — the scheduler "
                    f"can neither schedule tokens nor admit, finish, "
                    f"or preempt anything): " + self._pending_report())
        return self.finished

    # -- introspection / invariants ----------------------------------------

    @property
    def output_tokens(self) -> int:
        """Total output tokens emitted so far, in-flight requests
        included (monotone: preempted requests keep their out_tokens
        while queued, so nothing is ever double- or un-counted)."""
        live = sum(len(self.slot_req[i].out_tokens)
                   for i in self._active_slots())
        return live + sum(len(r.out_tokens) for r in self.finished) \
            + sum(len(r.out_tokens) for r in self.queue)

    def stats(self) -> Dict[str, int]:
        """Per-engine paging and reuse counters.

        Everything here is a cumulative COUNTER (monotone; per-step
        deltas are the rates — serve/metrics.counter_deltas computes
        them) except the GAUGES ``blocks_in_use`` / ``blocks_cached``
        / ``preempted_waiting`` / ``preemptable_pool``, which are
        instantaneous occupancy readings (serve/metrics.GAUGES names
        the split; docs/serving.md §telemetry)."""
        return {
            "steps": self.iters,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "scheduled_tokens": self.scheduled_tokens,
            "grid_tokens": self.grid_tokens,
            "budget_full_steps": self.budget_full_steps,
            "scheduled_prefill_tokens": self.scheduled_prefill_tokens,
            "admitted_prompt_tokens": self.admitted_prompt_tokens,
            "blocks_in_use": self.pool.blocks_in_use,
            "blocks_cached": self.pool.blocks_cached,
            "evictions": self.pool.evictions,
            "preemptions": self.preemptions,
            "swapped_out_blocks": self.swapped_out_blocks,
            "swapped_in_blocks": self.swapped_in_blocks,
            "swapped_in_tokens": self.swapped_in_tokens,
            "swap_d2h_fetches": self.swap_d2h_fetches,
            "recompute_tokens": self.recompute_tokens,
            "truncated_requests": self.truncated_requests,
            "finished_requests": len(self.finished),
            "output_tokens": self.output_tokens,
            "d2h_fetches": self.d2h_fetches,
            "sibling_requests": self.sibling_requests,
            "beam_forks": self.beam_forks,
            "masked_tokens": self.masked_tokens,
            "draft_tokens": self.draft_tokens,
            "accepted_tokens": self.accepted_tokens,
            "rejected_tokens": self.rejected_tokens,
            "bonus_tokens": self.bonus_tokens,
            "draft_d2h_fetches": self.draft_d2h_fetches,
            "preempted_waiting": len(self._resume),
            "preemptable_pool": int(self.preemptable),
        }

    def validate(self):
        """Assert the pool/table invariants (cheap, host-side only; the
        property suite calls this after every step):

          * pool hash maps are mutually consistent;
          * every block's refcount equals its multiplicity across
            active slots' tables (cached blocks: 0);
          * table rows are dense prefixes sized exactly
            ceil(cache_len / block_size);
          * a slot's token history matches its cache length;
          * a partially filled tail block is exclusively owned
            (refcount 1) — shared blocks are never written;
          * the last step's physical write targets were disjoint
            across slots.
        """
        self.pool.check()
        counts = np.zeros((self.pool.num_blocks,), np.int64)
        for i in range(self.slots):
            nb_i = int(self.slot_nblocks[i])
            if self.slot_req[i] is None:
                assert nb_i == 0 and (self.block_tables[i] == -1).all(), i
                assert not self.slot_hist[i] and not self.slot_chain[i], i
                continue
            cl = int(self.cache_len[i])
            bids = self.block_tables[i, :nb_i]
            assert (bids >= 0).all(), (i, bids)
            assert (self.block_tables[i, nb_i:] == -1).all(), i
            assert nb_i == -(-cl // self.block_size), (i, nb_i, cl)
            assert len(self.slot_hist[i]) == cl, (i, cl)
            np.add.at(counts, bids, 1)
            if cl % self.block_size:
                tail = int(self.block_tables[i, cl // self.block_size])
                assert self.pool.refcount[tail] == 1, (i, tail)
        # tail donations are metadata only — they hold no references,
        # so the slot tables alone must account for every refcount;
        # every cached entry's block must still be free (revive-able)
        for bid in self._tail_cache:
            assert counts[bid] == 0, (bid, counts[bid])
        assert (self.pool.refcount == counts).all(), \
            (self.pool.refcount, counts)
        if self._last_slot_map is not None:
            written = self._last_slot_map[self._last_slot_map >= 0]
            assert len(np.unique(written)) == len(written), written
