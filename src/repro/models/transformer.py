"""The unified model: dense / MoE / hybrid / VLM / audio / SSM decoder-or-
encoder transformer, built from the repeating-period layout in ArchConfig.

One code path covers all 10 assigned architectures:

  * params are stacked over periods and the depth loop is a lax.scan —
    HLO size and compile time are O(1) in depth (126-layer llama3-405B
    compiles as one period);
  * every matmul is a TernaryDense (the paper's technique is first-class:
    QAT in training, TiM codes at serving);
  * modes: 'train' (no cache), 'prefill' (build caches), 'decode'
    (one token against caches), 'mixed' (chunked-prefill serving: S
    tokens per slot appended at per-slot cache offsets, ragged via
    ``n_new``).

Caches are a pytree stacked over periods mirroring the layout:
attention blocks hold {k, v}; mamba blocks hold {conv, ssm}; cross-attn
blocks recompute K/V from the (small) media embeddings each step.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, BlockSpec
from repro.nn import attention as attn
from repro.nn.basic import (apply_rope, embedding_init, embedding_specs,
                            layernorm_apply, layernorm_init, layernorm_specs,
                            rmsnorm_apply, rmsnorm_init, rmsnorm_specs)
from repro.nn.linear import (dense_apply, dense_init, dense_specs,
                             ternary_dense_apply, ternary_dense_init,
                             ternary_dense_specs)
from repro.nn.mlp import mlp_apply, mlp_init, mlp_specs
from repro.nn.module import subkey
from repro.nn.moe import moe_apply, moe_init, moe_specs
from repro.nn.ssm import (mamba_apply, mamba_apply_packed, mamba_init,
                          mamba_init_cache, mamba_specs)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# norms (configurable rms/layer)
# ---------------------------------------------------------------------------

def _norm_init(cfg: ArchConfig, d: int):
    return rmsnorm_init(d, cfg.pdtype) if cfg.norm == "rms" \
        else layernorm_init(d, cfg.pdtype)


def _norm_specs(cfg: ArchConfig):
    return rmsnorm_specs() if cfg.norm == "rms" else layernorm_specs()


def _norm_apply(cfg: ArchConfig, p, x):
    return rmsnorm_apply(p, x) if cfg.norm == "rms" \
        else layernorm_apply(p, x)


# ---------------------------------------------------------------------------
# scheduled-token rows of a (slots, chunk) grid
# ---------------------------------------------------------------------------

class TokenRows(NamedTuple):
    """The scheduled tokens of a padded ``(slots, chunk)`` grid, packed
    into ``R`` rows for the token-wise work (embedding, norms, the
    linear layers, RoPE, the residual).  ``idx`` (R,) holds each row's
    flat grid index ``slot * chunk + col``; an index past the grid
    (``>= slots * chunk``) marks a padding row.  Attention (and any
    recurrent mixer) goes back to the grid through ``scatter`` and
    returns through ``gather``, so it sees the padded step's layout."""
    idx: jax.Array
    slots: int
    chunk: int

    @property
    def valid(self) -> jax.Array:
        return self.idx < self.slots * self.chunk

    @property
    def slot(self) -> jax.Array:
        return jnp.minimum(self.idx // self.chunk, self.slots - 1)

    def gather(self, grid: jax.Array) -> jax.Array:
        """(slots, chunk, ...) -> (R, 1, ...); padding rows read the
        last cell and are never written back."""
        flat = grid.reshape((self.slots * self.chunk,) + grid.shape[2:])
        return jnp.take(flat, self.idx, axis=0, mode="clip")[:, None]

    def scatter(self, rows: jax.Array) -> jax.Array:
        """(R, 1, ...) -> (slots, chunk, ...), zero where no row lands."""
        tail = rows.shape[2:]
        flat = jnp.zeros((self.slots * self.chunk,) + tail, rows.dtype)
        flat = flat.at[self.idx].set(rows[:, 0], mode="drop")
        return flat.reshape((self.slots, self.chunk) + tail)


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------

def _attn_block_init(key, cfg: ArchConfig, cross: bool):
    d, hd = cfg.d_model, cfg.hd
    pol = cfg.ternary
    p = {
        "ln1": _norm_init(cfg, d),
        "q": ternary_dense_init(subkey(key, "q"), d, cfg.n_heads * hd, pol,
                                dtype=cfg.pdtype),
        "k": ternary_dense_init(subkey(key, "k"), d, cfg.n_kv_heads * hd,
                                pol, dtype=cfg.pdtype),
        "v": ternary_dense_init(subkey(key, "v"), d, cfg.n_kv_heads * hd,
                                pol, dtype=cfg.pdtype),
        "o": ternary_dense_init(subkey(key, "o"), cfg.n_heads * hd, d, pol,
                                dtype=cfg.pdtype),
    }
    if cross:
        # llama3.2-vision style tanh gates on the cross path
        p["gate_attn"] = jnp.zeros((), cfg.pdtype)
        p["gate_ffn"] = jnp.zeros((), cfg.pdtype)
    return p


def _attn_block_specs(cfg: ArchConfig, cross: bool):
    pol = cfg.ternary
    kv_axis = "kv_heads"
    s = {
        "ln1": _norm_specs(cfg),
        "q": ternary_dense_specs(None, "heads", pol),
        "k": ternary_dense_specs(None, kv_axis, pol),
        "v": ternary_dense_specs(None, kv_axis, pol),
        "o": ternary_dense_specs("heads", None, pol),
    }
    if cross:
        s["gate_attn"] = ()
        s["gate_ffn"] = ()
    return s


def _kv_quantize(t: jax.Array):
    """Per-(token, head) int8 quantization of K/V: t (..., Hk, D) ->
    (codes int8, scale bf16 (..., Hk))."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    codes = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.bfloat16)


_kv_dequantize = attn.kv_dequantize


def _attn_block_apply(p, x, cfg: ArchConfig, positions, mode: str,
                      cache, cache_len, media, cross: bool,
                      n_new=None, block_tables=None, slot_map=None,
                      seg_ids=None, rows=None):
    b, s, _ = x.shape
    hd, h, hk = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    pol = cfg.ternary
    cd = cfg.cdtype

    xin = _norm_apply(cfg, p["ln1"], x)
    q = ternary_dense_apply(p["q"], xin, pol, cd).reshape(b, s, h, hd)

    if cross:
        # K/V from media embeddings, never cached (small, recomputed)
        k = ternary_dense_apply(p["k"], media, pol, cd)
        v = ternary_dense_apply(p["v"], media, pol, cd)
        pm = media.shape[1]
        k = k.reshape(b, pm, hk, hd)
        v = v.reshape(b, pm, hk, hd)
        o = attn.cross_attention(q, k, v)
        new_cache = cache
    else:
        k = ternary_dense_apply(p["k"], xin, pol, cd).reshape(b, s, hk, hd)
        v = ternary_dense_apply(p["v"], xin, pol, cd).reshape(b, s, hk, hd)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_variant)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_variant)
        causal = not cfg.encoder_only

        quant = cfg.kv_cache_dtype == "int8"
        if mode == "train":
            o = attn.chunked_attention(q, k, v, causal=causal,
                                       chunk_kv=cfg.attn_chunk_kv)
            new_cache = cache
        elif mode == "prefill":
            o = attn.chunked_attention(q, k, v, causal=causal,
                                       chunk_kv=cfg.attn_chunk_kv)
            if quant:
                kq, ks = _kv_quantize(k)
                vq, vs = _kv_quantize(v)
                new_cache = {
                    "k": jax.lax.dynamic_update_slice(
                        cache["k"], kq, (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        cache["v"], vq, (0, 0, 0, 0)),
                    "k_scale": jax.lax.dynamic_update_slice(
                        cache["k_scale"], ks, (0, 0, 0)),
                    "v_scale": jax.lax.dynamic_update_slice(
                        cache["v_scale"], vs, (0, 0, 0)),
                }
            else:
                kc = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
                new_cache = {"k": kc, "v": vc}
        else:  # decode / mixed: s new tokens per slot at per-slot offsets
            # ONE scatter/attend path for both cache layouts; only the
            # flat write position differs.  Paged (slot_map given): the
            # cache is a global (num_blocks, block_size, Hk, D) pool
            # and slot b's tokens land at the physical flat positions
            # slot_map[b, :n_new[b]] (block * block_size + offset,
            # computed host-side by the scheduler).  Contiguous: slot
            # b's row offset cache_len[b] + col, flattened.  Padding
            # columns (and any out-of-capacity position) point at the
            # sentinel and drop, so shorter chunks never corrupt the
            # shared cache.
            col = jnp.arange(s)[None, :]
            nn_ = jnp.full((b,), s, jnp.int32) if n_new is None else n_new
            if slot_map is not None:
                cap = cache["k"].shape[0] * cache["k"].shape[1]
                pos = slot_map
            else:
                # token-packed (seg_ids): B = T tokens scatter into
                # their SEGMENT's cache row, not row b — the cache
                # keeps (slots, S_max) rows while the grid is (T, 1)
                nrows, smax = cache["k"].shape[0], cache["k"].shape[1]
                cap = nrows * smax
                row = cache_len[:, None] + col
                if seg_ids is not None:
                    rid = jnp.clip(seg_ids, 0, nrows - 1)[:, None]
                else:
                    rid = jnp.arange(b)[:, None]
                pos = jnp.where(row < smax, rid * smax + row, cap)
            live = col < nn_[:, None]
            if rows is not None:
                # scheduled-token rows (R, 1): each row writes where
                # its grid cell would, padding rows drop
                pos, live = rows.gather(pos), rows.valid[:, None]
            widx = jnp.where(live, pos, cap).reshape(-1)

            def scatter(pool, vals):
                flat = pool.reshape((cap,) + pool.shape[2:])
                flat = flat.at[widx].set(
                    vals.reshape((b * s,) + vals.shape[2:]).astype(
                        pool.dtype), mode="drop")
                return flat.reshape(pool.shape)

            scale_kw = {}
            if quant:
                kq, ks = _kv_quantize(k)
                vq, vs = _kv_quantize(v)
                new_cache = {
                    "k": scatter(cache["k"], kq),
                    "v": scatter(cache["v"], vq),
                    "k_scale": scatter(cache["k_scale"], ks),
                    "v_scale": scatter(cache["v_scale"], vs),
                }
                if block_tables is not None:
                    # paged: the int8 codes and their scales page
                    # through the same tables; attention dequantizes
                    # gathered chunks (in-VMEM on the Pallas route)
                    kd, vd = new_cache["k"], new_cache["v"]
                    scale_kw = dict(k_scale=new_cache["k_scale"],
                                    v_scale=new_cache["v_scale"])
                else:
                    kd = _kv_dequantize(new_cache["k"],
                                        new_cache["k_scale"], cd)
                    vd = _kv_dequantize(new_cache["v"],
                                        new_cache["v_scale"], cd)
            else:
                new_cache = {"k": scatter(cache["k"], k),
                             "v": scatter(cache["v"], v)}
                kd, vd = new_cache["k"], new_cache["v"]
            if seg_ids is not None:
                # token-packed: per-token validity/offset; bucket
                # padding rides along with kv_valid_len == 0
                o = attn.packed_mixed_attention(
                    q, kd, vd, seg_ids, cache_len + nn_, cache_len,
                    chunk_kv=cfg.attn_chunk_kv,
                    block_tables=block_tables, **scale_kw)
            else:
                # scheduled-token rows attend on the slot grid: the
                # same kernel, grid and work as the padded step
                qg = q if rows is None else rows.scatter(q)
                o = attn.mixed_attention(qg, kd, vd, cache_len + nn_,
                                         cache_len,
                                         chunk_kv=cfg.attn_chunk_kv,
                                         block_tables=block_tables,
                                         **scale_kw)
                if rows is not None:
                    o = rows.gather(o)

    o = o.reshape(b, s, h * hd)
    o = ternary_dense_apply(p["o"], o, pol, cd)
    if cross:
        o = jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(cd) * o
    return x + o.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# block dispatch (mixer + ffn)
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ArchConfig, spec: BlockSpec):
    p = {}
    if spec.mixer in ("attn", "cross_attn"):
        p.update(_attn_block_init(subkey(key, "mixer"), cfg,
                                  spec.mixer == "cross_attn"))
    elif spec.mixer == "mamba":
        p["ln1"] = _norm_init(cfg, cfg.d_model)
        p["mamba"] = mamba_init(subkey(key, "mamba"), cfg.mamba, cfg.ternary,
                                cfg.pdtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn is not None:
        p["ln2"] = _norm_init(cfg, cfg.d_model)
        if spec.ffn == "mlp":
            p["ffn"] = mlp_init(subkey(key, "ffn"), cfg.d_model, cfg.d_ff,
                                cfg.ternary, cfg.mlp_kind, cfg.pdtype)
        else:
            p["ffn"] = moe_init(subkey(key, "moe"), cfg.d_model, cfg.moe,
                                cfg.ternary, cfg.pdtype)
    return p


def _block_specs(cfg: ArchConfig, spec: BlockSpec):
    s = {}
    if spec.mixer in ("attn", "cross_attn"):
        s.update(_attn_block_specs(cfg, spec.mixer == "cross_attn"))
    else:
        s["ln1"] = _norm_specs(cfg)
        s["mamba"] = mamba_specs(cfg.mamba, cfg.ternary)
    if spec.ffn is not None:
        s["ln2"] = _norm_specs(cfg)
        s["ffn"] = (mlp_specs(cfg.ternary, cfg.mlp_kind) if spec.ffn == "mlp"
                    else moe_specs(cfg.moe, cfg.ternary))
    return s


def _block_apply(p, x, cfg: ArchConfig, spec: BlockSpec, positions,
                 mode, cache, cache_len, media, n_new=None,
                 block_tables=None, slot_map=None, seg_ids=None,
                 rows=None):
    aux = jnp.zeros((), jnp.float32)
    if spec.mixer in ("attn", "cross_attn"):
        x, new_cache = _attn_block_apply(
            p, x, cfg, positions, mode, cache, cache_len, media,
            spec.mixer == "cross_attn", n_new, block_tables, slot_map,
            seg_ids, rows)
    else:
        h_in = _norm_apply(cfg, p["ln1"], x)
        if rows is not None:
            # the recurrence runs along each slot's chunk: on the grid
            h_in = rows.scatter(h_in)
        mcache = cache if (cache and "ssm" in cache) else None
        if seg_ids is not None and mcache is not None:
            # token-packed: per-slot recurrent state keyed by segment
            y, new_mcache = mamba_apply_packed(
                p["mamba"], h_in, cfg.mamba, cfg.ternary, cfg.cdtype,
                mcache, seg_ids, n_new)
        else:
            y, new_mcache = mamba_apply(p["mamba"], h_in, cfg.mamba,
                                        cfg.ternary, cfg.cdtype, mcache,
                                        n_new=n_new)
        if rows is not None:
            y = rows.gather(y)
        x = x + y.astype(x.dtype)
        new_cache = new_mcache if new_mcache is not None else cache

    if spec.ffn is not None:
        h_in = _norm_apply(cfg, p["ln2"], x)
        if spec.ffn == "mlp":
            y = mlp_apply(p["ffn"], h_in, cfg.ternary, cfg.mlp_kind,
                          cfg.cdtype)
        else:
            # decode/mixed serving is dropless (capacity == tokens*k):
            # per-token results must not depend on what else is in the
            # batch (or on the padding columns of a mixed step)
            cap = (x.shape[0] * x.shape[1] * cfg.moe.top_k
                   if mode in ("decode", "mixed") else None)
            y, aux = moe_apply(p["ffn"], h_in, cfg.moe, cfg.ternary,
                               cfg.cdtype, capacity_override=cap)
        if spec.mixer == "cross_attn":
            y = jnp.tanh(p["gate_ffn"].astype(jnp.float32)).astype(
                y.dtype) * y
        x = x + y.astype(x.dtype)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def period_key(key: jax.Array, i: int) -> jax.Array:
    return subkey(key, f"period{i}")


def init_period(cfg: ArchConfig, kp: jax.Array) -> Params:
    """Master params of one period (one entry of ``init``'s stack) from
    its ``period_key``."""
    return {f"b{j}": _block_init(subkey(kp, f"b{j}"), cfg, spec)
            for j, spec in enumerate(cfg.layout)}


def init(cfg: ArchConfig, key: jax.Array,
         layers: Optional[Params] = None) -> Params:
    """Master params.  ``layers`` supplies the period stack already
    built (``serve/engine.init_serving`` converts it period by period);
    by default every period's masters are built here and stacked."""
    p: Params = {}
    if cfg.frontend_dim:  # audio stub: project precomputed frames
        p["frontend"] = dense_init(subkey(key, "frontend"), cfg.frontend_dim,
                                   cfg.d_model, dtype=cfg.pdtype)
    else:
        p["embed"] = embedding_init(subkey(key, "embed"), cfg.vocab_padded,
                                    cfg.d_model, cfg.pdtype)
    if cfg.n_media_tokens:
        p["media_proj"] = dense_init(subkey(key, "media"), cfg.media_dim,
                                     cfg.d_model, dtype=cfg.pdtype)

    if layers is None:
        periods = [init_period(cfg, period_key(key, i))
                   for i in range(cfg.n_periods)]
        layers = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, 0), *periods)
    p["layers"] = layers
    p["final_norm"] = _norm_init(cfg, cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(subkey(key, "head"), cfg.d_model,
                                  cfg.vocab_padded, dtype=cfg.pdtype)
    return p


def specs(cfg: ArchConfig) -> Params:
    s: Params = {}
    if cfg.frontend_dim:
        s["frontend"] = dense_specs(None, None)
    else:
        s["embed"] = embedding_specs()
    if cfg.n_media_tokens:
        s["media_proj"] = dense_specs(None, None)
    period = {f"b{j}": _block_specs(cfg, spec)
              for j, spec in enumerate(cfg.layout)}
    s["layers"] = jax.tree_util.tree_map(
        lambda t: ("layers",) + t, period,
        is_leaf=lambda x: isinstance(x, tuple))
    s["final_norm"] = _norm_specs(cfg)
    if not cfg.tie_embeddings:
        s["lm_head"] = dense_specs(None, "vocab")
    return s


def embed_inputs(params: Params, cfg: ArchConfig, batch: Dict[str, Any]):
    cd = cfg.cdtype
    if cfg.frontend_dim:
        x = dense_apply(params["frontend"], batch["frames"], cd)
    else:
        x = params["embed"]["table"].astype(cd)[batch["tokens"]]
    media = None
    if cfg.n_media_tokens and "media" in batch:
        media = dense_apply(params["media_proj"], batch["media"], cd)
    return x, media


def forward(params: Params, cfg: ArchConfig, batch: Dict[str, Any],
            mode: str = "train",
            caches: Optional[Params] = None,
            cache_len: Optional[jax.Array] = None,
            n_new: Optional[jax.Array] = None,
            block_tables: Optional[jax.Array] = None,
            slot_map: Optional[jax.Array] = None,
            seg_ids: Optional[jax.Array] = None,
            rows: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (hidden (B,S,d), new_caches (or None), moe_aux_loss).

    Modes: 'train' (no cache), 'prefill' (build caches from position 0),
    'decode' (one token per slot against the caches), and 'mixed' — the
    serving engine's unified step: S tokens per slot appended at the
    per-slot ``cache_len`` write offset, of which only the first
    ``n_new[b]`` are real (n_new == None means all S).  'decode' is the
    S == 1 special case of 'mixed'; both share the same cache-append +
    offset-causal attention path.

    Paged serving ('mixed' + ``block_tables``/``slot_map``): attention
    KV caches are a global block pool (``init_paged_caches``) shared
    across requests; ``slot_map`` ((B, S) int32) gives each new token's
    physical flat position ``block * block_size + offset`` and
    ``block_tables`` ((B, max_blocks) int32) resolves logical reads.
    Logical semantics (positions, causality, validity) are unchanged —
    paged and contiguous mixed steps are bit-identical.  Mamba conv/ssm
    recurrent state stays per-slot (it is O(1) per slot, not per-token).

    Token-packed serving ('mixed' + ``seg_ids``): the batch is a flat
    (T, 1) token buffer — B = total_tokens, S = 1 — and ``seg_ids``
    ((T,) int32) names the slot each token belongs to (out-of-range
    values mark bucket padding).  ``cache_len``/``n_new`` become
    per-TOKEN (T,) arrays (the token's write position and 1/0
    real-or-padding flag); attention routes through
    ``packed_mixed_attention`` and mamba state gathers/scatters at
    segment boundaries.  Per-token math is the padded grid's exactly
    (same masks, same chunk boundaries), so greedy decoding is
    token-for-token identical — docs/serving.md §token-packed.

    Scheduled-token rows (paged 'mixed' + ``rows``): the batch keeps
    the padded (slots, chunk) grid and ``rows`` ((R,) int32) lists the
    flat grid index ``slot * chunk + col`` of each scheduled token (an
    index past the grid, or a cell past its slot's ``n_new``, marks
    padding).  The token-wise work runs on those R rows and only
    attention's core goes back to the grid (``TokenRows``), so every
    real token's values are the padded grid's; the hidden states
    return on the grid, zero off the rows.
    """
    from repro.distrib.sharding import hint_constrain

    grid = None
    if rows is not None:
        assert mode == "mixed" and slot_map is not None \
            and seg_ids is None, "rows ride the paged mixed step"
        slots, chunk = batch["tokens"].shape
        # a row whose cell lies past its slot's n_new is padding
        cell = jnp.minimum(rows, slots * chunk - 1)
        real = (rows < slots * chunk) & (cell % chunk
                                         < n_new[cell // chunk])
        grid = TokenRows(jnp.where(real, rows, slots * chunk), slots,
                         chunk)
        batch = dict(batch, tokens=grid.gather(batch["tokens"]))
    x, media = embed_inputs(params, cfg, batch)
    b, s = x.shape[0], x.shape[1]
    if grid is not None:
        positions = grid.gather(cache_len[:, None]
                                + jnp.arange(grid.chunk)[None, :])
        if media is not None:
            media = media[grid.slot]                # per-row media
    elif mode in ("decode", "mixed"):
        positions = cache_len[:, None] + jnp.arange(s)[None, :]  # (B, S)
    else:
        positions = jnp.arange(s)[None, :]
    # sequence-parallel residual stream (Megatron-SP) when hinted:
    # norms/residual math runs seq-sharded; GSPMD turns the TP
    # all-reduces into reduce-scatter + all-gather pairs around the
    # attention/MLP blocks
    x = hint_constrain(x, ("batch", "seq", None))

    def period_fn(x, period_params, period_cache):
        aux_total = jnp.zeros((), jnp.float32)
        new_caches = {}
        for j, spec in enumerate(cfg.layout):
            blk_cache = None if period_cache is None else period_cache[
                f"b{j}"]
            x, nc, aux = _block_apply(
                period_params[f"b{j}"], x, cfg, spec, positions, mode,
                blk_cache, cache_len, media, n_new, block_tables,
                slot_map, seg_ids, grid)
            x = hint_constrain(x, ("batch", "seq", None))
            new_caches[f"b{j}"] = nc if nc is not None else {}
            aux_total = aux_total + aux
        return x, new_caches, aux_total

    if mode == "train" and cfg.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat == "dots" else None)
        period_fn = jax.checkpoint(period_fn, policy=policy,
                                   static_argnums=())

    def scan_body(carry, xs):
        x, aux_acc = carry
        pparams, pcache = xs
        x, ncache, aux = period_fn(x, pparams, pcache)
        return (x, aux_acc + aux), ncache

    if caches is None:
        def scan_body_nc(carry, pparams):
            x, aux_acc = carry
            x, _, aux = period_fn(x, pparams, None)
            return (x, aux_acc + aux), None
        (x, aux), _ = jax.lax.scan(
            scan_body_nc, (x, jnp.zeros((), jnp.float32)), params["layers"])
        new_caches = None
    else:
        (x, aux), new_caches = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], caches))

    x = _norm_apply(cfg, params["final_norm"], x)
    if grid is not None:
        x = grid.scatter(x)
    return x, new_caches, aux


def logits(params: Params, cfg: ArchConfig, hidden: jax.Array) -> jax.Array:
    cd = cfg.cdtype
    if cfg.tie_embeddings:
        out = hidden.astype(cd) @ params["embed"]["table"].astype(cd).T
    else:
        out = dense_apply(params["lm_head"], hidden, cd)
    if cfg.vocab_padded != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
        out = jnp.where(pad_mask, jnp.asarray(-1e30, out.dtype), out)
    return out


def init_caches(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    """Stacked (over periods) cache pytree matching the layout."""
    hd, hk = cfg.hd, cfg.n_kv_heads

    def one_block(spec: BlockSpec):
        if spec.mixer == "attn":
            if cfg.kv_cache_dtype == "int8":
                return {
                    "k": jnp.zeros((batch, max_len, hk, hd), jnp.int8),
                    "v": jnp.zeros((batch, max_len, hk, hd), jnp.int8),
                    "k_scale": jnp.zeros((batch, max_len, hk),
                                         jnp.bfloat16),
                    "v_scale": jnp.zeros((batch, max_len, hk),
                                         jnp.bfloat16),
                }
            return {
                "k": jnp.zeros((batch, max_len, hk, hd), jnp.bfloat16),
                "v": jnp.zeros((batch, max_len, hk, hd), jnp.bfloat16),
            }
        if spec.mixer == "mamba":
            return mamba_init_cache(cfg.mamba, batch)
        return {}  # cross_attn: recomputed from media

    period = {f"b{j}": one_block(spec) for j, spec in enumerate(cfg.layout)}
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape).copy()
        if hasattr(a, "shape") else a, period)


def init_paged_caches(cfg: ArchConfig, batch: int, num_blocks: int,
                      block_size: int) -> Params:
    """Block-paged cache pytree: attention KV lives in ONE global
    (num_blocks, block_size, ...) pool per period shared by every slot
    (serve/block_pool owns the host-side allocation); mamba conv/ssm
    recurrent state stays per-slot ((batch, ...) — it is constant-size
    per slot, there is nothing to page)."""
    hd, hk = cfg.hd, cfg.n_kv_heads

    def one_block(spec: BlockSpec):
        if spec.mixer == "attn":
            if cfg.kv_cache_dtype == "int8":
                return {
                    "k": jnp.zeros((num_blocks, block_size, hk, hd),
                                   jnp.int8),
                    "v": jnp.zeros((num_blocks, block_size, hk, hd),
                                   jnp.int8),
                    "k_scale": jnp.zeros((num_blocks, block_size, hk),
                                         jnp.bfloat16),
                    "v_scale": jnp.zeros((num_blocks, block_size, hk),
                                         jnp.bfloat16),
                }
            return {
                "k": jnp.zeros((num_blocks, block_size, hk, hd),
                               jnp.bfloat16),
                "v": jnp.zeros((num_blocks, block_size, hk, hd),
                               jnp.bfloat16),
            }
        if spec.mixer == "mamba":
            return mamba_init_cache(cfg.mamba, batch)
        return {}  # cross_attn: recomputed from media

    period = {f"b{j}": one_block(spec) for j, spec in enumerate(cfg.layout)}
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape).copy()
        if hasattr(a, "shape") else a, period)


def paged_cache_specs(cfg: ArchConfig, shard_blocks: bool = False) -> Params:
    """Logical axes for the paged cache pytree (mirrors
    init_paged_caches).  ``shard_blocks`` shards the pool's block axis
    (the paged analogue of sequence-sharding a contiguous cache)."""
    blk_ax = "cache_seq" if shard_blocks else None

    def one_block(spec: BlockSpec):
        if spec.mixer == "attn":
            kv = ("layers", blk_ax, None, "kv_heads_cache", None)
            out = {"k": kv, "v": kv}
            if cfg.kv_cache_dtype == "int8":
                sc = ("layers", blk_ax, None, "kv_heads_cache")
                out["k_scale"] = sc
                out["v_scale"] = sc
            return out
        if spec.mixer == "mamba":
            return {
                "conv": ("layers", "batch", None, "ssm_inner"),
                "ssm": ("layers", "batch", "ssm_heads", None, None),
            }
        return {}

    return {f"b{j}": one_block(spec) for j, spec in enumerate(cfg.layout)}


def cache_specs(cfg: ArchConfig, shard_seq: bool = False) -> Params:
    """Logical axes for the cache pytree (mirrors init_caches)."""
    seq_ax = "cache_seq" if shard_seq else None

    def one_block(spec: BlockSpec):
        if spec.mixer == "attn":
            kv = ("layers", "batch", seq_ax, "kv_heads_cache", None)
            out = {"k": kv, "v": kv}
            if cfg.kv_cache_dtype == "int8":
                sc = ("layers", "batch", seq_ax, "kv_heads_cache")
                out["k_scale"] = sc
                out["v_scale"] = sc
            return out
        if spec.mixer == "mamba":
            return {
                "conv": ("layers", "batch", None, "ssm_inner"),
                "ssm": ("layers", "batch", "ssm_heads", None, None),
            }
        return {}

    return {f"b{j}": one_block(spec) for j, spec in enumerate(cfg.layout)}
