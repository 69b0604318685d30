"""Sequence-parallel decode / mixed-chunk attention (the long_500k
enabler).

For long-context decode the KV cache is sharded along its *sequence*
dim (batch=1 leaves no other axis).  Plain GSPMD would all-gather the
cache to softmax over it — hundreds of GB.  Instead each device attends
over its local cache shard and the partial results merge with the
flash-attention log-sum-exp identity using three tiny psums:

    m   = max_i m_i
    l   = sum_i l_i * exp(m_i - m)
    out = sum_i o_i * l_i * exp(m_i - m) / l

Per-step communication is O(B * Sq * H * D) — independent of context
length.

``sharded_mixed_attention`` is the chunked-prefill generalization the
serving engine's unified step needs: Sq >= 1 new tokens per slot at
per-slot write offsets (``q_offset``), causally masked against global
cache positions, so a prefill chunk can stream into a sequence-sharded
cache without gathering it.  ``sharded_decode_attention`` is its
Sq == 1 wrapper (kept for the long_500k decode cells).

``sharded_paged_mixed_attention`` is the block-paged variant: the KV
pool (num_blocks, block_size, Hk, D) is sharded along its *block* axis
(each device owns a contiguous physical block range), block tables are
replicated, and every device attends only the logical positions whose
physical block is local — the same three-psum lse merge stitches the
partials, so per-step wire bytes stay O(B * Sq * H * D) while shared
prefix blocks live on exactly one device shard.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

NEG_INF = -1e30


def _local_partial(q, k, v, kv_base, cache_len, q_offset=None,
                   kpos=None, extra_valid=None):
    """Local attention stats over this device's cache shard.

    q: (B, Sq, H, D); k/v: (B, S_loc, Hk, D); kv_base: global index of
    local position 0; cache_len: (B,) valid global length; q_offset:
    (B,) global position of each slot's query 0 (None: no causal mask —
    classic last-token decode, validity alone is the mask).  ``kpos``
    ((S_loc,) or per-slot (B, S_loc)) overrides the global positions of
    the local keys (the paged path gathers compacted blocks at per-slot
    logical positions) and ``extra_valid`` ((B, S_loc) bool) ANDs into
    the validity mask (the paged path's is-local-block test).
    Returns m, l: (B, Hk, G, Sq), o: (B, Hk, G, Sq, D) partials.
    """
    b, sq, h, d = q.shape
    s_loc, hk = k.shape[1], k.shape[2]
    g = h // hk
    qg = q.reshape(b, sq, hk, g, d).astype(jnp.float32) * (d ** -0.5)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    if kpos is None:
        kpos = kv_base + jnp.arange(s_loc)
    kpos_b = kpos[None] if kpos.ndim == 1 else kpos      # (1 or B, S_loc)
    valid = kpos_b < cache_len[:, None]                  # (B, S_loc)
    if extra_valid is not None:
        valid = valid & extra_valid
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    if q_offset is not None:
        qpos = q_offset[:, None] + jnp.arange(sq)[None, :]   # (B, Sq)
        causal = qpos[:, :, None] >= kpos_b[:, None, :]      # (B, Sq, S_loc)
        s = jnp.where(causal[:, None, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return m, l, o


def _lse_merge(m, l, o, axis_name: str, out_dtype):
    """Stitch per-shard (m, l, o) partials with the log-sum-exp
    identity (three tiny psums; both clamped finite so fully-masked
    shards contribute exactly zero)."""
    m_g = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(jnp.maximum(m, -1e29) - jnp.maximum(m_g, -1e29))
    l_g = jax.lax.psum(l * corr, axis_name)
    o_g = jax.lax.psum(o * corr[..., None], axis_name)
    out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
    b, hk, g, sq, d = out.shape
    return jnp.moveaxis(out, 3, 1).reshape(b, sq, hk * g, d).astype(
        out_dtype)


def sharded_mixed_attention(q, k_cache, v_cache, cache_len,
                            mesh: Mesh, seq_axis: str = "data",
                            q_offset: Optional[jax.Array] = None):
    """q: (B,Sq,H,D) replicated over seq_axis; caches (B,S,Hk,D) sharded
    on dim 1 over seq_axis; cache_len / q_offset (B,) replicated.

    cache_len is the post-append valid length (the Sq new tokens' K/V
    must already be written at [q_offset, q_offset + n_new)); q_offset
    enables causal masking at the per-slot nonzero offset."""
    n = mesh.shape[seq_axis]
    s_global = k_cache.shape[1]
    s_loc = s_global // n

    def body(qs, ks, vs, cl, qo):
        idx = jax.lax.axis_index(seq_axis)
        m, l, o = _local_partial(qs, ks, vs, idx * s_loc, cl, qo)
        return _lse_merge(m, l, o, seq_axis, qs.dtype)

    in_specs = [P(), P(None, seq_axis), P(None, seq_axis), P(), P()]
    args = [q, k_cache, v_cache, cache_len,
            jnp.zeros_like(cache_len) if q_offset is None else q_offset]
    if q_offset is None:
        # preserve the decode contract: no causal term, validity only
        fn = lambda qs, ks, vs, cl, qo: body(qs, ks, vs, cl, None)
    else:
        fn = body
    return shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=P())(*args)


def sharded_paged_mixed_attention(q, k_pool, v_pool, block_tables,
                                  cache_len, mesh: Mesh,
                                  block_axis: str = "data",
                                  q_offset: Optional[jax.Array] = None,
                                  impl: str = "auto",
                                  chunk_kv: int = 1024):
    """Mixed-chunk attention against a block-paged KV pool sharded on
    its block axis.

    q: (B, Sq, H, D) replicated; k_pool/v_pool: (num_blocks, block_size,
    Hk, D) sharded on dim 0 over ``block_axis``; block_tables: (B,
    nblk) int32 replicated (physical pool block of logical block j, or
    any out-of-range value for unassigned entries); cache_len: (B,)
    post-append valid logical lengths; q_offset: (B,) global position
    of each slot's query 0 (None: validity-only masking — the decode
    contract).

    Each device COMPACTS its slice of the table first — a stable
    local-first argsort keeps at most ``min(nblk, nb_loc)`` entries per
    slot (a device cannot own more distinct blocks than its shard
    holds; table rows must not repeat a physical block, which the
    engine guarantees) — then attends those blocks at their *logical*
    positions and contributes lse partials, merged exactly like
    ``sharded_mixed_attention``.  Per-device score compute is therefore
    O(min(nblk, nb_loc) * block_size), i.e. 1/n of the logical length
    in the long-context regime where the pool outgrows one device,
    not a replicated full-length pass.

    ``impl`` picks how each device turns its compacted table into
    partials: ``'pallas'`` feeds it straight to the paged-attention
    kernel's ``normalize=False`` entry point (``logical_blocks`` =
    the kept logical indices, ``entry_valid`` = the is-local mask; the
    block gather happens in-VMEM inside the kernel, ``chunk_kv``
    positions per flash step); ``'xla'`` gathers with ``ks[g_ids]``
    and computes one whole-shard ``_local_partial`` (the oracle).
    ``'auto'`` = pallas on TPU, xla elsewhere — the kernels/ops.py
    dispatch discipline.
    """
    n = mesh.shape[block_axis]
    nb_global = k_pool.shape[0]
    assert nb_global % n == 0, (nb_global, n)
    nb_loc = nb_global // n
    bs_blk = k_pool.shape[1]
    l_loc = min(block_tables.shape[1], nb_loc)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"

    def _compact(tbl, idx):
        base = idx * nb_loc
        is_local = (tbl >= base) & (tbl < base + nb_loc)  # (B, nblk)
        # local entries first (stable: logical order preserved), then
        # keep the static per-device bound
        order = jnp.argsort(jnp.where(is_local, 0, 1), axis=1)
        keep = order[:, :l_loc]                           # (B, l_loc)
        sel_local = jnp.take_along_axis(is_local, keep, axis=1)
        g_ids = jnp.clip(jnp.take_along_axis(tbl, keep, axis=1) - base,
                         0, nb_loc - 1)
        return keep, sel_local, g_ids

    def body(qs, ks, vs, tbl, cl, qo):
        keep, sel_local, g_ids = _compact(tbl, jax.lax.axis_index(
            block_axis))
        b_ = tbl.shape[0]
        hk, d = ks.shape[2], ks.shape[3]
        kg = ks[g_ids].reshape(b_, l_loc * bs_blk, hk, d)
        vg = vs[g_ids].reshape(b_, l_loc * bs_blk, hk, d)
        kpos = (keep[:, :, None] * bs_blk
                + jnp.arange(bs_blk)[None, None, :]
                ).reshape(b_, l_loc * bs_blk)             # per-slot logical
        m, l, o = _local_partial(
            qs, kg, vg, 0, cl, qo, kpos=kpos,
            extra_valid=jnp.repeat(sel_local, bs_blk, axis=1))
        return _lse_merge(m, l, o, block_axis, qs.dtype)

    def body_pallas(qs, ks, vs, tbl, cl, qo):
        from repro.kernels.paged_attention import paged_attention_pallas
        keep, sel_local, g_ids = _compact(tbl, jax.lax.axis_index(
            block_axis))
        ck = min(chunk_kv - chunk_kv % bs_blk or bs_blk,
                 l_loc * bs_blk)
        o, m, l = paged_attention_pallas(
            qs, ks, vs, g_ids, cl,
            q_offset=jnp.zeros_like(cl) if qo is None else qo,
            chunk_kv=max(ck, bs_blk), causal=qo is not None,
            logical_blocks=keep.astype(jnp.int32),
            entry_valid=sel_local.astype(jnp.int32), normalize=False)
        return _lse_merge(m, l, o, block_axis, qs.dtype)

    in_specs = (P(), P(block_axis), P(block_axis), P(), P(), P())
    args = [q, k_pool, v_pool, block_tables, cache_len,
            jnp.zeros_like(cache_len) if q_offset is None else q_offset]
    inner = body_pallas if impl == "pallas" else body
    if q_offset is None:
        fn = lambda qs, ks, vs, tbl, cl, qo: inner(qs, ks, vs, tbl, cl,
                                                   None)
    else:
        fn = inner
    if impl == "pallas":
        # pallas_call has no replication rule for shard_map's rep check
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(*args)
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=P())(*args)


def sharded_packed_mixed_attention(q, k_pool, v_pool, block_tables,
                                   seg_ids, kv_valid_len,
                                   mesh: Mesh, block_axis: str = "data",
                                   q_offset: Optional[jax.Array] = None,
                                   impl: str = "auto",
                                   chunk_kv: int = 1024):
    """Token-packed variant of ``sharded_paged_mixed_attention``: T
    single-token queries (T, 1, H, D) with per-token ``seg_ids`` naming
    each token's slot in the (slots, nblk) block table.  The per-B
    contract of the paged path already generalizes to B = T — this
    wrapper just gathers each token's table row (bucket-padding rows,
    seg -1, clamp to slot 0 and are masked by their zero validity
    length) and delegates, so the compaction, lse merge, and both
    ``impl`` routes are shared, not re-implemented."""
    nslots = block_tables.shape[0]
    seg = jnp.clip(seg_ids, 0, nslots - 1).astype(jnp.int32)
    return sharded_paged_mixed_attention(
        q, k_pool, v_pool, block_tables[seg], kv_valid_len, mesh,
        block_axis=block_axis, q_offset=q_offset, impl=impl,
        chunk_kv=chunk_kv)


def sharded_decode_attention(q, k_cache, v_cache, cache_len,
                             mesh: Mesh, seq_axis: str = "data"):
    """One-token decode (Sq == 1) against a sequence-sharded cache."""
    return sharded_mixed_attention(q, k_cache, v_cache, cache_len, mesh,
                                   seq_axis)


def reference_decode_attention(q, k_cache, v_cache, cache_len):
    """Unsharded oracle for tests."""
    from repro.nn.attention import decode_attention
    return decode_attention(q, k_cache, v_cache, cache_len)


def reference_mixed_attention(q, k_cache, v_cache, cache_len, q_offset):
    """Unsharded oracle for the mixed-chunk case."""
    from repro.nn.attention import mixed_attention
    return mixed_attention(q, k_cache, v_cache, cache_len, q_offset,
                           chunk_kv=k_cache.shape[1])


def reference_paged_mixed_attention(q, k_pool, v_pool, block_tables,
                                    cache_len, q_offset):
    """Unsharded paged oracle for tests."""
    from repro.nn.attention import mixed_attention
    nblk = block_tables.shape[1]
    return mixed_attention(q, k_pool, v_pool, cache_len, q_offset,
                           chunk_kv=nblk * k_pool.shape[1],
                           block_tables=block_tables)
