"""Pallas paged-attention: the block-table KV gather runs *inside* the
kernel, not in front of it.

The XLA route (``nn/attention._paged_chunked_attention``) gathers
``chunk_kv / block_size`` physical KV blocks per online-softmax step
with ``k_pool[ids]`` — XLA materializes every gathered chunk as a fresh
HBM array that the scan body then re-reads, so each serving step pays
the logical KV bytes roughly three times (pool read + copy write + copy
read).  TiM-DNN's thesis is that the gather and the multiply belong in
the same access: here the per-slot block table is a **scalar-prefetch**
argument (``pltpu.PrefetchScalarGridSpec``), the BlockSpec index map
reads it to pick which physical ``(block_size, head_dim)`` block each
grid step DMAs into VMEM, and the flash recurrence consumes the block
straight out of VMEM — the pool is read exactly once and no gathered
copy ever exists in HBM.

Layout
------
Grid ``(B, Hk, nc, cb)`` with ``cb = chunk_kv // block_size`` blocks
per logical chunk and ``nc`` chunks.  Queries are pre-grouped host-side
to ``(B, Hk, G*Sq, D)`` f32 (pre-scaled by ``D**-0.5``), so one grid
cell owns all of a KV head's query rows.  Per inner step the index map
resolves ``tbl[b, c*cb + i]`` and the kernel copies that block's K and V
tiles into ``(chunk_kv, D)`` VMEM scratch at sublane offset
``i*block_size``; at ``i == cb-1`` one dot scores the assembled chunk,
the chunk's masks apply, and the flash update runs.  (Writing each
block's scores at lane offset ``i*block_size`` instead is refused by
the TPU compiler: a dynamic lane offset must be a multiple of 128.)
Every reduction (row max, row sum, the ``p @ V`` contraction) spans
exactly the same ``chunk_kv`` positions as the shared scan body in
``nn/attention._online_softmax_scan``, so the kernel agrees with the
XLA gather route to a few ulp (``tests/test_paged_attention_kernel.
py``; the XLA route is in turn bit-identical to the contiguous cache).

VMEM per grid cell: kbuf + vbuf ``chunk_kv * D`` f32 each + the
``(G*Sq, D)`` accumulator — ~1.1 MB at the serving shape (G*Sq = 64,
chunk_kv = 1024, D = 128).  The block table (and the ``kv_valid_len`` /
``q_offset`` vectors) live in SMEM via scalar prefetch.

Variants
--------
* ``paged_mixed_attention_pallas`` — S >= 1 new tokens per slot at
  per-slot ``q_offset`` (the serving engine's unified mixed step).
* ``paged_decode_attention_pallas`` — the S == 1 decode special case;
  skips the causal term entirely (the last token's causality is implied
  by ``kv_valid_len``, exactly the classic-decode contract).
* ``paged_packed_attention_pallas`` — the token-packed serving layout:
  T single-token queries with per-token ``seg_ids``; the block table
  stays per-SLOT and the index map resolves ``tbl[seg[t], j]`` (a
  second SMEM read), so packing never materializes a per-token table.
* int8 KV: pass ``k_scale``/``v_scale`` pools — codes and their
  per-(token, head) scales are gathered by the same index map and
  dequantized in-VMEM (``codes * scale -> compute dtype``), matching
  ``nn/attention.kv_dequantize`` bit-for-bit.
* ``normalize=False`` returns un-normalized ``(o_acc, m, l)`` flash
  partials instead of the softmax output — what ``distrib/decode_attn.
  sharded_paged_mixed_attention`` feeds its cross-device log-sum-exp
  merge.  With it, ``logical_blocks``/``entry_valid`` describe a
  COMPACTED table (each entry names its logical block explicitly and
  may be invalid) — the per-device table-compaction path.

``interpret=None`` picks interpret mode on the CPU backend only
(``kernels/ops.pallas_interpret``): CI validates the kernel body through
the interpreter, TPUs run it compiled, and any other backend raises.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import pallas_interpret

NEG_INF = -1e30
# the scores and p @ V are f32 math, as on the XLA route off-TPU: ask
# the MXU for full f32 contraction, not a single bf16 pass
_F32 = jax.lax.Precision.HIGHEST

# Static VMEM contract (timcheck pallas-contract checker;
# docs/static-analysis.md §vmem-budgets).  Symbols at the serving
# shape the docstring budgets: gsq = G*Sq = 64 grouped queries,
# D = 128, block_size = 16, chunk_kv = 1024 (so cb = 64 table entries
# per chunk), up to hk = 8 KV heads of scales per block.  The assembled
# K + V chunk scratch dominates (1 MiB, ~1.1 MiB in all); the
# (gsq, chunk_kv) scores are a transient value of the flash step.
TIMCHECK_VMEM = {
    "symbols": {"gsq": 64, "d": 128, "bs": 16, "cb": 64, "hk": 8},
    "budgets": {"_paged_attn_kernel": 3 * 2 ** 19},
}


def _head_scale(scales, h):
    """(bs, Hk) per-(token, head) scales -> head h's (bs, 1) column, as
    an exact one-hot lane reduction (no dynamic lane slice)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    return jnp.sum(jnp.where(lane == h, scales.astype(jnp.float32), 0.0),
                   axis=1, keepdims=True)


def _pool_index_maps(tbl_idx):
    """Index maps over a KV pool viewed as (nb, bs, Hk*D) (``_flat_heads``)
    and its (nb, bs, Hk) scales.  A K/V block is one head's (bs, D) tile,
    so its trailing dims are (full bs, D): a (1, D) head slice of an
    (Hk, D) pair would break the TPU's (8, 128) block tiling.  Scales
    come as whole (bs, Hk) rows and the kernel picks its head
    (``_head_scale``)."""
    def kv_idx(bb, hh, c, i, *refs):
        return (tbl_idx(bb, c, i, *refs), 0, hh)

    def scale_idx(bb, hh, c, i, *refs):
        return (tbl_idx(bb, c, i, *refs), 0, 0)

    return kv_idx, scale_idx


def _flat_heads(pool):
    """(nb, bs, Hk, D) -> (nb, bs, Hk*D): a free, contiguous view."""
    nb, bs, hk, d = pool.shape
    return pool.reshape(nb, bs, hk * d)


def _paged_attn_kernel(*args, nc: int, cb: int, bs: int, sq: int,
                       gsq: int, causal: bool, quant: bool,
                       compacted: bool, normalize: bool, dequant_dtype,
                       packed: bool = False):
    # packed: a 6th scalar-prefetch operand (per-token segment IDs)
    # rides along for the index maps only — the body never reads it
    # (vlen/qoff are already per-B = per-token)
    tbl_ref, lblk_ref, sel_ref, vlen_ref, qoff_ref = args[:5]
    idx = 6 if packed else 5
    q_ref, k_ref, v_ref = args[idx:idx + 3]
    idx += 3
    if quant:
        ks_ref, vs_ref = args[idx:idx + 2]
        idx += 2
    if normalize:
        o_ref = args[idx]
        idx += 1
    else:
        o_ref, mo_ref, lo_ref = args[idx:idx + 3]
        idx += 3
    kbuf_ref, vbuf_ref, m_ref, l_ref, acc_ref = args[idx:idx + 5]

    b = pl.program_id(0)
    c = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when((c == 0) & (i == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = k_ref[0]                                         # (bs, d)
    v = v_ref[0]
    if quant:
        # exactly nn/attention.kv_dequantize: codes*scale in f32, cast
        # to the compute dtype, THEN to f32 for the dot — the bf16
        # round-trip is part of the contract
        h = pl.program_id(1)
        k = (k.astype(jnp.float32) * _head_scale(ks_ref[0], h)
             ).astype(dequant_dtype)
        v = (v.astype(jnp.float32) * _head_scale(vs_ref[0], h)
             ).astype(dequant_dtype)
    # assemble the chunk's K and V along sublanes; the scores of the
    # whole chunk come from one dot at the last block
    kbuf_ref[pl.dslice(i * bs, bs), :] = k.astype(jnp.float32)
    vbuf_ref[pl.dslice(i * bs, bs), :] = v.astype(jnp.float32)

    @pl.when(i == cb - 1)
    def _flash():
        ck = cb * bs
        q = q_ref[0, 0]                                  # (gsq, d) f32
        sfull = jax.lax.dot_general(q, kbuf_ref[...],
                                    (((1,), (1,)), ((), ())),
                                    precision=_F32)      # (gsq, ck)
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, ck), 1)
        if compacted:
            # entry e of this chunk covers logical block lblk[b, e]
            # and contributes only if sel[b, e]
            ent = pos // bs
            kpos = jnp.zeros_like(pos)
            live = jnp.zeros(pos.shape, jnp.bool_)
            for e in range(cb):
                here = ent == e
                kpos = jnp.where(here, lblk_ref[b, c * cb + e] * bs, kpos)
                live = live | (here & (sel_ref[b, c * cb + e] > 0))
            kpos = kpos + pos % bs
        else:
            kpos = c * ck + pos                          # (1, ck)
        if causal:
            # query row r is (g = r // sq, q = r % sq); position qoff + q
            rq = jax.lax.broadcasted_iota(jnp.int32, (gsq, 1), 0) % sq
            qpos = qoff_ref[b] + rq                      # (gsq, 1)
            sfull = jnp.where(qpos >= kpos, sfull, NEG_INF)
        valid = kpos < vlen_ref[b]                       # (1, ck)
        if compacted:
            valid = valid & live
        sfull = jnp.where(valid, sfull, NEG_INF)
        m_prev = m_ref[...]                              # (gsq, 1)
        mj = jnp.maximum(m_prev, jnp.max(sfull, axis=-1, keepdims=True))
        m_safe = jnp.maximum(mj, -1e29)
        p = jnp.exp(sfull - m_safe)
        corr = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1,
                                                 keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, vbuf_ref[...], (((1,), (0,)), ((), ())), precision=_F32)
        m_ref[...] = mj

    @pl.when((c == nc - 1) & (i == cb - 1))
    def _done():
        if normalize:
            o_ref[0, 0] = (acc_ref[...] /
                           jnp.maximum(l_ref[...], 1e-30)
                           ).astype(o_ref.dtype)
        else:
            o_ref[0, 0] = acc_ref[...]
            mo_ref[0, 0] = m_ref[...]
            lo_ref[0, 0] = l_ref[...]


def paged_attention_pallas(
        q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
        block_tables: jax.Array, kv_valid_len: jax.Array,
        *, q_offset: Optional[Union[int, jax.Array]] = None,
        chunk_kv: int = 1024,
        k_scale: Optional[jax.Array] = None,
        v_scale: Optional[jax.Array] = None,
        causal: bool = True,
        logical_blocks: Optional[jax.Array] = None,
        entry_valid: Optional[jax.Array] = None,
        normalize: bool = True,
        interpret: Optional[bool] = None):
    """In-kernel block-table paged attention (see module docstring).

    q: (B, Sq, H, D); k_pool/v_pool: (num_blocks, block_size, Hk, D)
    (+ optional (num_blocks, block_size, Hk) scales for int8 KV);
    block_tables: (B, nblk) int32 (out-of-range entries are clamped and
    must be masked by ``kv_valid_len``/``entry_valid``); kv_valid_len:
    (B,) valid *logical* lengths.  ``logical_blocks``/``entry_valid``
    (both (B, nblk)) mark a compacted table whose entry j covers
    logical block ``logical_blocks[:, j]`` (invalid entries contribute
    nothing) — without them entry j IS logical block j.

    Returns (B, Sq, H, D), or un-normalized flash partials
    (o (B,Hk,G,Sq,D) f32, m (B,Hk,G,Sq) f32, l (B,Hk,G,Sq) f32) when
    ``normalize=False``.
    """
    b, sq, h, d = q.shape
    nb, bs, hk = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    assert h % hk == 0, (h, hk)
    g = h // hk
    gsq = g * sq
    quant = k_scale is not None
    compacted = logical_blocks is not None
    assert (entry_valid is not None) == compacted
    if interpret is None:
        interpret = pallas_interpret()

    assert chunk_kv % bs == 0, (chunk_kv, bs)
    cb = chunk_kv // bs
    nblk = block_tables.shape[1]
    pad = (-nblk) % cb
    tbl = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    if compacted:
        lblk = logical_blocks.astype(jnp.int32)
        sel = entry_valid.astype(jnp.int32)
    else:
        lblk = jnp.zeros((1, 1), jnp.int32)   # unused (entry == block)
        sel = jnp.zeros((1, 1), jnp.int32)
    if pad:
        tbl = jnp.pad(tbl, ((0, 0), (0, pad)))
        if compacted:  # padded entries masked via sel == 0
            lblk = jnp.pad(lblk, ((0, 0), (0, pad)))
            sel = jnp.pad(sel, ((0, 0), (0, pad)))
        # non-compacted padding is masked positionally: entry e covers
        # logical positions >= nblk*bs >= kv_valid_len
    nc = (nblk + pad) // cb

    # exactly the oracle's query prep: group, cast f32, THEN pre-scale
    qg = q.reshape(b, sq, hk, g, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b, hk, gsq, d).astype(jnp.float32) * (d ** -0.5)
    vlen = jnp.asarray(kv_valid_len, jnp.int32).reshape(b)
    qoff = jnp.broadcast_to(
        jnp.asarray(0 if q_offset is None else q_offset, jnp.int32),
        (b,))

    def _tbl_idx(bb, c, i, tbl_r, *_):
        return tbl_r[bb, c * cb + i]

    kv_idx, scale_idx = _pool_index_maps(_tbl_idx)
    in_specs = [
        pl.BlockSpec((1, 1, gsq, d), lambda bb, hh, c, i, *_: (bb, hh, 0, 0)),
        pl.BlockSpec((1, bs, d), kv_idx),
        pl.BlockSpec((1, bs, d), kv_idx),
    ]
    inputs = [qg, _flat_heads(k_pool), _flat_heads(v_pool)]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, hk), scale_idx),
                     pl.BlockSpec((1, bs, hk), scale_idx)]
        inputs += [k_scale, v_scale]

    o_spec = pl.BlockSpec((1, 1, gsq, d), lambda bb, hh, c, i, *_:
                          (bb, hh, 0, 0))
    if normalize:
        out_shape = jax.ShapeDtypeStruct((b, hk, gsq, d), q.dtype)
        out_specs = o_spec
    else:
        ml_spec = pl.BlockSpec((1, 1, gsq, 1), lambda bb, hh, c, i, *_:
                               (bb, hh, 0, 0))
        out_shape = (jax.ShapeDtypeStruct((b, hk, gsq, d), jnp.float32),
                     jax.ShapeDtypeStruct((b, hk, gsq, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, hk, gsq, 1), jnp.float32))
        out_specs = (o_spec, ml_spec, ml_spec)

    kernel = functools.partial(
        _paged_attn_kernel, nc=nc, cb=cb, bs=bs, sq=sq, gsq=gsq,
        causal=causal, quant=quant, compacted=compacted,
        normalize=normalize, dequant_dtype=q.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hk, nc, cb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((cb * bs, d), jnp.float32),     # assembled K chunk
            pltpu.VMEM((cb * bs, d), jnp.float32),     # assembled V chunk
            pltpu.VMEM((gsq, 1), jnp.float32),         # running max
            pltpu.VMEM((gsq, 1), jnp.float32),         # running sum
            pltpu.VMEM((gsq, d), jnp.float32),         # accumulator
        ])

    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(tbl, lblk, sel, vlen, qoff, *inputs)

    if normalize:
        o = outs.reshape(b, hk, g, sq, d).transpose(0, 3, 1, 2, 4)
        return o.reshape(b, sq, h, d)
    o, m, l = outs
    return (o.reshape(b, hk, g, sq, d),
            m.reshape(b, hk, g, sq),
            l.reshape(b, hk, g, sq))


def paged_mixed_attention_pallas(q, k_pool, v_pool, block_tables,
                                 kv_valid_len, q_offset, *,
                                 chunk_kv: int = 1024, k_scale=None,
                                 v_scale=None, interpret=None):
    """S >= 1 tokens per slot at per-slot offsets — the serving
    engine's unified mixed prefill/decode step, in-kernel gather."""
    return paged_attention_pallas(
        q, k_pool, v_pool, block_tables, kv_valid_len,
        q_offset=q_offset, chunk_kv=chunk_kv, k_scale=k_scale,
        v_scale=v_scale, causal=True, interpret=interpret)


def paged_packed_attention_pallas(
        q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
        block_tables: jax.Array, seg_ids: jax.Array,
        kv_valid_len: jax.Array, *,
        q_offset: jax.Array,
        chunk_kv: int = 1024,
        k_scale: Optional[jax.Array] = None,
        v_scale: Optional[jax.Array] = None,
        interpret: Optional[bool] = None):
    """Packed-query paged attention: block tables index per-SEGMENT.

    The token-packed serving layout — q: (T, 1, H, D) single-token
    queries, ``block_tables`` the un-gathered PER-SLOT (slots,
    max_blocks) table, ``seg_ids`` (T,) the slot each token reads
    (out-of-range entries — bucket padding — are clamped host-side and
    masked by ``kv_valid_len == 0``).  ``kv_valid_len`` / ``q_offset``
    are per-token (T,).

    Same kernel body as ``_paged_attn_kernel`` (vlen/qoff are already
    per-grid-row, so at B = T they are simply per-token); the only new
    machinery is a 6th scalar-prefetch operand and an index map that
    resolves ``tbl[seg[t], c*cb + i]`` — two SMEM reads per grid step,
    so no (T, max_blocks) gathered table ever exists in HBM.  Grid
    (T, Hk, nc, cb); VMEM per cell is the mixed kernel's at Sq = 1
    (gsq = G), i.e. strictly under the ``TIMCHECK_VMEM`` budget.

    Returns (T, 1, H, D).
    """
    b, sq, h, d = q.shape
    assert sq == 1, q.shape
    nb, bs, hk = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    assert h % hk == 0, (h, hk)
    g = h // hk
    gsq = g * sq
    quant = k_scale is not None
    if interpret is None:
        interpret = pallas_interpret()

    assert chunk_kv % bs == 0, (chunk_kv, bs)
    cb = chunk_kv // bs
    nslots, nblk = block_tables.shape
    pad = (-nblk) % cb
    tbl = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    if pad:  # padded entries masked positionally via kv_valid_len
        tbl = jnp.pad(tbl, ((0, 0), (0, pad)))
    nc = (nblk + pad) // cb
    lblk = jnp.zeros((1, 1), jnp.int32)       # unused (entry == block)
    sel = jnp.zeros((1, 1), jnp.int32)

    qg = q.reshape(b, sq, hk, g, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b, hk, gsq, d).astype(jnp.float32) * (d ** -0.5)
    vlen = jnp.asarray(kv_valid_len, jnp.int32).reshape(b)
    qoff = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    seg = jnp.clip(seg_ids, 0, nslots - 1).astype(jnp.int32)

    def _tbl_idx(bb, c, i, tbl_r, lblk_r, sel_r, vlen_r, qoff_r, seg_r):
        return tbl_r[seg_r[bb], c * cb + i]

    kv_idx, scale_idx = _pool_index_maps(_tbl_idx)
    in_specs = [
        pl.BlockSpec((1, 1, gsq, d), lambda bb, hh, c, i, *_: (bb, hh, 0, 0)),
        pl.BlockSpec((1, bs, d), kv_idx),
        pl.BlockSpec((1, bs, d), kv_idx),
    ]
    inputs = [qg, _flat_heads(k_pool), _flat_heads(v_pool)]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, hk), scale_idx),
                     pl.BlockSpec((1, bs, hk), scale_idx)]
        inputs += [k_scale, v_scale]

    o_spec = pl.BlockSpec((1, 1, gsq, d), lambda bb, hh, c, i, *_:
                          (bb, hh, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, hk, gsq, d), q.dtype)

    kernel = functools.partial(
        _paged_attn_kernel, nc=nc, cb=cb, bs=bs, sq=sq, gsq=gsq,
        causal=True, quant=quant, compacted=False,
        normalize=True, dequant_dtype=q.dtype, packed=True)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b, hk, nc, cb),
        in_specs=in_specs,
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((cb * bs, d), jnp.float32),     # assembled K chunk
            pltpu.VMEM((cb * bs, d), jnp.float32),     # assembled V chunk
            pltpu.VMEM((gsq, 1), jnp.float32),         # running max
            pltpu.VMEM((gsq, 1), jnp.float32),         # running sum
            pltpu.VMEM((gsq, d), jnp.float32),         # accumulator
        ])

    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_packed_attention",
    )(tbl, lblk, sel, vlen, qoff, seg, *inputs)

    o = outs.reshape(b, hk, g, sq, d).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, sq, h, d)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables,
                                  kv_valid_len, *, chunk_kv: int = 1024,
                                  k_scale=None, v_scale=None,
                                  interpret=None):
    """One-token decode (Sq == 1): validity alone is the mask — the
    single query sits at position ``kv_valid_len - 1``, so causality is
    implied and the causal term is compiled out entirely."""
    assert q.shape[1] == 1, q.shape
    return paged_attention_pallas(
        q, k_pool, v_pool, block_tables, kv_valid_len,
        q_offset=None, chunk_kv=chunk_kv, k_scale=k_scale,
        v_scale=v_scale, causal=False, interpret=interpret)
