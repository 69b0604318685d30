"""Pallas paged-attention: the block-table KV gather runs *inside* the
kernel, not in front of it, and only over the live KV.

The XLA route (``nn/attention._paged_chunked_attention``) gathers
``chunk_kv / block_size`` physical KV blocks per online-softmax step
with ``k_pool[ids]`` — XLA materializes every gathered chunk as a fresh
HBM array that the scan body then re-reads, so each serving step pays
the logical KV bytes roughly three times (pool read + copy write + copy
read).  TiM-DNN's thesis is that the gather and the multiply belong in
the same access: here the per-slot block table is a **scalar-prefetch**
argument (``pltpu.PrefetchScalarGridSpec``), the kernel reads it to DMA
each physical block straight from the pool in HBM into VMEM, and the
flash recurrence consumes the chunk out of VMEM — the pool is read at
most once and no gathered copy of K or V ever exists in HBM (int8 KV's
scales are the exception: see Variants).

Layout
------
Grid ``(B, Hk, nc)``: one grid step per ``chunk_kv`` chunk of a row's
table (``cb = chunk_kv // block_size`` entries a chunk, ``nc`` chunks).
Queries are pre-grouped host-side to ``(B, Hk, G*Sq, D)`` f32
(pre-scaled by ``D**-0.5``), so one ``(b, h)`` pair owns all of a KV
head's query rows.  The K/V pools stay in HBM (``memory_space=pl.ANY``,
viewed as ``(nb, bs, Hk*D)``).  In chunk ``c`` of row ``b`` the step

* counts the **live** entries: those holding a position below
  ``kv_valid_len[b]``, i.e. ``min(cb, ceil((vlen - c*chunk_kv) / bs))``
  of them (for a compacted table, the entries with ``entry_valid`` and
  a logical block that starts below ``vlen``);
* if there are none, does nothing at all — no DMA, no flash update.
  That is exact: a fully masked chunk's update is the identity
  (``p = 0``, ``corr = 1``; ``l = acc = 0`` on a row with no key yet);
* else starts one ``make_async_copy`` per live entry (head ``h``'s
  ``(bs, D)`` K and V tiles of block ``tbl[b, c*cb + j]``) into
  ``(cb, bs, D)`` scratch held in the pool's dtype, waits for them all,
  upcasts, and runs one flash update over the whole chunk: one dot for
  the scores, the chunk's masks, one ``p @ V``.

So a row's DMAs and flash updates follow its context, not the table's
size.  Every reduction (row max, row sum, the ``p @ V`` contraction)
spans exactly the same ``chunk_kv`` positions as the shared scan body
in ``nn/attention._online_softmax_scan``, so the kernel agrees with the
XLA gather route to a few ulp (``tests/test_paged_attention_kernel.
py``; the XLA route is in turn bit-identical to the contiguous cache).

**The V-tail rule.**  Scratch rows past a chunk's live entries hold
whatever an earlier chunk (or nothing) left there.  Their scores are
replaced by ``NEG_INF`` before the softmax, so K needs nothing more;
V's dead rows are selected to zero before ``p @ V``, because
``0 * NaN`` is NaN.  The output therefore never depends on a block no
live position reaches.

VMEM per grid step: K + V chunk scratch ``chunk_kv * D`` each in the
pool's dtype (512 KiB in bf16 at chunk_kv = 1024, D = 128), their f32
upcasts and the ``(G*Sq, chunk_kv)`` scores as transients of the flash
step, plus the ``(G*Sq, D)`` query, output and accumulator blocks.  The
block table (and the ``kv_valid_len`` / ``q_offset`` vectors) live in
SMEM via scalar prefetch.

Variants
--------
* ``paged_mixed_attention_pallas`` — S >= 1 new tokens per slot at
  per-slot ``q_offset`` (the serving engine's unified mixed step).
* ``paged_decode_attention_pallas`` — the S == 1 decode special case;
  skips the causal term entirely (the last token's causality is implied
  by ``kv_valid_len``, exactly the classic-decode contract).
* ``paged_packed_attention_pallas`` — the token-packed serving layout:
  T single-token queries with per-token ``seg_ids``; the block table
  stays per-SLOT and the kernel resolves ``tbl[seg[t], j]`` (a second
  SMEM read), so packing never materializes a per-token table.
* int8 KV: pass ``k_scale``/``v_scale`` pools — the codes ride the
  same live copies; their per-(token, head) scales are gathered by the
  table in front of the kernel (``_launch``) and enter one chunk at a
  time; both are dequantized in-VMEM (``codes * scale -> compute
  dtype``), matching ``nn/attention.kv_dequantize`` bit-for-bit.  The
  gather of the scales still costs table-sized work: it writes a
  ``(B, nblk, bs, Hk)`` temporary to HBM for every call.  The pipeline
  then copies a ``(cb, bs, Hk)`` scale block for live chunks only (a
  dead chunk names the last live chunk's block); a compacted table's
  every chunk.
* ``normalize=False`` returns un-normalized ``(o_acc, m, l)`` flash
  partials instead of the softmax output — what ``distrib/decode_attn.
  sharded_paged_mixed_attention`` feeds its cross-device log-sum-exp
  merge.  With it, ``logical_blocks``/``entry_valid`` describe a
  COMPACTED table (each entry names its logical block explicitly and
  may be invalid) — the per-device table-compaction path.

All variants share ``_paged_attn_kernel``; each takes its live bound
from its own ``kv_valid_len`` (and ``entry_valid``).

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
# per chunk), up to hk = 8 KV heads of scales per block.  The (cb, bs,
# D) K + V chunk scratch dominates (priced at f32 by the checker,
# 1 MiB; 512 KiB in bf16); the f32 upcasts and the (gsq, chunk_kv)
# scores are transient values of the flash step.
TIMCHECK_VMEM = {
    "symbols": {"gsq": 64, "d": 128, "bs": 16, "cb": 64, "hk": 8},
    "budgets": {"_paged_attn_kernel": 3 * 2 ** 19},
}


def _head_scale(scales, h):
    """(n, Hk) per-(token, head) scales -> head h's (n, 1) column, as
    an exact one-hot lane reduction (no dynamic lane slice)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    return jnp.sum(jnp.where(lane == h, scales.astype(jnp.float32), 0.0),
                   axis=1, keepdims=True)


def _flat_heads(pool):
    """(nb, bs, Hk, D) -> (nb, bs, Hk*D): a free, contiguous view, in
    which head h's (bs, D) tile of a block is a 128-aligned lane slice."""
    nb, bs, hk, d = pool.shape
    return pool.reshape(nb, bs, hk * d)


def _paged_attn_kernel(*args, cb: int, bs: int, d: int, sq: int,
                       gsq: int, nc: int, causal: bool, quant: bool,
                       compacted: bool, normalize: bool, dequant_dtype,
                       packed: bool = False):
    # packed: a 6th scalar-prefetch operand (per-token segment IDs)
    # picks the table row; vlen/qoff are already per-B = per-token
    tbl_ref, lblk_ref, sel_ref, vlen_ref, qoff_ref = args[:5]
    idx = 6 if packed else 5
    q_ref, k_hbm, v_hbm = args[idx:idx + 3]
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
    kbuf, vbuf, sem, m_ref, l_ref, acc_ref = args[idx:idx + 6]

    b = pl.program_id(0)
    h = pl.program_id(1)
    c = pl.program_id(2)
    row = args[5][b] if packed else b
    ck = cb * bs
    vlen = vlen_ref[b]

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the chunk's live entries: a plain table's are a prefix (those
    # holding a position below vlen); a compacted table marks each one
    # (sel already folds in "its logical block starts below vlen")
    if compacted:
        def entry_live(j):
            return sel_ref[b, c * cb + j] > 0

        n_live = jax.lax.fori_loop(
            0, cb, lambda j, n: n + entry_live(j).astype(jnp.int32), 0)
        n_scan = cb
    else:
        n_live = jnp.clip((vlen - c * ck + bs - 1) // bs, 0, cb)
        n_scan = n_live

    def copies(j):
        blk = tbl_ref[row, c * cb + j]
        heads = pl.ds(pl.multiple_of(h * d, d), d)
        return (pltpu.make_async_copy(k_hbm.at[blk, :, heads], kbuf.at[j],
                                      sem.at[0]),
                pltpu.make_async_copy(v_hbm.at[blk, :, heads], vbuf.at[j],
                                      sem.at[1]))

    def each_live(op):
        def one(j):
            for cp in copies(j):
                getattr(cp, op)()

        def body(j, carry):
            if compacted:
                pl.when(entry_live(j))(lambda: one(j))
            else:
                one(j)
            return carry
        jax.lax.fori_loop(0, n_scan, body, 0)

    @pl.when(n_live > 0)
    def _flash():
        # every copy of the chunk is in flight before the first wait
        each_live("start")
        each_live("wait")
        k = kbuf[...].astype(jnp.float32).reshape(ck, d)
        v = vbuf[...].astype(jnp.float32).reshape(ck, d)
        if quant:
            # exactly nn/attention.kv_dequantize: codes*scale in f32,
            # cast to the compute dtype, THEN to f32 for the dot — the
            # bf16 round-trip is part of the contract
            hk = ks_ref.shape[-1]
            ks = _head_scale(ks_ref[0].astype(jnp.float32)
                             .reshape(ck, hk), h)
            vs = _head_scale(vs_ref[0].astype(jnp.float32)
                             .reshape(ck, hk), h)
            k = (k * ks).astype(dequant_dtype).astype(jnp.float32)
            v = (v * vs).astype(dequant_dtype).astype(jnp.float32)
        q = q_ref[0, 0]                                  # (gsq, d) f32
        sfull = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=_F32)      # (gsq, ck)
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, ck), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (ck, 1), 0)
        if compacted:
            # entry e of this chunk covers logical block lblk[b, e]
            # and contributes only if sel[b, e]
            ent = pos // bs
            row_ent = rows // bs
            kpos = jnp.zeros_like(pos)
            live = jnp.zeros(pos.shape, jnp.bool_)
            v_live = jnp.zeros(rows.shape, jnp.bool_)
            for e in range(cb):
                here = ent == e
                sel_e = sel_ref[b, c * cb + e] > 0
                kpos = jnp.where(here, lblk_ref[b, c * cb + e] * bs, kpos)
                live = live | (here & sel_e)
                v_live = v_live | ((row_ent == e) & sel_e)
            kpos = kpos + pos % bs
        else:
            kpos = c * ck + pos                          # (1, ck)
            v_live = rows < n_live * bs                  # (ck, 1)
        # the V-tail rule: rows no live entry was copied to are stale
        v = jnp.where(v_live, v, 0.0)
        if causal:
            # query row r is (g = r // sq, q = r % sq); position qoff + q
            rq = jax.lax.broadcasted_iota(jnp.int32, (gsq, 1), 0) % sq
            qpos = qoff_ref[b] + rq                      # (gsq, 1)
            sfull = jnp.where(qpos >= kpos, sfull, NEG_INF)
        valid = kpos < vlen                              # (1, ck)
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
            p, v, (((1,), (0,)), ((), ())), precision=_F32)
        m_ref[...] = mj

    @pl.when(c == nc - 1)
    def _done():
        if normalize:
            o_ref[0, 0] = (acc_ref[...] /
                           jnp.maximum(l_ref[...], 1e-30)
                           ).astype(o_ref.dtype)
        else:
            o_ref[0, 0] = acc_ref[...]
            mo_ref[0, 0] = m_ref[...]
            lo_ref[0, 0] = l_ref[...]


def _launch(scalars, qg, k_pool, v_pool, k_scale, v_scale, row_tbl, *,
            nc: int, cb: int, sq: int, causal: bool, compacted: bool,
            normalize: bool, dtype, name: str, interpret: bool):
    """The one ``pallas_call`` of every variant: grid (B, Hk, nc), the
    K/V pools left in HBM for the kernel's own DMAs; ``dtype`` (the
    queries') is the output's and the dequantized KV's.

    int8 scales are gathered by ``row_tbl`` (each grid row's padded
    table) in front of the kernel and ride in as one (cb, bs, Hk) block
    per chunk: the TPU compiler refuses a DMA slice of a pool whose
    lanes are Hk < 128 wide.  They cost Hk * 2 bytes a position against
    the codes' D a head (1/32 at Hk = 2, D = 128), and dead rows'
    scales are masked like the rest.  On a plain table a dead chunk
    re-uses the last live chunk's scale block, so only live chunks'
    scales are copied in; a compacted table's live entries may sit in
    any chunk, so there every chunk's block is copied."""
    b, hk, gsq, d = qg.shape
    bs = k_pool.shape[1]
    quant = k_scale is not None
    packed = len(scalars) == 6
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((1, 1, gsq, d), lambda bb, hh, c, *_:
                          (bb, hh, 0, 0))
    in_specs = [q_spec, hbm, hbm]
    inputs = [qg, _flat_heads(k_pool), _flat_heads(v_pool)]
    if quant:
        ck = cb * bs

        def scale_idx(bb, hh, c, *refs):
            # a dead chunk of a plain table reads no scale: it names the
            # row's last live chunk's block, so the pipeline skips the copy
            if not compacted:
                vlen = refs[3][bb]
                c = jnp.minimum(c, jnp.maximum((vlen + ck - 1) // ck - 1, 0))
            return (bb, c, 0, 0)

        scale_spec = pl.BlockSpec((1, cb, bs, hk), scale_idx)
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scale[row_tbl], v_scale[row_tbl]]
    scratch = [pltpu.VMEM((cb, bs, d), k_pool.dtype),   # K chunk
               pltpu.VMEM((cb, bs, d), v_pool.dtype),   # V chunk
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.VMEM((gsq, 1), jnp.float32),       # running max
               pltpu.VMEM((gsq, 1), jnp.float32),       # running sum
               pltpu.VMEM((gsq, d), jnp.float32)]       # accumulator

    if normalize:
        out_shape = jax.ShapeDtypeStruct((b, hk, gsq, d), dtype)
        out_specs = q_spec
    else:
        ml_spec = pl.BlockSpec((1, 1, gsq, 1), lambda bb, hh, c, *_:
                               (bb, hh, 0, 0))
        out_shape = (jax.ShapeDtypeStruct((b, hk, gsq, d), jnp.float32),
                     jax.ShapeDtypeStruct((b, hk, gsq, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, hk, gsq, 1), jnp.float32))
        out_specs = (q_spec, ml_spec, ml_spec)

    kernel = functools.partial(
        _paged_attn_kernel, cb=cb, bs=bs, d=d, sq=sq, gsq=gsq, nc=nc,
        causal=causal, quant=quant, compacted=compacted,
        normalize=normalize, dequant_dtype=dtype, packed=packed)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, hk, nc),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch)

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*scalars, *inputs)


def _group_queries(q, hk):
    """(B, Sq, H, D) -> (B, Hk, G*Sq, D) f32: exactly the oracle's
    query prep — group, cast f32, THEN pre-scale."""
    b, sq, h, d = q.shape
    g = h // hk
    qg = q.reshape(b, sq, hk, g, d).transpose(0, 2, 3, 1, 4)
    return qg.reshape(b, hk, g * sq, d).astype(jnp.float32) * (d ** -0.5)


def _padded_table(block_tables, nb, cb):
    """Clamp entries into the pool and pad the table to whole chunks;
    padded entries cover logical positions >= nblk*bs >= kv_valid_len,
    so the live bound never reaches them."""
    tbl = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    pad = (-tbl.shape[1]) % cb
    if pad:
        tbl = jnp.pad(tbl, ((0, 0), (0, pad)))
    return tbl, tbl.shape[1] // cb


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
    compacted = logical_blocks is not None
    assert (entry_valid is not None) == compacted
    if interpret is None:
        interpret = pallas_interpret()

    assert chunk_kv % bs == 0, (chunk_kv, bs)
    cb = chunk_kv // bs
    tbl, nc = _padded_table(block_tables, nb, cb)
    vlen = jnp.asarray(kv_valid_len, jnp.int32).reshape(b)
    if compacted:
        lblk = logical_blocks.astype(jnp.int32)
        # an entry is live if selected and its block starts below vlen
        sel = ((entry_valid > 0) & (lblk * bs < vlen[:, None])
               ).astype(jnp.int32)
        pad = tbl.shape[1] - lblk.shape[1]
        if pad:  # padded entries are dead (sel == 0)
            lblk = jnp.pad(lblk, ((0, 0), (0, pad)))
            sel = jnp.pad(sel, ((0, 0), (0, pad)))
    else:
        lblk = jnp.zeros((1, 1), jnp.int32)   # unused (entry == block)
        sel = jnp.zeros((1, 1), jnp.int32)
    qoff = jnp.broadcast_to(
        jnp.asarray(0 if q_offset is None else q_offset, jnp.int32),
        (b,))

    outs = _launch(
        (tbl, lblk, sel, vlen, qoff), _group_queries(q, hk), k_pool,
        v_pool, k_scale, v_scale, tbl, nc=nc, cb=cb, sq=sq, causal=causal,
        compacted=compacted, normalize=normalize, dtype=q.dtype,
        name="paged_attention", interpret=interpret)

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
    per-grid-row, so at B = T they are simply per-token, and so is the
    live bound); the only new machinery is a 6th scalar-prefetch
    operand, through which the kernel's DMAs resolve ``tbl[seg[t],
    c*cb + j]`` — two SMEM reads per block, so no (T, max_blocks)
    gathered table ever exists in HBM.  Grid (T, Hk, nc); VMEM per step
    is the mixed kernel's at Sq = 1 (gsq = G), i.e. strictly under the
    ``TIMCHECK_VMEM`` budget.

    Returns (T, 1, H, D).
    """
    b, sq, h, d = q.shape
    assert sq == 1, q.shape
    nb, bs, hk = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    assert h % hk == 0, (h, hk)
    if interpret is None:
        interpret = pallas_interpret()

    assert chunk_kv % bs == 0, (chunk_kv, bs)
    cb = chunk_kv // bs
    nslots = block_tables.shape[0]
    tbl, nc = _padded_table(block_tables, nb, cb)
    lblk = jnp.zeros((1, 1), jnp.int32)       # unused (entry == block)
    sel = jnp.zeros((1, 1), jnp.int32)
    vlen = jnp.asarray(kv_valid_len, jnp.int32).reshape(b)
    qoff = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    seg = jnp.clip(seg_ids, 0, nslots - 1).astype(jnp.int32)

    outs = _launch(
        (tbl, lblk, sel, vlen, qoff, seg), _group_queries(q, hk), k_pool,
        v_pool, k_scale, v_scale, tbl[seg], nc=nc, cb=cb, sq=sq, causal=True,
        compacted=False, normalize=True, dtype=q.dtype,
        name="paged_packed_attention", interpret=interpret)
    o = outs.reshape(b, hk, h // hk, sq, d).transpose(0, 3, 1, 2, 4)
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
