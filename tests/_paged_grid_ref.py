"""The block-grid paged-attention kernel: the bit-exact oracle of the
live-bound kernel in ``kernels/paged_attention.py``.

It is the same arithmetic without the live bound: grid ``(B, Hk, nc,
cb)``, one ``(bs, D)`` block per grid step through a BlockSpec index
map that reads the scalar-prefetched table, every block of the table
fetched (past ``kv_valid_len`` too) and masked, and one flash update
per chunk, dead chunks included.  The live-bound kernel must give the
same bits on every row: it skips only work whose result is the
identity.  Run in interpret mode only.

The token-packed variant is this oracle on the per-token table
``block_tables[seg_ids]``: the kernel body never reads the segment.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import NEG_INF, _F32, _head_scale


def _kernel(tbl_ref, lblk_ref, sel_ref, vlen_ref, qoff_ref, q_ref, k_ref,
            v_ref, *rest, nc, cb, bs, sq, gsq, causal, quant, compacted,
            normalize, dequant_dtype):
    rest = list(rest)
    ks_ref, vs_ref = (rest.pop(0), rest.pop(0)) if quant else (None, None)
    n_out = 1 if normalize else 3
    outs, rest = rest[:n_out], rest[n_out:]
    kbuf_ref, vbuf_ref, m_ref, l_ref, acc_ref = rest
    b, c, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when((c == 0) & (i == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k, v = k_ref[0], v_ref[0]
    if quant:
        h = pl.program_id(1)
        k = (k.astype(jnp.float32) * _head_scale(ks_ref[0], h)
             ).astype(dequant_dtype)
        v = (v.astype(jnp.float32) * _head_scale(vs_ref[0], h)
             ).astype(dequant_dtype)
    kbuf_ref[pl.dslice(i * bs, bs), :] = k.astype(jnp.float32)
    vbuf_ref[pl.dslice(i * bs, bs), :] = v.astype(jnp.float32)

    @pl.when(i == cb - 1)
    def _flash():
        ck = cb * bs
        sfull = jax.lax.dot_general(q_ref[0, 0], kbuf_ref[...],
                                    (((1,), (1,)), ((), ())),
                                    precision=_F32)
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, ck), 1)
        if compacted:
            ent = pos // bs
            kpos = jnp.zeros_like(pos)
            live = jnp.zeros(pos.shape, jnp.bool_)
            for e in range(cb):
                here = ent == e
                kpos = jnp.where(here, lblk_ref[b, c * cb + e] * bs, kpos)
                live = live | (here & (sel_ref[b, c * cb + e] > 0))
            kpos = kpos + pos % bs
        else:
            kpos = c * ck + pos
        if causal:
            rq = jax.lax.broadcasted_iota(jnp.int32, (gsq, 1), 0) % sq
            sfull = jnp.where(qoff_ref[b] + rq >= kpos, sfull, NEG_INF)
        valid = kpos < vlen_ref[b]
        if compacted:
            valid = valid & live
        sfull = jnp.where(valid, sfull, NEG_INF)
        m_prev = m_ref[...]
        mj = jnp.maximum(m_prev, jnp.max(sfull, axis=-1, keepdims=True))
        m_safe = jnp.maximum(mj, -1e29)
        p = jnp.exp(sfull - m_safe)
        corr = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, vbuf_ref[...], (((1,), (0,)), ((), ())), precision=_F32)
        m_ref[...] = mj

    @pl.when((c == nc - 1) & (i == cb - 1))
    def _done():
        if normalize:
            outs[0][0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                             ).astype(outs[0].dtype)
        else:
            outs[0][0, 0] = acc_ref[...]
            outs[1][0, 0] = m_ref[...]
            outs[2][0, 0] = l_ref[...]


def grid_paged_attention(q, k_pool, v_pool, block_tables, kv_valid_len, *,
                         q_offset=None, chunk_kv=1024, k_scale=None,
                         v_scale=None, causal=True, logical_blocks=None,
                         entry_valid=None, normalize=True):
    """Same contract as ``kernels.paged_attention.paged_attention_pallas``."""
    b, sq, h, d = q.shape
    nb, bs, hk = k_pool.shape[:3]
    g = h // hk
    gsq = g * sq
    quant = k_scale is not None
    compacted = logical_blocks is not None
    cb = chunk_kv // bs
    nblk = block_tables.shape[1]
    pad = (-nblk) % cb
    tbl = jnp.pad(jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32),
                  ((0, 0), (0, pad)))
    if compacted:
        lblk = jnp.pad(logical_blocks.astype(jnp.int32), ((0, 0), (0, pad)))
        sel = jnp.pad(entry_valid.astype(jnp.int32), ((0, 0), (0, pad)))
    else:
        lblk = sel = jnp.zeros((1, 1), jnp.int32)
    nc = (nblk + pad) // cb
    qg = q.reshape(b, sq, hk, g, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b, hk, gsq, d).astype(jnp.float32) * (d ** -0.5)
    vlen = jnp.asarray(kv_valid_len, jnp.int32).reshape(b)
    qoff = jnp.broadcast_to(
        jnp.asarray(0 if q_offset is None else q_offset, jnp.int32), (b,))

    def kv_idx(bb, hh, c, i, tbl_r, *_):
        return (tbl_r[bb, c * cb + i], 0, hh)

    def scale_idx(bb, hh, c, i, tbl_r, *_):
        return (tbl_r[bb, c * cb + i], 0, 0)

    head = lambda bb, hh, c, i, *_: (bb, hh, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, 1, gsq, d), head),
                pl.BlockSpec((1, bs, d), kv_idx),
                pl.BlockSpec((1, bs, d), kv_idx)]
    inputs = [qg, k_pool.reshape(nb, bs, hk * d),
              v_pool.reshape(nb, bs, hk * d)]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, hk), scale_idx)] * 2
        inputs += [k_scale, v_scale]
    o_spec = pl.BlockSpec((1, 1, gsq, d), head)
    if normalize:
        out_shape = jax.ShapeDtypeStruct((b, hk, gsq, d), q.dtype)
        out_specs = o_spec
    else:
        ml = jax.ShapeDtypeStruct((b, hk, gsq, 1), jnp.float32)
        out_shape = (jax.ShapeDtypeStruct((b, hk, gsq, d), jnp.float32),
                     ml, ml)
        ml_spec = pl.BlockSpec((1, 1, gsq, 1), head)
        out_specs = (o_spec, ml_spec, ml_spec)
    kernel = functools.partial(
        _kernel, nc=nc, cb=cb, bs=bs, sq=sq, gsq=gsq, causal=causal,
        quant=quant, compacted=compacted, normalize=normalize,
        dequant_dtype=q.dtype)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(b, hk, nc, cb), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((cb * bs, d), jnp.float32),
                            pltpu.VMEM((cb * bs, d), jnp.float32),
                            pltpu.VMEM((gsq, 1), jnp.float32),
                            pltpu.VMEM((gsq, 1), jnp.float32),
                            pltpu.VMEM((gsq, d), jnp.float32)]),
        out_shape=out_shape, interpret=True,
    )(tbl, lblk, sel, vlen, qoff, *inputs)
    if normalize:
        o = outs.reshape(b, hk, g, sq, d).transpose(0, 3, 1, 2, 4)
        return o.reshape(b, sq, h, d)
    o, m, l = outs
    return (o.reshape(b, hk, g, sq, d), m.reshape(b, hk, g, sq),
            l.reshape(b, hk, g, sq))
