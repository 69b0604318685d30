"""Pallas flash-attention kernel (causal/bidirectional, GQA-aware).

The XLA online-softmax scan in nn/attention.py is memory-correct but
materializes (B, Hk, G, Sq, chunk) score blocks through HBM between
scan steps.  This kernel keeps the running (m, l, acc) statistics in
VMEM across the KV-block grid dimension — the classic flash-attention
schedule on the MXU.

Layout: queries flattened to (B*H, Sq, D); K/V stay (B*Hk, Sk, D) and
the BlockSpec index map routes each query head to its GQA group's KV
head (no KV repetition in HBM).  Grid: (B*H, Sq/bq, Sk/bk), KV
innermost with `arbitrary` semantics; m/l/acc live in VMEM scratch.

VMEM @ bq=bk=256, D=128: q 128 KB + k/v 256 KB + acc/m/l ~132 KB f32
< 0.6 MB — ample headroom for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

# Static VMEM contract (timcheck pallas-contract checker;
# docs/static-analysis.md §vmem-budgets): symbols at the default
# block_q/block_k=256, D=128 geometry; Q/K/V/O tiles + the running
# max/sum/accumulator scratch land around 0.63 MiB.
TIMCHECK_VMEM = {
    "symbols": {"bq": 256, "bk": 256, "d": 128},
    "budgets": {"_fa_kernel": 2 ** 20},
}


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
               *, nk: int, bq: int, bk: int, causal: bool, scale: float,
               sk_valid: int):
    kk = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    kpos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    if sk_valid % bk != 0:   # static: mask the KV padding tail
        s = jnp.where(kpos < sk_valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.maximum(m_new, -1e29)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(kk == nk - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, causal: bool = True,
                           block_q: int = 256, block_k: int = 256,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Sk, Hk, D) with H % Hk == 0.
    Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    assert h % hk == 0
    g = h // hk

    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * hk, sk, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * hk, sk, d)

    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded KV columns are masked in-kernel via the static sk bound
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    sqp, skp = qf.shape[1], kf.shape[1]
    nq, nk = sqp // bq, skp // bk

    kernel = functools.partial(
        _fa_kernel, nk=nk, bq=bq, bk=bk, causal=causal,
        scale=d ** -0.5, sk_valid=sk)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)

    out = out[:, :sq].reshape(b, h, sq, d)
    return jnp.moveaxis(out, 1, 2)
