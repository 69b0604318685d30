"""Dispatching wrappers for TiM ternary matmuls.

Three implementations of the same contract:

  * ``impl='pallas'`` — the Pallas TPU kernels (kernels/tim_matmul.py),
    compiled on a TPU and run with interpret=True on the CPU backend so
    the tests validate the kernel bodies; any other backend raises
    (``pallas_interpret``).
  * ``impl='xla'``    — the same S/T sign-magnitude decomposition written
    as jnp int8 dot_generals.  This is what distributed model code uses
    under jit: XLA fuses the epilogue, GSPMD shards it, and the dry-run
    cost analysis sees the true int8 FLOPs/bytes.
  * ``impl='ref'``    — dequantize + dense matmul (oracle, tests only).

The contract (all impls agree to float tolerance):

    out[m, n] = sum_k I(x_q[m, k]) * W(w_q[k, n])

with I/W the weighted ternary decodings, optional per-L-block ADC
saturation (``n_max``), and two-phase execution when the encoding
demands it (asymmetric weights with signed inputs, or asymmetric
inputs).  Every combination now lowers on every impl: 2-bit packed
weights compose with the ADC-fidelity clamp (the pallas kernels force
the K step to L=16 codes = 4 packed bytes and unpack in-VMEM before
clamping), so ``tim_matmul(..., impl='pallas')`` with packed weights
and ``n_max`` set is a supported serving configuration, not an error.

Bit-serial activations take arbitrary ``bits`` (``tim_matmul_bitserial``);
the policy level exposes 2-bit (WRPN, ``act_mode='int2'``) and 4-bit
(``act_mode='int4'``) serving — the fused kernel applies all ``bits``
planes against one weight stream, so the HBM weight-traffic win grows
linearly with ``bits``.

Fused multi-pass execution (default)
------------------------------------
Two-phase and bit-serial cases historically lowered as multiple full
launches — ``run(pos) - run(neg)`` and one launch per bit-plane — each
re-streaming the whole weight matrix from HBM.  With ``fused=True``
(the default) a single launch performs every pass per tile:

  * pallas: the fused kernels derive phase masks / bit-planes in-VMEM
    and apply them against one W tile read
    (``tim_matmul_fused_pallas`` / ``tim_matmul_bitserial_fused_pallas``);
  * xla: the phase (or bit-plane) patterns are stacked along M so a
    *single* dot_general streams W once; the signed / shifted
    combination is an epilogue over the stacked result.

``fused=False`` keeps the historical multi-launch route as the parity
oracle for the fused path (tests assert bit-identical two-phase
output); no production caller takes it.
``weight_stream_stats`` quantifies the HBM weight-traffic win; the
kernel benchmark and tests consume it.

Public contract
---------------
* Production routes: ``impl='auto'`` resolves to 'pallas' on TPU and
  'xla' elsewhere; 'xla' is also what distributed/jitted model code
  lowers under GSPMD.  Oracles: ``impl='ref'`` (dense dequantized
  matmul) and ``fused=False`` (multi-launch).  The same dispatch
  discipline governs the paged-attention kernel in nn/attention.py —
  the whole family is documented in docs/kernels.md.
* Invariants the tests pin: all impls agree to float tolerance on the
  contract above; fused == unfused bit-for-bit on the xla route;
  packed and ``n_max`` compose on every route; ``weight_stream_stats``
  launch counts are gated against
  benchmarks/baselines/kernel_bench_baseline.csv in CI.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.ternary import TernaryScales
from repro.core.weights import TernaryWeight
from repro.kernels import ref as _ref
from repro.kernels import tim_matmul as _tk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Whether a Pallas kernel must run in interpret mode: on the CPU
    backend (where the tests validate the kernel bodies) it must, on a
    TPU it runs compiled, and any other backend has no route at all."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on backend {backend!r}: use "
        f"impl='xla' there")


def _as_vec(scale, n, dtype=jnp.float32):
    s = jnp.asarray(scale, dtype).reshape(-1)
    if s.shape[0] == 1 and n != 1:
        s = jnp.broadcast_to(s, (n,))
    return s


def _st_matmul_xla(x_q, w_q, w1, w2, i1, need_t, n_max, l_block=16):
    """S/T decomposition in plain jnp (GSPMD-friendly path).

    ``x_q`` may carry leading batch dims — (..., M, K) codes against a
    (K, N) weight.  The fused routes rely on this: they stack phase /
    bit-plane patterns along a fresh leading axis.
    """
    cdims = (((x_q.ndim - 1,), (0,)), ((), ()))
    if n_max is None:
        s = jax.lax.dot_general(x_q, w_q, cdims,
                                preferred_element_type=jnp.int32)
        out = (w1 + w2) * 0.5 * s.astype(jnp.float32)
        if need_t:
            t = jax.lax.dot_general(jnp.abs(x_q), jnp.abs(w_q), cdims,
                                    preferred_element_type=jnp.int32)
            out = out + (w1 - w2) * 0.5 * t.astype(jnp.float32)
        return i1 * out
    # saturating: block the K dim and clamp counts per block
    kdim = x_q.shape[-1]
    pad = (-kdim) % l_block
    if pad:
        widths = [(0, 0)] * (x_q.ndim - 1) + [(0, pad)]
        x_q = jnp.pad(x_q, widths)
        w_q = jnp.pad(w_q, ((0, pad), (0, 0)))
    nb = x_q.shape[-1] // l_block
    xb = x_q.reshape(x_q.shape[:-1] + (nb, l_block)).astype(jnp.int32)
    wb = w_q.reshape(nb, l_block, -1).astype(jnp.int32)
    s = jnp.einsum("...bl,bln->...bn", xb, wb)
    t = jnp.einsum("...bl,bln->...bn", jnp.abs(xb), jnp.abs(wb))
    n = jnp.minimum((t + s) // 2, n_max)
    k = jnp.minimum((t - s) // 2, n_max)
    out = (w1 * n.astype(jnp.float32) - w2 * k.astype(jnp.float32)).sum(-2)
    return i1 * out


def _constrain_stacked(x):
    """Pin the phase/bit-plane-stacked activation to the batch (DP)
    axes under GSPMD (no-op outside an active sharding_hints context).

    Lazy import: kernels must stay importable without distrib (which
    transitively imports configs -> nn -> this module).
    """
    from repro.distrib.sharding import tim_stacked_constraint
    return tim_stacked_constraint(x)


def _st_matmul_xla_fused_phases(x_q, w_q, w1, w2, i1, i2, need_t, n_max):
    """Two-phase S/T matmul with a single weight stream.

    The pos/neg phase patterns (Fig. 5b) are stacked along a fresh
    leading axis so one dot_general reads W once; the signed
    i1*p1 - i2*p2 combination is applied to the per-phase slices.

    GSPMD note: the stack axis is deliberately a NEW (unsharded) dim,
    not a concat along M.  Concatenating along the batch-sharded M dim
    lowers to a dynamic-update-slice + all-reduce materialization that
    sums the model-axis replicas of each activation shard (observed on
    XLA:CPU 0.4.x: results scaled by the model axis size).  Stacking on
    a fresh axis keeps every per-device tile local — the per-device M
    work still doubles, W stays sharded exactly as in the unfused route.
    """
    pos = jnp.where(x_q > 0, 1, 0).astype(jnp.int8)
    neg = jnp.where(x_q < 0, 1, 0).astype(jnp.int8)
    both = _constrain_stacked(jnp.stack([pos, neg], axis=0))
    out = _st_matmul_xla(both, w_q, w1, w2, 1.0, need_t, n_max)
    return i1 * out[0] - i2 * out[1]


def _st_matmul_xla_fused_bitserial(act_codes, w_q, w1, w2, step, bits,
                                   need_t, n_max):
    """Bit-serial S/T matmul with a single weight stream: all bit-planes
    stacked along a fresh leading axis (same GSPMD reasoning as the
    two-phase route), one dot_general, PCU shift applied per slice."""
    planes = _constrain_stacked(jnp.stack(
        [((act_codes >> b) & 1).astype(jnp.int8) for b in range(bits)],
        axis=0))
    out = _st_matmul_xla(planes, w_q, w1, w2, 1.0, need_t, n_max)
    acc = out[0]
    for b in range(1, bits):
        acc = acc + out[b] * float(1 << b)
    return acc * step


def _pad_packed_k(xq: jax.Array, w: TernaryWeight) -> jax.Array:
    """Pad activations along K to the packed weight's padded K (zero
    codes are inert, so pack padding never changes the product)."""
    kp = w.data.shape[0] * 4
    if kp != xq.shape[1]:
        xq = jnp.pad(xq, ((0, 0), (0, kp - xq.shape[1])))
    return xq


def _flatten_lead(x: jax.Array, w: TernaryWeight):
    """Flatten leading batch dims to a (M, K) codes matrix."""
    return x.shape[:-1], w.shape[1], x.reshape(-1, x.shape[-1])


def _dispatch_prelude(w: TernaryWeight):
    """Shared entry-point prep: vectorize the weight scales."""
    n = w.shape[1]
    return _as_vec(w.scales.pos, n), _as_vec(w.scales.neg, n)


def tim_matmul(x_q: jax.Array, w: TernaryWeight,
               i_scales: Optional[TernaryScales] = None,
               *, n_max: Optional[int] = None,
               impl: str = "auto", fused: bool = True,
               out_dtype=jnp.float32,
               block_m: int = _tk.DEFAULT_BM, block_n: int = _tk.DEFAULT_BN,
               block_k: int = _tk.DEFAULT_BK) -> jax.Array:
    """Weighted ternary matmul: (..., K) codes x TernaryWeight(K, N).

    Handles arbitrary leading batch dims, phase decomposition (fused
    single-launch by default; ``fused=False`` restores the historical
    two-launch route), packed weights (pallas/xla), and the
    ADC-saturation fidelity mode.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"

    lead, n, x2 = _flatten_lead(x_q, w)

    if impl == "ref":
        out = _ref.ternary_matmul_ref(x2, w.codes(), w.scales, i_scales,
                                      out_dtype) if n_max is None else \
            _ref.ternary_matmul_saturating_ref(x2, w.codes(), w.scales,
                                               i_scales, n_max,
                                               out_dtype=out_dtype)
        return out.reshape(lead + (n,))

    w1, w2 = _dispatch_prelude(w)
    asym_w = not w.scales.symmetric
    asym_i = i_scales is not None and not i_scales.symmetric
    need_phases = asym_i or asym_w
    # symmetric fast path never needs T; any asymmetric weight does.
    need_t = asym_w

    def run(xq, i1):
        if impl == "pallas":
            xq = _pad_packed_k(xq, w) if w.packed else xq
            return _tk.tim_matmul_pallas(
                xq, w.data, w1, w2, jnp.asarray(i1), packed=w.packed,
                need_t=need_t, n_max=n_max, block_m=block_m,
                block_n=block_n, block_k=block_k, out_dtype=out_dtype,
                interpret=pallas_interpret())[..., :n]
        wq = w.codes()
        return _st_matmul_xla(xq, wq, w1, w2, jnp.asarray(
            i1, jnp.float32), need_t, n_max).astype(out_dtype)

    if not need_phases:
        i1 = i_scales.pos if i_scales is not None else 1.0
        out = run(x2, i1)
    else:
        # two-phase execution (paper Fig. 5b): non-negative wordline
        # patterns disambiguate the W1/W2 scale per product.
        i1 = i_scales.pos if i_scales is not None else 1.0
        i2 = i_scales.neg if i_scales is not None else 1.0
        if fused and impl == "pallas":
            xf = _pad_packed_k(x2, w) if w.packed else x2
            out = _tk.tim_matmul_fused_pallas(
                xf, w.data, w1, w2, jnp.asarray(i1), jnp.asarray(i2),
                packed=w.packed, need_t=need_t, n_max=n_max,
                block_m=block_m, block_n=block_n, block_k=block_k,
                out_dtype=out_dtype, interpret=pallas_interpret())[..., :n]
        elif fused:  # impl == 'xla'
            out = _st_matmul_xla_fused_phases(
                x2, w.codes(), w1, w2,
                jnp.asarray(i1, jnp.float32), jnp.asarray(i2, jnp.float32),
                need_t, n_max).astype(out_dtype)
        else:
            pos = jnp.where(x2 > 0, 1, 0).astype(jnp.int8)
            neg = jnp.where(x2 < 0, 1, 0).astype(jnp.int8)
            out = run(pos, i1) - run(neg, i2)

    return out.reshape(lead + (n,))


def tim_matmul_bitserial(act_codes: jax.Array, act_step: jax.Array,
                         w: TernaryWeight, bits: int,
                         *, n_max: Optional[int] = None,
                         impl: str = "auto", fused: bool = True,
                         out_dtype=jnp.float32,
                         block_m: int = _tk.DEFAULT_BM,
                         block_n: int = _tk.DEFAULT_BN,
                         block_k: int = _tk.DEFAULT_BK) -> jax.Array:
    """Bit-serial unsigned activations (WRPN 2-bit) x ternary weights.

    ``fused=True`` (default) applies every bit-plane against a single
    weight stream; ``fused=False`` restores the historical one-launch-
    per-plane route (the parity oracle).
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"

    if impl != "ref" and fused:
        lead, n, a2 = _flatten_lead(act_codes, w)
        w1, w2 = _dispatch_prelude(w)
        need_t = not w.scales.symmetric
        if impl == "pallas":
            if w.packed:
                a2 = _pad_packed_k(a2, w)
            out = _tk.tim_matmul_bitserial_fused_pallas(
                a2, w.data, w1, w2, jnp.asarray(act_step),
                bits=bits, packed=w.packed, need_t=need_t, n_max=n_max,
                block_m=block_m, block_n=block_n, block_k=block_k,
                out_dtype=out_dtype, interpret=pallas_interpret())[..., :n]
        else:
            out = _st_matmul_xla_fused_bitserial(
                a2, w.codes(), w1, w2,
                jnp.asarray(act_step, jnp.float32), bits, need_t,
                n_max).astype(out_dtype)
        return out.reshape(lead + (n,))

    acc = None
    for b in range(bits):
        plane = ((act_codes >> b) & 1).astype(jnp.int8)
        part = tim_matmul(plane, w, None, n_max=n_max, impl=impl,
                          fused=False, out_dtype=out_dtype)
        part = part * (2.0 ** b)
        acc = part if acc is None else acc + part
    return (acc * act_step).astype(out_dtype)


# ---------------------------------------------------------------------------
# HBM weight-traffic accounting (consumed by benchmarks/kernel_bench.py
# and the fused-kernel tests).
# ---------------------------------------------------------------------------

def weight_stream_stats(m: int, w: TernaryWeight,
                        i_scales: Optional[TernaryScales] = None,
                        *, bits: Optional[int] = None, fused: bool = True,
                        block_m: int = _tk.DEFAULT_BM) -> dict:
    """Analytic HBM weight-byte traffic for one matmul of M rows.

    Each launch streams the full weight matrix once per M-grid step
    (the K x N tile grid revisits every W tile for each row-block i).
    The fused kernels always issue exactly one launch; the historical
    route issues one per phase (two-phase) and, bit-serially, one per
    bit-plane *times* the per-plane phase count.
    """
    asym_w = not w.scales.symmetric
    asym_i = i_scales is not None and not i_scales.symmetric
    if bits is None:
        launches = 2 if (asym_w or asym_i) else 1
    else:
        # historical bit-serial: each plane pays the full tim_matmul
        # dispatch, including a (degenerate, all-zero) negative phase
        # when the weights are asymmetric.
        launches = bits * (2 if asym_w else 1)
    if fused:
        launches = 1
    m_steps = -(-m // _tk.block_rows(m, block_m))
    bytes_per_stream = w.nbytes_hbm * m_steps
    return {
        "launches": launches,
        "weight_bytes_per_stream": bytes_per_stream,
        "weight_bytes_streamed": launches * bytes_per_stream,
    }


def bitserial_pass_ratio(draft_bits: int, target_bits: int) -> float:
    """Compute-cost ratio of a ``draft_bits``-wide bit-serial VMM to a
    ``target_bits``-wide one over the same weight tiles.

    Bit-serial activation quantization lowers one tile pass per
    activation bit-plane (the PR-2 act-bits crossover: int2 runs half
    the passes of int4 over identical ternary codes), so per-token
    compute scales linearly in the width.  benchmarks/roofline.py uses
    this to price speculative-draft FLOPs at the cheap-encoding rate.
    """
    if draft_bits < 1 or target_bits < 1:
        raise ValueError((draft_bits, target_bits))
    return draft_bits / target_bits
