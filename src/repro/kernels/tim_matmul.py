"""Pallas TPU kernels for TiM ternary matrix multiplication.

This is the TPU-native re-expression of the TiM tile (paper §III-B/C).
The analog bitline trick — accumulate +1 products on BL (count n) and -1
products on BLB (count k) — becomes a *sign/magnitude decomposition* that
the MXU executes as int8 matmuls:

    S = X_q @ W_q        (signed codes)      = n - k
    T = |X_q| @ |W_q|    (magnitude codes)   = n + k
      ⇒ n = (T + S) / 2,  k = (T - S) / 2

so any weighted ternary output is an epilogue over S and T:

    out = I * [ W1*n - W2*k ] = I * [ (W1-W2)/2 * T + (W1+W2)/2 * S ]

For symmetric encodings (W1 == W2) the T matmul vanishes and one int8
MXU pass suffices — the fast path (``tim_matmul_pallas``).

Fused multi-pass kernels
------------------------
The paper's hardware runs asymmetric encodings in two phases (Fig. 5b:
apply the positive input mask, then the negative mask) and multi-bit
activations bit-serially (§III-C: one access per bit-plane).  A naive
port pays for that fidelity at the *launch* level — one ``pallas_call``
per phase / per bit-plane, each re-streaming the full weight matrix
from HBM.  The fused kernels here collapse all passes into a single
launch:

* ``tim_matmul_fused_pallas`` — reads each X/W tile into VMEM **once**;
  the phase masks are derived in-kernel from the signed codes
  (``pos = max(x, 0)``, ``neg = max(-x, 0)``), the 2–4 int8 MXU passes
  per tile (S/T × phase) accumulate into per-phase VMEM scratch, and
  the signed ``i1·p1 − i2·p2`` epilogue runs once at ``kk == nsteps-1``,
  in f32 with one rounding to ``out_dtype`` (the fused XLA route's
  arithmetic; the two-launch path rounds each phase first), at half
  the HBM weight traffic.

* ``tim_matmul_bitserial_fused_pallas`` — applies all ``bits``
  bit-planes of an activation tile against a single W read; the PCU
  shifter becomes an exact int32 ``<< b`` folded into the accumulation,
  and the scale epilogue runs once.  HBM weight traffic drops by
  ``bits``× (and by ``2·bits``× vs the naive route, which also paid an
  all-zero negative phase per plane).

Every kernel takes dense int8 codes or TPC-style 2-bit packed weights
(static ``packed`` flag; the unpack runs on the VPU after the
4x-smaller tile is already in VMEM).

Mosaic constraints
------------------
The TPU vector unit has no 8-bit max/abs/shift, so tiles are widened to
int32 for all elementwise work and narrowed back to int8 only as MXU
operands.  A packed tile unpacks *field-major*: byte row r of a
``(bk/4, bn)`` tile holds codes ``4r..4r+3``, and field j of every byte
goes to rows ``[j*bk/4, (j+1)*bk/4)`` of the unpacked tile — four
whole-tile planes stacked along sublanes, never a sublane interleave.
The wrapper permutes each ``bk``-wide K block of X to the same order
(``_field_major``), so the contraction is unchanged.

Fidelity mode (``n_max``) reproduces the 3-bit flash ADC: counts are
clamped per L=16-row block (per phase / per plane, exactly as the tile
hardware saturates each access) before digital accumulation.  The K
step drops to one 128-lane block (the narrowest the TPU tiling admits)
and the kernel clamps each of its L=16 sub-blocks separately: X is
masked to one sub-block per MXU pass.  This is deliberately *not* a
performance path — it exists to validate the paper's accuracy claims,
while the fast path is what serving uses.  It composes with packed
weights: the sub-block mask follows the field-major column order.

VMEM tiling: X tile (bm, bk) int8, W tile (bk, bn) int8, up to four
int32 accumulators (bm, bn) in VMEM scratch.  bm/bn default to 128/256
— MXU-aligned (multiples of 128 in the lane dim, int8 native) — and
bk=512 keeps the fused working set at
  128*512 + 512*256 + 4*128*256*4 B ≈ 0.7 MB ≪ 16 MB VMEM,
leaving headroom for double-buffered HBM→VMEM pipelining.  The int32
views of a tile are transient values, not scratch.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import CODES_PER_BYTE

DEFAULT_BM = 128
DEFAULT_BN = 256
DEFAULT_BK = 512
L_BLOCK = 16
# K step under the ADC clamp: one lane-aligned block of 128 // L_BLOCK
# sub-blocks, each clamped on its own
N_MAX_BK = 128
# int8 operands tile as (32, 128) on the TPU: M tiles round up to it
M_ALIGN = 32

# Static VMEM contract, machine-checked by repro.analysis (timcheck's
# pallas-contract checker; docs/static-analysis.md §vmem-budgets).
# ``symbols`` bind the block-shape names used in the BlockSpecs at the
# DEFAULT_* tile sizes (wk = the unpacked worst case — the packed
# kernels stream bk//4 weight bytes and come in under this estimate);
# ``budgets`` cap each kernel's estimated resident footprint (input +
# output + scratch blocks, f32-priced).  The fused two-phase kernel is
# the high-water mark at ~1.4 MiB.
TIMCHECK_VMEM = {
    "symbols": {"bm": 128, "bn": 256, "bk": 512, "wk": 512},
    "budgets": {
        "_tim_kernel": 2 * 2 ** 20,
        "_tim_kernel_fused": 2 * 2 ** 20,
        "_tim_kernel_bitserial": 2 * 2 ** 20,
    },
}


def _compiler_params():
    # grid is always (M/bm, N/bn, K/bk) with K innermost-accumulating
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _dot_i32(a, b):
    """int8 MXU pass over int32-held codes (|codes| <= 127).  The
    precision is pinned: an ambient ``default_matmul_precision`` of
    'highest' would otherwise ask Mosaic for an f32 contraction, which
    it refuses for int8 operands."""
    return jax.lax.dot_general(
        a.astype(jnp.int8), b.astype(jnp.int8), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32)


def _epilogue(s, t, w1, w2, i1, out_dtype):
    """out = i1 * (c_s * S + c_t * T) with per-column ternary scales."""
    sf = s.astype(jnp.float32)
    c_s = (w1 + w2) * 0.5
    if t is None:
        return (i1 * c_s * sf).astype(out_dtype)
    tf = t.astype(jnp.float32)
    c_t = (w1 - w2) * 0.5
    return (i1 * (c_s * sf + c_t * tf)).astype(out_dtype)


def _clamped_st(s, t, n_max):
    """ADC saturation for one access: clamp (n, k) at n_max, return the
    clamped counts re-expressed in the (S, T) basis.  T ± S is twice a
    non-negative count, so the halving is an exact shift."""
    n = jnp.minimum((t + s) >> 1, n_max)
    k = jnp.minimum((t - s) >> 1, n_max)
    return n - k, n + k


def _unpack2b_tile(pw):
    """(bkp, bn) uint8 -> (4*bkp, bn) int32 ternary codes, field-major:
    row ``j*bkp + r`` is field j of byte row r (K index ``4r + j``).

    Field encoding per core/packing.py: 00→0, 01→+1, 11→-1.
    """
    p = pw.astype(jnp.int32)
    planes = []
    for j in range(CODES_PER_BYTE):
        f = (p >> (2 * j)) & 0b11
        planes.append(jnp.where(f == 1, 1, jnp.where(f == 3, -1, 0)))
    return jnp.concatenate(planes, axis=0)


def _w_tile(w_ref, packed: bool):
    w = w_ref[...]
    return _unpack2b_tile(w) if packed else w.astype(jnp.int32)


def _l_blocks(x, n_max, packed: bool):
    """The X tile as the MXU sees it: whole, or — under the ADC clamp —
    one copy per L=16 K sub-block with every other column zeroed.
    Columns are in the W tile's row order (field-major when packed)."""
    if n_max is None:
        return [x]
    bk = x.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if packed:
        bkp = bk // CODES_PER_BYTE
        col = (col % bkp) * CODES_PER_BYTE + col // bkp
    blk = col // L_BLOCK
    return [jnp.where(blk == lb, x, 0) for lb in range(-(-bk // L_BLOCK))]


def _st_passes(x, w, aw, n_max, packed: bool):
    """(S, T) partials of one tile, ADC-clamped per L block if n_max."""
    s_sum = t_sum = None
    for xl in _l_blocks(x, n_max, packed):
        s = _dot_i32(xl, w)
        t = None if aw is None else _dot_i32(jnp.abs(xl), aw)
        if n_max is not None:
            s, t = _clamped_st(s, t, n_max)
        s_sum = s if s_sum is None else s_sum + s
        if t is not None:
            t_sum = t if t_sum is None else t_sum + t
    return s_sum, t_sum


def _tim_kernel(x_ref, w_ref, w1_ref, w2_ref, i1_ref, o_ref,
                s_acc, t_acc, *, nsteps: int, need_t: bool,
                n_max: Optional[int], packed: bool, out_dtype):
    """Grid (M/bm, N/bn, K/bk); K innermost (arbitrary semantics)."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        if need_t:
            t_acc[...] = jnp.zeros_like(t_acc)

    x = x_ref[...].astype(jnp.int32)
    w = _w_tile(w_ref, packed)
    s, t = _st_passes(x, w, jnp.abs(w) if need_t else None, n_max, packed)
    s_acc[...] += s
    if need_t:
        t_acc[...] += t

    @pl.when(kk == nsteps - 1)
    def _done():
        w1 = w1_ref[...].astype(jnp.float32)
        w2 = w2_ref[...].astype(jnp.float32)
        i1 = i1_ref[0].astype(jnp.float32)
        t_fin = t_acc[...] if need_t else None
        o_ref[...] = _epilogue(s_acc[...], t_fin, w1, w2, i1, out_dtype)


def _pad_dim(a, axis, mult):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _field_major(x, bk: int):
    """Permute each bk-wide K block of X into the field-major order of
    an unpacked packed-weight tile (column ``j*bk/4 + r`` = K ``4r+j``)."""
    m, kp = x.shape
    x = x.reshape(m, kp // bk, bk // CODES_PER_BYTE, CODES_PER_BYTE)
    return x.transpose(0, 1, 3, 2).reshape(m, kp)


class _TilePlan(NamedTuple):
    """Shared tiling for every TiM kernel wrapper: clamped block sizes,
    block-padded operands, the (M, N, K) grid, and the block specs."""

    x: jax.Array
    w: jax.Array
    w1: jax.Array
    w2: jax.Array
    bm: int
    bn: int
    grid: tuple
    in_specs: list
    out_spec: "pl.BlockSpec"
    out_shape: tuple


def _tile_plan(x, w_data, w1, w2, *, packed: bool, n_max: Optional[int],
               block_m: int, block_n: int, block_k: int) -> _TilePlan:
    m, kdim = x.shape
    n = w_data.shape[1]
    bm = block_rows(m, block_m)
    bk = min(N_MAX_BK if n_max is not None else block_k, kdim)
    if packed:
        bk -= bk % CODES_PER_BYTE
    bn = min(block_n, n)

    x = _pad_dim(_pad_dim(x, 0, bm), 1, bk)
    if packed:
        x = _field_major(x, bk)
    wk = bk // CODES_PER_BYTE if packed else bk
    w_data = _pad_dim(_pad_dim(w_data, 0, wk), 1, bn)
    w1 = _pad_dim(w1.reshape(1, n), 1, bn)
    w2 = _pad_dim(w2.reshape(1, n), 1, bn)
    mp, kp = x.shape
    np_ = w_data.shape[1]
    return _TilePlan(
        x=x, w=w_data, w1=w1, w2=w2, bm=bm, bn=bn,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((wk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_spec=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=(mp, np_),
    )


def block_rows(m: int, block_m: int) -> int:
    """M tile: ``block_m`` rows, or all M rounded up to the int8 tile."""
    return min(block_m, -(-m // M_ALIGN) * M_ALIGN)


def _acc_shapes(plan: _TilePlan, flags) -> list:
    """VMEM int32 accumulators; (1, 1) dummies keep signatures uniform
    for the accumulators a configuration doesn't need."""
    return [pltpu.VMEM((plan.bm, plan.bn) if on else (1, 1), jnp.int32)
            for on in flags]


def _check_k(kdim, w_data, packed: bool):
    rows = w_data.shape[0] * (CODES_PER_BYTE if packed else 1)
    assert rows == kdim, (kdim, w_data.shape, packed)


@functools.partial(
    jax.jit,
    static_argnames=("packed", "need_t", "n_max", "block_m", "block_n",
                     "block_k", "out_dtype", "interpret"))
def tim_matmul_pallas(x_q: jax.Array, w_data: jax.Array,
                      w1: jax.Array, w2: jax.Array, i1: jax.Array,
                      *, packed: bool = False, need_t: bool,
                      n_max: Optional[int] = None,
                      block_m: int = DEFAULT_BM, block_n: int = DEFAULT_BN,
                      block_k: int = DEFAULT_BK,
                      out_dtype=jnp.float32, interpret: bool = False
                      ) -> jax.Array:
    """Single-phase ternary matmul.  x_q: (M, K) int8 codes (phase-masked
    upstream if asymmetric inputs), w_data: (K, N) int8 codes or
    (K//4, N) uint8 codes packed along K, w1/w2: (N,) f32
    positive/negative weight scales, i1: scalar input scale.
    """
    m, kdim = x_q.shape
    n = w_data.shape[1]
    _check_k(kdim, w_data, packed)
    if n_max is not None:
        need_t = True

    plan = _tile_plan(x_q, w_data, w1, w2, packed=packed, n_max=n_max,
                      block_m=block_m, block_n=block_n, block_k=block_k)
    kernel = functools.partial(
        _tim_kernel, nsteps=plan.grid[2], need_t=need_t, n_max=n_max,
        packed=packed, out_dtype=out_dtype)

    out = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=plan.in_specs,
        out_specs=plan.out_spec,
        out_shape=jax.ShapeDtypeStruct(plan.out_shape, out_dtype),
        scratch_shapes=_acc_shapes(plan, (True, need_t)),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="tim_matmul",
    )(plan.x, plan.w, plan.w1, plan.w2,
      jnp.reshape(i1, (1,)).astype(jnp.float32))
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Fused two-phase kernel: both phases of the paper's asymmetric execution
# (Fig. 5b) against a single HBM read of each X/W tile.
# ---------------------------------------------------------------------------

def _tim_kernel_fused(x_ref, w_ref, w1_ref, w2_ref, i12_ref, o_ref,
                      sp_acc, tp_acc, sn_acc, tn_acc, *, nsteps: int,
                      need_t: bool, n_max: Optional[int], packed: bool,
                      out_dtype):
    """Grid (M/bm, N/bn, K/bk); K innermost (arbitrary semantics).

    The signed X tile is read once; the non-negative phase patterns of
    Fig. 5b are derived in-register (pos = max(x, 0), neg = max(-x, 0))
    and each phase's S (and T, for asymmetric weights) partials go to
    their own VMEM accumulator.  The signed combination i1*p1 - i2*p2
    happens once, in the epilogue.  Under the ADC clamp each phase is a
    separate hardware access, so each is clamped on its own.
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        sp_acc[...] = jnp.zeros_like(sp_acc)
        sn_acc[...] = jnp.zeros_like(sn_acc)
        if need_t:
            tp_acc[...] = jnp.zeros_like(tp_acc)
            tn_acc[...] = jnp.zeros_like(tn_acc)

    x = x_ref[...].astype(jnp.int32)
    w = _w_tile(w_ref, packed)
    aw = jnp.abs(w) if need_t else None
    for xp, s_acc, t_acc in ((jnp.maximum(x, 0), sp_acc, tp_acc),
                             (jnp.maximum(-x, 0), sn_acc, tn_acc)):
        s, t = _st_passes(xp, w, aw, n_max, packed)
        s_acc[...] += s
        if need_t:
            t_acc[...] += t

    @pl.when(kk == nsteps - 1)
    def _done():
        w1 = w1_ref[...].astype(jnp.float32)
        w2 = w2_ref[...].astype(jnp.float32)
        i1 = i12_ref[0].astype(jnp.float32)
        i2 = i12_ref[1].astype(jnp.float32)
        tp_fin = tp_acc[...] if need_t else None
        tn_fin = tn_acc[...] if need_t else None
        # the phases combine in f32 and round to out_dtype once — the
        # fused XLA route's arithmetic (the two-launch route rounds each
        # phase to out_dtype first, a bf16 ulp of the phase apart)
        p1 = _epilogue(sp_acc[...], tp_fin, w1, w2, i1, jnp.float32)
        p2 = _epilogue(sn_acc[...], tn_fin, w1, w2, i2, jnp.float32)
        o_ref[...] = (p1 - p2).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("packed", "need_t", "n_max", "block_m", "block_n",
                     "block_k", "out_dtype", "interpret"))
def tim_matmul_fused_pallas(x_q: jax.Array, w_data: jax.Array,
                            w1: jax.Array, w2: jax.Array,
                            i1: jax.Array, i2: jax.Array,
                            *, packed: bool, need_t: bool,
                            n_max: Optional[int] = None,
                            block_m: int = DEFAULT_BM,
                            block_n: int = DEFAULT_BN,
                            block_k: int = DEFAULT_BK,
                            out_dtype=jnp.float32,
                            interpret: bool = False) -> jax.Array:
    """Fused two-phase ternary matmul: one launch, one weight stream.

    x_q: (M, K) *signed* int8 codes; w_data: (K, N) int8 codes or
    (K//4, N) uint8 packed codes; w1/w2: (N,) weight scales; i1/i2:
    scalar positive/negative input scales.  Computes
    ``i1 * phase(pos) - i2 * phase(neg)`` in a single ``pallas_call``.
    """
    m, kdim = x_q.shape
    n = w_data.shape[1]
    _check_k(kdim, w_data, packed)
    if n_max is not None:
        need_t = True

    plan = _tile_plan(x_q, w_data, w1, w2, packed=packed, n_max=n_max,
                      block_m=block_m, block_n=block_n, block_k=block_k)
    kernel = functools.partial(
        _tim_kernel_fused, nsteps=plan.grid[2], need_t=need_t, n_max=n_max,
        packed=packed, out_dtype=out_dtype)

    i12 = jnp.stack([jnp.reshape(i1, ()), jnp.reshape(i2, ())]
                    ).astype(jnp.float32)
    out = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=plan.in_specs,
        out_specs=plan.out_spec,
        out_shape=jax.ShapeDtypeStruct(plan.out_shape, out_dtype),
        scratch_shapes=_acc_shapes(plan, (True, need_t, True, need_t)),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="tim_matmul_fused",
    )(plan.x, plan.w, plan.w1, plan.w2, i12)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Fused bit-serial kernel: every bit-plane of the activation tile applied
# against a single W read; the PCU shift is an exact int32 << b folded
# into the accumulation (§III-C, one launch instead of `bits`).
# ---------------------------------------------------------------------------

def _tim_kernel_bitserial(x_ref, w_ref, w1_ref, w2_ref, step_ref, o_ref,
                          s_acc, t_acc, *, nsteps: int, bits: int,
                          need_t: bool, n_max: Optional[int], packed: bool,
                          out_dtype):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        if need_t:
            t_acc[...] = jnp.zeros_like(t_acc)

    x = x_ref[...].astype(jnp.int32)      # unsigned codes < 2**bits
    w = _w_tile(w_ref, packed)
    aw = jnp.abs(w) if need_t else None
    for b in range(bits):
        # every bit-plane is its own hardware access: clamped per plane
        s, t = _st_passes((x >> b) & 1, w, aw, n_max, packed)
        s_acc[...] += s * (1 << b)
        if need_t:
            t_acc[...] += t * (1 << b)

    @pl.when(kk == nsteps - 1)
    def _done():
        w1 = w1_ref[...].astype(jnp.float32)
        w2 = w2_ref[...].astype(jnp.float32)
        step = step_ref[0].astype(jnp.float32)
        t_fin = t_acc[...] if need_t else None
        o_ref[...] = _epilogue(s_acc[...], t_fin, w1, w2, step, out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "packed", "need_t", "n_max", "block_m",
                     "block_n", "block_k", "out_dtype", "interpret"))
def tim_matmul_bitserial_fused_pallas(act_codes: jax.Array,
                                      w_data: jax.Array,
                                      w1: jax.Array, w2: jax.Array,
                                      act_step: jax.Array,
                                      *, bits: int, packed: bool,
                                      need_t: bool,
                                      n_max: Optional[int] = None,
                                      block_m: int = DEFAULT_BM,
                                      block_n: int = DEFAULT_BN,
                                      block_k: int = DEFAULT_BK,
                                      out_dtype=jnp.float32,
                                      interpret: bool = False) -> jax.Array:
    """Fused bit-serial matmul: all bit-planes in one launch.

    act_codes: (M, K) int8 unsigned codes in [0, 2**bits); w_data as in
    ``tim_matmul_fused_pallas``; act_step: scalar activation step size
    (folded into the epilogue, like the PCU's final scale).
    """
    m, kdim = act_codes.shape
    n = w_data.shape[1]
    _check_k(kdim, w_data, packed)
    if n_max is not None:
        need_t = True

    plan = _tile_plan(act_codes, w_data, w1, w2, packed=packed,
                      n_max=n_max, block_m=block_m, block_n=block_n,
                      block_k=block_k)
    kernel = functools.partial(
        _tim_kernel_bitserial, nsteps=plan.grid[2], bits=bits,
        need_t=need_t, n_max=n_max, packed=packed, out_dtype=out_dtype)

    out = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=plan.in_specs,
        out_specs=plan.out_spec,
        out_shape=jax.ShapeDtypeStruct(plan.out_shape, out_dtype),
        scratch_shapes=_acc_shapes(plan, (True, need_t)),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="tim_matmul_bitserial",
    )(plan.x, plan.w, plan.w1, plan.w2,
      jnp.reshape(act_step, (1,)).astype(jnp.float32))
    return out[:m, :n]
