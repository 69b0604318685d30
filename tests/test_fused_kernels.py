"""Parity matrix for the fused single-launch TiM kernels (ISSUE-1).

Sweeps pallas(interpret) vs xla vs ref across
{unweighted, symmetric, asymmetric-weights, asymmetric-inputs} x
{packed, unpacked} x ragged shapes, and asserts the fused two-phase
output is numerically *identical* to the historical two-launch path.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ternary import (
    TernaryScales, quantize_act_ternary, quantize_act_unsigned,
)
from repro.core.weights import ternarize_weight
from repro.kernels import ops, ref

# ragged on purpose: M/K/N not multiples of the 128/256/512 block sizes
SHAPES = [
    (5, 130, 48),
    (3, 20, 7),
    (17, 300, 130),
]

# encoding cases: (weight encoding, asymmetric input scales?)
CASES = [
    ("unweighted", False),
    ("symmetric", False),
    ("asymmetric", False),   # asymmetric weights -> two-phase + T pass
    ("symmetric", True),     # asymmetric inputs  -> two-phase, no T pass
    ("asymmetric", True),    # both asymmetric    -> two-phase + T pass
]


def _case(m, k, n, enc, asym_inputs, pack, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    qx, sx = quantize_act_ternary(x)
    if asym_inputs:
        sx = TernaryScales(jnp.float32(0.75), jnp.float32(0.35), sym=False)
    tw = ternarize_weight(w, enc, per_channel=True, pack=pack)
    return tw, qx, sx


def _dyadic_case(m, k, n, enc, asym_inputs, pack, seed=0):
    """Like _case but with low-mantissa (dyadic-ish) scales: every
    epilogue product is exactly representable in f32, so the result is
    independent of the compiler's mul/sub association (FMA contraction)
    and bit-for-bit equality between launch topologies is well-defined.
    """
    from repro.core.weights import TernaryWeight

    tw, qx, sx = _case(m, k, n, enc, asym_inputs, pack, seed)
    idx = np.arange(n)
    w1 = (1.0 + 0.5 * (idx % 2)) * 2.0 ** ((idx % 5) - 2)
    if enc == "asymmetric":
        w2 = (1.0 + 0.5 * ((idx + 1) % 2)) * 2.0 ** (((idx + 2) % 5) - 2)
        sym = False
    else:
        w2, sym = w1, tw.scales.symmetric
    scales = TernaryScales(jnp.asarray(w1, jnp.float32),
                           jnp.asarray(w2, jnp.float32), sym)
    tw = TernaryWeight(tw.data, scales, tw.packed, tw.k_dim)
    if asym_inputs:
        sx = TernaryScales(jnp.float32(0.75), jnp.float32(0.375), sym=False)
    return tw, qx, sx


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("enc,asym_inputs", CASES)
@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_matches_ref(shape, enc, asym_inputs, pack, impl):
    m, k, n = shape
    tw, qx, sx = _case(m, k, n, enc, asym_inputs, pack)
    want = ref.ternary_matmul_ref(qx, tw.codes(), tw.scales, sx)
    got = ops.tim_matmul(qx, tw, sx, impl=impl, fused=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("enc,asym_inputs", [c for c in CASES
                                             if c[0] == "asymmetric" or c[1]])
@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_two_phase_bit_identical_to_two_launch(shape, enc, asym_inputs,
                                                     pack, impl):
    # exact-product scales: bit-for-bit equality is well-defined (no
    # rounding anywhere), so any structural divergence — wrong phase
    # mask, swapped scale, missing T pass — fails loudly
    m, k, n = shape
    tw, qx, sx = _dyadic_case(m, k, n, enc, asym_inputs, pack, seed=1)
    fused = ops.tim_matmul(qx, tw, sx, impl=impl, fused=True)
    two = ops.tim_matmul(qx, tw, sx, impl=impl, fused=False)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(two))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("enc,asym_inputs", [c for c in CASES
                                             if c[0] == "asymmetric" or c[1]])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_two_phase_parity_arbitrary_scales(shape, enc, asym_inputs,
                                                 impl):
    # arbitrary (gaussian-derived) scales: identical int accumulators,
    # identical f32 epilogue expression — the only freedom left to the
    # compiler is FMA-contracting the final mul/sub, worth < 2 ulp
    m, k, n = shape
    tw, qx, sx = _case(m, k, n, enc, asym_inputs, pack=False, seed=1)
    fused = np.asarray(ops.tim_matmul(qx, tw, sx, impl=impl, fused=True))
    two = np.asarray(ops.tim_matmul(qx, tw, sx, impl=impl, fused=False))
    np.testing.assert_allclose(fused, two, rtol=3e-6, atol=3e-6)


@pytest.mark.parametrize("enc", ["symmetric", "asymmetric"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_saturating_matches_oracle(enc, impl):
    m, k, n = 6, 96, 40
    tw, qx, sx = _case(m, k, n, enc, enc == "asymmetric", pack=False, seed=2)
    want = ref.ternary_matmul_saturating_ref(qx, tw.codes(), tw.scales, sx,
                                             n_max=8)
    got = ops.tim_matmul(qx, tw, sx, impl=impl, n_max=8, fused=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(6, 96, 40), (5, 130, 48), (3, 20, 7)])
@pytest.mark.parametrize("enc,asym_inputs", [
    ("symmetric", False),
    ("asymmetric", False),
    ("symmetric", True),
    ("asymmetric", True),
])
@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_saturating_packed_matches_oracle(shape, enc, asym_inputs, fused,
                                          impl):
    """Packed weights + ADC fidelity (the combination that used to raise
    NotImplementedError on pallas): the 2-bit in-VMEM unpack composes
    with the per-L-block clamp on every impl, fused and unfused, across
    the symmetric/asymmetric x ragged-shape matrix."""
    m, k, n = shape
    tw, qx, sx = _case(m, k, n, enc, asym_inputs, pack=True, seed=2)
    want = ref.ternary_matmul_saturating_ref(qx, tw.codes(), tw.scales, sx,
                                             n_max=8)
    got = ops.tim_matmul(qx, tw, sx, impl=impl, n_max=8, fused=fused)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_bitserial_saturating_packed(pack, impl):
    """Bit-serial + n_max (+ packed): fused matches the historical
    one-launch-per-plane route, which clamps each plane separately."""
    m, k, n = 5, 64, 24
    rng = np.random.default_rng(8)
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    x = jnp.asarray(np.abs(rng.normal(size=(m, k))).astype(np.float32))
    qa, step = quantize_act_unsigned(x, 2)
    tw = ternarize_weight(w, "asymmetric", per_channel=True, pack=pack)
    got = ops.tim_matmul_bitserial(qa, step, tw, bits=2, n_max=8,
                                   impl=impl, fused=True)
    want = ops.tim_matmul_bitserial(qa, step, tw, bits=2, n_max=8,
                                    impl=impl, fused=False)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("enc", ["unweighted", "symmetric", "asymmetric"])
@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_bitserial_matches_dense(shape, enc, pack, impl):
    m, k, n = shape
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    x = jnp.asarray(np.abs(rng.normal(size=(m, k))).astype(np.float32))
    qa, step = quantize_act_unsigned(x, 2)
    tw = ternarize_weight(w, enc, per_channel=True, pack=pack)
    want = (qa.astype(jnp.float32) * step) @ tw.dequantize()
    got = ops.tim_matmul_bitserial(qa, step, tw, bits=2, impl=impl,
                                   fused=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    unfused = ops.tim_matmul_bitserial(qa, step, tw, bits=2, impl=impl,
                                       fused=False)
    np.testing.assert_allclose(got, unfused, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_bitserial_4bit_matches_dense(pack, impl):
    """bits=4 (the act_mode='int4' serving point) against the dense
    oracle: 16-level codes, exact PCU shifts, one weight stream."""
    m, k, n = 5, 130, 48
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    x = jnp.asarray(np.abs(rng.normal(size=(m, k))).astype(np.float32))
    qa, step = quantize_act_unsigned(x, 4)
    assert int(qa.max()) > 3, "4-bit codes should exceed the 2-bit range"
    tw = ternarize_weight(w, "asymmetric", per_channel=True, pack=pack)
    want = (qa.astype(jnp.float32) * step) @ tw.dequantize()
    got = ops.tim_matmul_bitserial(qa, step, tw, bits=4, impl=impl,
                                   fused=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    unfused = ops.tim_matmul_bitserial(qa, step, tw, bits=4, impl=impl,
                                       fused=False)
    np.testing.assert_allclose(got, unfused, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_batched_leading_dims(impl):
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(2, 3, 64)).astype(np.float32))
    qx, sx = quantize_act_ternary(x)
    tw = ternarize_weight(w, "asymmetric", per_channel=True)
    got = ops.tim_matmul(qx, tw, sx, impl=impl, fused=True)
    assert got.shape == (2, 3, 32)
    flat = ops.tim_matmul(qx.reshape(6, 64), tw, sx, impl=impl, fused=True)
    np.testing.assert_allclose(np.asarray(got).reshape(6, 32), flat,
                               rtol=1e-5)


def test_weight_stream_reduction():
    # acceptance: fused two-phase streams each weight tile once — at
    # least a 1.5x HBM weight-byte reduction on asymmetric shapes
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=(512, 256)).astype(np.float32))
    tw = ternarize_weight(w, "asymmetric", per_channel=True)
    fused = ops.weight_stream_stats(64, tw, None, fused=True)
    two = ops.weight_stream_stats(64, tw, None, fused=False)
    assert fused["launches"] == 1 and two["launches"] == 2
    ratio = two["weight_bytes_streamed"] / fused["weight_bytes_streamed"]
    assert ratio >= 1.5
    # bit-serial with asymmetric weights: 2 phases x 2 planes collapse
    bs_two = ops.weight_stream_stats(64, tw, None, bits=2, fused=False)
    bs_fused = ops.weight_stream_stats(64, tw, None, bits=2, fused=True)
    assert bs_two["weight_bytes_streamed"] \
        == 4 * bs_fused["weight_bytes_streamed"]
    # the win grows linearly with bits: int4 -> 2 phases x 4 planes
    bs4_two = ops.weight_stream_stats(64, tw, None, bits=4, fused=False)
    bs4_fused = ops.weight_stream_stats(64, tw, None, bits=4, fused=True)
    assert bs4_two["weight_bytes_streamed"] \
        == 8 * bs4_fused["weight_bytes_streamed"]
    # symmetric weights + symmetric inputs never needed a second stream
    tws = ternarize_weight(w, "symmetric", per_channel=True)
    assert ops.weight_stream_stats(64, tws, None, fused=False)["launches"] == 1


def test_serve_weight_stream_report():
    from repro.configs.base import ArchConfig
    from repro.nn.linear import TernaryPolicy
    from repro.serve.engine import weight_stream_report

    rng = np.random.default_rng(6)
    tw = ternarize_weight(
        jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32)),
        "asymmetric", per_channel=True)
    params = {"layer": {"q": {"w": tw}, "o": {"w": tw}}}
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=8,
                     n_heads=1, n_kv_heads=1, d_ff=16, vocab_size=32,
                     ternary=TernaryPolicy(enabled=True,
                                           encoding="asymmetric",
                                           act_mode="ternary"))
    rep = weight_stream_report(params, cfg, decode_batch=8)
    assert rep["weight_bytes_resident"] == 2 * tw.nbytes_hbm
    assert rep["weight_bytes_streamed_unfused"] \
        == 2 * rep["weight_bytes_streamed_fused"]
    # weight-only serving never launches a TiM kernel: no fictitious win
    cfg_wo = dataclasses.replace(cfg, ternary=cfg.ternary.replace(
        act_mode="none"))
    rep_wo = weight_stream_report(params, cfg_wo, decode_batch=8)
    assert rep_wo["weight_bytes_streamed_unfused"] \
        == rep_wo["weight_bytes_streamed_fused"]
    # int4 bit-serial on asymmetric weights: 2 phases x 4 planes -> 8x
    cfg_i4 = dataclasses.replace(cfg, ternary=cfg.ternary.replace(
        act_mode="int4"))
    rep_i4 = weight_stream_report(params, cfg_i4, decode_batch=8)
    assert rep_i4["weight_bytes_streamed_unfused"] \
        == 8 * rep_i4["weight_bytes_streamed_fused"]


def test_policy_act_bits_parsing():
    from repro.nn.linear import TernaryPolicy

    assert TernaryPolicy(act_mode="none").act_bits is None
    assert TernaryPolicy(act_mode="ternary").act_bits is None
    assert TernaryPolicy(act_mode="int2").act_bits == 2
    assert TernaryPolicy(act_mode="int4").act_bits == 4
    with pytest.raises(ValueError):
        TernaryPolicy(act_mode="int1")      # 1-bit: use ternary instead
    with pytest.raises(ValueError):
        TernaryPolicy(act_mode="int8")      # codes would overflow int8
    with pytest.raises(ValueError):
        TernaryPolicy(act_mode="fp8")


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_out_dtypes(out_dtype, impl):
    tw, qx, sx = _case(8, 128, 64, "asymmetric", False, pack=False, seed=7)
    got = ops.tim_matmul(qx, tw, sx, impl=impl, fused=True,
                         out_dtype=out_dtype)
    assert got.dtype == out_dtype
    # the unfused two-launch route rounds each bf16 phase before
    # subtracting (the fused routes round once at the end and are
    # strictly more accurate), so allow a couple of bf16 ulps *of the
    # phase magnitude* — the pre-cancellation intermediates, not the
    # possibly-tiny result
    want = ops.tim_matmul(qx, tw, sx, impl=impl, fused=False,
                          out_dtype=out_dtype)
    want_f32 = np.asarray(want.astype(jnp.float32))
    if out_dtype == jnp.bfloat16:
        ref_f32 = np.asarray(ref.ternary_matmul_ref(qx, tw.codes(),
                                                    tw.scales, sx))
        atol = 4 * 2.0 ** -8 * np.abs(ref_f32).max()
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   want_f32, atol=atol)
    else:
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   want_f32, rtol=1e-5, atol=1e-5)
