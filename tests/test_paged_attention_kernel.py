"""Pallas paged-attention kernel parity matrix.

The in-kernel block-table gather (`kernels/paged_attention.py`, run in
interpret mode on CPU) is checked against the XLA-gather route of
``nn/attention.mixed_attention`` — the production path off-TPU and the
parity oracle everywhere — across block_size {16, 64} x decode (S=1) /
mixed (S>1, ragged) x {bf16-free f32, int8 KV + paged scales}.

Tolerance note: the oracle's online-softmax scan is a compiled
``lax.scan`` while the interpret-mode kernel is a separately lowered
program, and XLA's fusion choices differ between the two — identical
math, identical reduction *grouping* (same ``chunk_kv`` boundaries),
but one-ulp f32 differences appear data-dependently (the same effect
makes an eager re-execution of the oracle's own ops differ from the
scan).  The cross-program parity matrix therefore asserts a <= few-ulp
bound (`_ULP_TOL`, tight enough that any mask / position / gather bug
fails by orders of magnitude), while everything that IS one program is
asserted **bit-exact**:

  * gather invariance — two different random physical block placements
    of the same logical cache produce bit-identical kernel output;
  * the compacted-table entry point with identity logical_blocks /
    all-valid entries equals the plain kernel bit-for-bit;
  * the S=1 decode variant (causal term compiled out) equals the
    causal kernel bit-for-bit;
  * the live bound: against the block-grid kernel (``_paged_grid_ref``,
    every table entry fetched and masked) the kernel agrees bit for
    bit on every row, for every variant and context shape, and on a
    pool whose dead blocks are NaN it copies only live blocks.

``normalize=False`` flash partials with a single chunk match
``distrib/decode_attn._local_partial`` (the lse-merge oracle) within
the cross-program bound.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_decode_attention_pallas,
                                           paged_mixed_attention_pallas)
from repro.nn.attention import mixed_attention

B, H, HK, D = 2, 4, 2, 8
S_MAX = 128
_ULP_TOL = dict(rtol=3e-6, atol=3e-6)


def _pool_from_contiguous(k, v, block_size, seed=0):
    """Scatter a contiguous (B, S, Hk, D) cache into a block pool under
    a random physical permutation (same helper as the XLA-route matrix
    in test_paged_attention.py)."""
    rng = np.random.default_rng(seed)
    b, s = k.shape[0], k.shape[1]
    nblk = s // block_size
    nb = b * nblk + 3
    perm = rng.permutation(nb)[:b * nblk].reshape(b, nblk)
    pool_k = rng.normal(size=(nb, block_size) + k.shape[2:]) \
        .astype(np.asarray(k).dtype)
    pool_v = rng.normal(size=pool_k.shape).astype(pool_k.dtype)
    for i in range(b):
        for j in range(nblk):
            pool_k[perm[i, j]] = np.asarray(
                k[i, j * block_size:(j + 1) * block_size])
            pool_v[perm[i, j]] = np.asarray(
                v[i, j * block_size:(j + 1) * block_size])
    return (jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(perm, jnp.int32))


@pytest.fixture(scope="module")
def kv():
    rng = np.random.default_rng(7)
    k = jnp.asarray(rng.normal(size=(B, S_MAX, HK, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S_MAX, HK, D)).astype(np.float32))
    return k, v


@pytest.fixture(scope="module")
def kv_int8(kv):
    """int8 codes + per-(token, head) bf16 scales (the kv8 cache)."""
    from repro.models.transformer import _kv_quantize
    k, v = kv
    kq, ks = _kv_quantize(k)
    vq, vs = _kv_quantize(v)
    return kq, ks, vq, vs


def _case(kv, block_size, chunk_kv, q_offset, n_new, seed=1):
    k, v = kv
    rng = np.random.default_rng(seed)
    sq = int(max(n_new))
    q = jnp.asarray(rng.normal(size=(B, sq, H, D)).astype(np.float32))
    offs = jnp.asarray(q_offset, jnp.int32)
    nnew = jnp.asarray(n_new, jnp.int32)
    pk, pv, tables = _pool_from_contiguous(k, v, block_size, seed)
    return q, offs, nnew, pk, pv, tables


# -- kernel vs the XLA-gather oracle (block_size x S x offsets) -------------

@pytest.mark.parametrize("block_size,chunk_kv", [(16, 32), (64, 64),
                                                 (16, 64)])
@pytest.mark.parametrize("q_offset,n_new", [
    ([17, 63], [5, 3]),                 # mixed ragged chunk
    ([15, 32], [4, 4]),                 # block-boundary +-1 offsets
    ([S_MAX - 1, 31], [1, 1]),          # decode as S=1
])
def test_kernel_matches_xla_gather(kv, block_size, chunk_kv, q_offset,
                                   n_new):
    q, offs, nnew, pk, pv, tables = _case(kv, block_size, chunk_kv,
                                          q_offset, n_new)
    want = mixed_attention(q, pk, pv, offs + nnew, offs,
                           chunk_kv=chunk_kv, block_tables=tables)
    got = paged_mixed_attention_pallas(q, pk, pv, tables, offs + nnew,
                                       offs, chunk_kv=chunk_kv)
    for i in range(B):
        nv = int(nnew[i])
        np.testing.assert_allclose(np.asarray(got[i, :nv]),
                                   np.asarray(want[i, :nv]), **_ULP_TOL)


@pytest.mark.parametrize("block_size,chunk_kv", [(16, 32), (64, 64)])
def test_kernel_matches_xla_gather_int8(kv_int8, block_size, chunk_kv):
    """int8 KV: codes and their scales page through the same tables;
    the kernel dequantizes in-VMEM exactly like kv_dequantize."""
    kq, ks, vq, vs = kv_int8
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, 4, H, D)).astype(np.float32))
    offs = jnp.asarray([33, 90], jnp.int32)
    nnew = jnp.asarray([4, 2], jnp.int32)
    pk, pv, tables = _pool_from_contiguous(kq, vq, block_size, 5)
    psk, psv, tables2 = _pool_from_contiguous(ks[..., None], vs[..., None],
                                              block_size, 5)
    np.testing.assert_array_equal(np.asarray(tables), np.asarray(tables2))
    psk, psv = psk[..., 0], psv[..., 0]
    want = mixed_attention(q, pk, pv, offs + nnew, offs,
                           chunk_kv=chunk_kv, block_tables=tables,
                           k_scale=psk, v_scale=psv)
    got = paged_mixed_attention_pallas(q, pk, pv, tables, offs + nnew,
                                       offs, chunk_kv=chunk_kv,
                                       k_scale=psk, v_scale=psv)
    for i in range(B):
        nv = int(nnew[i])
        np.testing.assert_allclose(np.asarray(got[i, :nv]),
                                   np.asarray(want[i, :nv]), **_ULP_TOL)


# -- bit-exact single-program invariants ------------------------------------

def test_gather_invariance_is_bit_exact(kv):
    """Two different physical placements of the same logical cache:
    the in-kernel gather must make the layout invisible, bit-for-bit."""
    k, v = kv
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(B, 3, H, D)).astype(np.float32))
    offs = jnp.asarray([40, 77], jnp.int32)
    nnew = jnp.asarray([3, 3], jnp.int32)
    outs = []
    for seed in (1, 2):
        pk, pv, tables = _pool_from_contiguous(k, v, 16, seed)
        outs.append(np.asarray(paged_mixed_attention_pallas(
            q, pk, pv, tables, offs + nnew, offs, chunk_kv=32)))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_compacted_identity_is_bit_exact(kv):
    """logical_blocks == arange + all-valid entries must be the plain
    kernel, bit-for-bit (the sharded-compaction entry point's no-op)."""
    k, v = kv
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(B, 2, H, D)).astype(np.float32))
    offs = jnp.asarray([50, 100], jnp.int32)
    nnew = jnp.asarray([2, 2], jnp.int32)
    pk, pv, tables = _pool_from_contiguous(k, v, 16, 3)
    nblk = tables.shape[1]
    plain = paged_attention_pallas(q, pk, pv, tables, offs + nnew,
                                   q_offset=offs, chunk_kv=32)
    lblk = jnp.broadcast_to(jnp.arange(nblk, dtype=jnp.int32), (B, nblk))
    sel = jnp.ones((B, nblk), jnp.int32)
    comp = paged_attention_pallas(q, pk, pv, tables, offs + nnew,
                                  q_offset=offs, chunk_kv=32,
                                  logical_blocks=lblk, entry_valid=sel)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(comp))


def test_decode_variant_drops_causal_bit_exact(kv):
    """S=1 with kv_valid_len == q_offset + 1: the decode variant (no
    causal term at all) must equal the causal kernel bit-for-bit."""
    k, v = kv
    rng = np.random.default_rng(17)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)).astype(np.float32))
    cl = jnp.asarray([97, S_MAX], jnp.int32)
    pk, pv, tables = _pool_from_contiguous(k, v, 16, 9)
    causal = paged_attention_pallas(q, pk, pv, tables, cl,
                                    q_offset=cl - 1, chunk_kv=32,
                                    causal=True)
    dec = paged_decode_attention_pallas(q, pk, pv, tables, cl,
                                        chunk_kv=32)
    np.testing.assert_array_equal(np.asarray(causal), np.asarray(dec))


def test_packed_query_variant_matches_oracle(kv):
    """The token-packed (T, 1) entry point: seg_ids index the block
    table per token instead of per slot-row.  Each real token must
    match the packed XLA oracle within the cross-program bound, and
    appending bucket-padding rows (seg -1, vlen 0) must leave the real
    rows bit-identical — padding is dead weight, not a perturbation."""
    from repro.kernels.paged_attention import paged_packed_attention_pallas
    from repro.nn.attention import packed_mixed_attention
    k, v = kv
    rng = np.random.default_rng(29)
    offs, n_new = [17, 63], [5, 3]
    seg, vlen, qoff = [], [], []
    for i, (o, n) in enumerate(zip(offs, n_new)):
        for j in range(n):
            seg.append(i)
            vlen.append(o + j + 1)
            qoff.append(o + j)
    t = len(seg)
    q_flat = jnp.asarray(rng.normal(size=(t, 1, H, D)).astype(np.float32))
    pk, pv, tables = _pool_from_contiguous(k, v, 16, 27)
    seg_j = jnp.asarray(seg, jnp.int32)
    vlen_j = jnp.asarray(vlen, jnp.int32)
    qoff_j = jnp.asarray(qoff, jnp.int32)

    want = packed_mixed_attention(q_flat, pk, pv, seg_j, vlen_j, qoff_j,
                                  chunk_kv=32, block_tables=tables,
                                  impl="xla")
    got = paged_packed_attention_pallas(q_flat, pk, pv, tables, seg_j,
                                        vlen_j, q_offset=qoff_j,
                                        chunk_kv=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_ULP_TOL)

    pad = 3
    got_pad = paged_packed_attention_pallas(
        jnp.concatenate([q_flat, jnp.zeros((pad, 1, H, D),
                                           q_flat.dtype)]),
        pk, pv, tables,
        jnp.concatenate([seg_j, jnp.full((pad,), -1, jnp.int32)]),
        jnp.concatenate([vlen_j, jnp.zeros((pad,), jnp.int32)]),
        q_offset=jnp.concatenate([qoff_j, jnp.zeros((pad,),
                                                    jnp.int32)]),
        chunk_kv=32)
    np.testing.assert_array_equal(np.asarray(got_pad[:t]),
                                  np.asarray(got))


def test_partials_match_local_partial_oracle(kv):
    """normalize=False with ONE chunk: the un-normalized (o, m, l)
    partials must match distrib/decode_attn._local_partial — what the
    sharded lse merge consumes."""
    from repro.distrib.decode_attn import _local_partial
    k, v = kv
    rng = np.random.default_rng(19)
    bs = 16
    q = jnp.asarray(rng.normal(size=(B, 2, H, D)).astype(np.float32))
    offs = jnp.asarray([20, 61], jnp.int32)
    nnew = jnp.asarray([2, 2], jnp.int32)
    pk, pv, tables = _pool_from_contiguous(k, v, bs, 23)
    nblk = tables.shape[1]
    keep = jnp.broadcast_to(jnp.arange(nblk, dtype=jnp.int32), (B, nblk))
    sel = np.ones((B, nblk), np.int32)
    sel[:, 5:] = 0                        # only blocks 0..4 are "local"
    sel = jnp.asarray(sel)
    o, m, l = paged_attention_pallas(
        q, pk, pv, tables, offs + nnew, q_offset=offs,
        chunk_kv=nblk * bs,               # single chunk => bit-exact
        logical_blocks=keep, entry_valid=sel, normalize=False)
    # oracle: gather the same blocks, attend at logical positions with
    # the same selection mask
    kg = pk[tables].reshape(B, nblk * bs, HK, D)
    vg = pv[tables].reshape(B, nblk * bs, HK, D)
    kpos = jnp.broadcast_to(jnp.arange(nblk * bs, dtype=jnp.int32),
                            (B, nblk * bs))
    ev = jnp.repeat(sel.astype(bool), bs, axis=1)
    m_o, l_o, o_o = _local_partial(q, kg, vg, 0, offs + nnew,
                                   q_offset=offs, kpos=kpos,
                                   extra_valid=ev)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_o), **_ULP_TOL)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_o), **_ULP_TOL)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_o), **_ULP_TOL)


# -- the live bound: bit-exact against the block-grid kernel ----------------
#
# The kernel DMAs only the blocks below kv_valid_len and skips dead
# chunks; the block-grid oracle (tests/_paged_grid_ref.py) fetches and
# masks every table entry.  Skipped work is the identity, so every row
# -- padding rows included -- must agree to the bit.

LB_ROWS, LB_BS, LB_CK = 4, 16, 64          # 4 entries a chunk
LB_NBLK = 16                               # 4 chunks, 256 positions a row
LB_CTX = LB_NBLK * LB_BS
_CONTEXTS = {
    "mid-block": [37, 5, 100, 250],
    "block-edge": [32, 16, 80, 240],
    "chunk-edge": [64, 128, 192, LB_CTX],
    "mid-chunk": [40, 90, 150, 200],
    "empty-and-one-block": [0, 16, 0, 7],
    "all-full": [LB_CTX] * LB_ROWS,
}
# "bf16": the mixed variant over bf16 pools and queries, as served
_VARIANTS = ["mixed", "decode", "packed", "int8", "compacted", "bf16"]


def _lb_pools(seed, variant):
    """A pool twice the rows' need, rows scattered over it at random."""
    rng = np.random.default_rng(seed)
    nb = 2 * LB_ROWS * LB_NBLK
    k = rng.normal(size=(nb, LB_BS, HK, D)).astype(np.float32)
    v = rng.normal(size=(nb, LB_BS, HK, D)).astype(np.float32)
    tables = rng.permutation(nb)[:LB_ROWS * LB_NBLK].reshape(
        LB_ROWS, LB_NBLK)
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32
    pools = {"k_pool": jnp.asarray(k, dtype), "v_pool": jnp.asarray(v, dtype)}
    if variant == "int8":
        from repro.models.transformer import _kv_quantize
        pools["k_pool"], pools["k_scale"] = _kv_quantize(pools["k_pool"])
        pools["v_pool"], pools["v_scale"] = _kv_quantize(pools["v_pool"])
    return pools, jnp.asarray(tables, jnp.int32)


def _lb_call(variant, contexts, pools, tables, seed=0):
    """One case: (live-bound kernel output, block-grid oracle as a
    function of the pools, the physical block of every live (grid row,
    table entry) -- what the kernel may DMA, once a KV head)."""
    from _paged_grid_ref import grid_paged_attention
    from repro.kernels.paged_attention import paged_packed_attention_pallas
    rng = np.random.default_rng(seed + 100)
    vlen = jnp.asarray(contexts, jnp.int32)
    kw = dict(chunk_kv=LB_CK)

    def prefixes(tbl, lens):
        return [int(t) for row, n in zip(np.asarray(tbl), lens)
                for t in row[:-(-int(n) // LB_BS)]]

    if variant == "packed":
        # the rows' last two tokens, then bucket padding (seg -1, vlen 0)
        seg, tv = [], []
        for r, n in enumerate(contexts):
            for p in range(max(n - 2, 0), n):
                seg.append(r)
                tv.append(p + 1)
        seg += [-1, -1]
        tv += [0, 0]
        seg_j = jnp.asarray(seg, jnp.int32)
        tvlen = jnp.asarray(tv, jnp.int32)
        qoff = jnp.maximum(tvlen - 1, 0)
        q = jnp.asarray(rng.normal(size=(len(seg), 1, H, D)), jnp.float32)
        got = paged_packed_attention_pallas(q, pools["k_pool"],
                                            pools["v_pool"], tables, seg_j,
                                            tvlen, q_offset=qoff, **kw)
        tok_tables = tables[jnp.clip(seg_j, 0, LB_ROWS - 1)]
        return got, lambda p: grid_paged_attention(
            q, p["k_pool"], p["v_pool"], tok_tables, tvlen, q_offset=qoff,
            **kw), prefixes(tok_tables, tv)
    sq = 1 if variant == "decode" else 3
    q = jnp.asarray(rng.normal(size=(LB_ROWS, sq, H, D)),
                    pools["k_pool"].dtype if variant == "bf16"
                    else jnp.float32)
    if variant == "decode":
        kw["causal"] = False
    else:
        kw["q_offset"] = jnp.maximum(vlen - sq, 0)
    if variant == "compacted":
        # a per-device compaction: each entry names its logical block
        # (a shuffled order) and about half of them are local
        keep = np.stack([rng.permutation(LB_NBLK) for _ in contexts])
        sel = rng.integers(0, 2, size=keep.shape)
        kw.update(logical_blocks=jnp.asarray(keep, jnp.int32),
                  entry_valid=jnp.asarray(sel, jnp.int32), normalize=False)
        tables = jnp.take_along_axis(tables, jnp.asarray(keep), axis=1)
        live = [int(tables[r, e]) for r, n in enumerate(contexts)
                for e in range(LB_NBLK)
                if sel[r, e] and keep[r, e] * LB_BS < n]
    else:
        live = prefixes(tables, contexts)
    scales = {k: pools[k] for k in ("k_scale", "v_scale") if k in pools}
    got = paged_attention_pallas(q, pools["k_pool"], pools["v_pool"],
                                 tables, vlen, **scales, **kw)
    return got, lambda p: grid_paged_attention(
        q, p["k_pool"], p["v_pool"], tables, vlen,
        **{k: p[k] for k in scales}, **kw), live


def _assert_bits(got, want):
    for g, w in zip(*(x if isinstance(x, tuple) else (x,)
                      for x in (got, want))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("contexts", list(_CONTEXTS.values()),
                         ids=list(_CONTEXTS))
@pytest.mark.parametrize("variant", _VARIANTS)
def test_live_bound_matches_block_grid_bit_exact(variant, contexts):
    pools, tables = _lb_pools(31, variant)
    got, oracle, _ = _lb_call(variant, contexts, pools, tables)
    _assert_bits(got, oracle(pools))


@pytest.fixture
def dma_log(monkeypatch):
    """Physical block of every K/V copy the kernel starts, as it runs."""
    from jax.experimental.pallas import tpu as pltpu
    real, log = pltpu.make_async_copy, []

    class _Logged:
        def __init__(self, src, dst, sem):
            self.copy = real(src, dst, sem)
            self.block = src.transforms[-1].indices[0]

        def start(self):
            jax.debug.callback(lambda b: log.append(int(b)), self.block)
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(pltpu, "make_async_copy", _Logged)
    return log


@pytest.mark.parametrize("variant", _VARIANTS)
def test_poisoned_pool_is_never_read(variant, dma_log):
    """Every pool block no live position reaches is NaN (its scales too
    for int8 KV).  The kernel copies each live block exactly once per
    KV head for K and for V and no other block, and stale scratch never
    reaches p @ V: the output is finite and equals the block-grid
    oracle's on the clean pool, bit for bit."""
    contexts = [37, 0, 128, 7]
    pools, tables = _lb_pools(41, variant)
    clean, _, live = _lb_call(variant, contexts, pools, tables)
    jax.block_until_ready(clean)
    dma_log.clear()
    dead = np.ones(pools["k_pool"].shape[0], bool)
    dead[live] = False
    assert dead.any() and live
    poisoned = {}
    for name, arr in pools.items():
        a = np.array(arr)
        if a.dtype != np.int8:                  # codes carry no NaN
            a[dead] = np.nan
        poisoned[name] = jnp.asarray(a, arr.dtype)
    got, oracle, _ = _lb_call(variant, contexts, poisoned, tables)
    jax.block_until_ready(got)
    assert sorted(dma_log) == sorted(live * (2 * HK))
    for g in got if isinstance(got, tuple) else (got,):
        assert np.isfinite(np.asarray(g)).all()
    _assert_bits(got, oracle(pools))
