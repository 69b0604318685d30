"""Plain reference of the served model: its weights rebuilt from the seed
and a straightforward forward pass over whole sequences.

Nothing here imports the program or reads what it made.  The weights
follow the construction a configuration file states under ``weights``
(named PRNG streams, truncated-normal masters, TWN-threshold asymmetric
ternarization of the bfloat16 view, per-output-column scales), and the
forward pass rounds where the file's ``numerics`` say: the residual
stream, every matmul output and the KV in the compute dtype, ternary
activation codes at a fixed threshold, and the ternary matmul's f32
epilogue ``c_s * S + c_t * T`` per activation phase (the TiM tile's
sign/magnitude counts, paper section III).  Attention is one causal
softmax over the whole sequence at float32 ``highest`` precision.

The model is built layer by layer: one layer's weights exist at a time,
and every sequence passes through that layer before the next one is
made, so the reference fits on the chip beside nothing else.

``layer0_kv`` gives the first layer's cached K and V, which the
program's KV pool must hold for the same tokens.

``precision='control'`` is the same reference with every compute-dtype
rounding replaced by float8 e4m3 (``lax.reduce_precision``): the
cheaper precision that the correctness limits have to reject.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

TWN_FACTOR = 0.7
LOGIT_BLOCK = 256


def model_key(seed: int) -> jax.Array:
    """The root PRNG key of a run's weights: 64 bits of seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def named(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


class Dims(NamedTuple):
    """The widths and roundings the reference needs (hashable: it is a
    static argument of the jitted layer)."""

    n_layers: int
    d: int
    n_heads: int
    n_kv: int
    hd: int
    d_ff: int
    vocab: int
    vocab_padded: int
    rope: str              # standard | half
    rope_theta: float
    eps: float
    act_threshold: float

    def linears(self):
        """(name, d_in, d_out, parent) of every ternary matmul of a layer."""
        d, hd = self.d, self.hd
        return [("q", d, self.n_heads * hd, "mixer"),
                ("k", d, self.n_kv * hd, "mixer"),
                ("v", d, self.n_kv * hd, "mixer"),
                ("o", self.n_heads * hd, d, "mixer"),
                ("gate", d, self.d_ff, "ffn"),
                ("up", d, self.d_ff, "ffn"),
                ("down", self.d_ff, d, "ffn")]


def dims_of(cfg: Dict) -> Dims:
    """Read a configuration file's ``model`` block through its ``keys``
    map (reference name -> the source's key) and its ``numerics``."""
    m, k, num = cfg["model"], cfg["keys"], cfg["numerics"]
    n_heads, d = int(m[k["n_heads"]]), int(m[k["d_model"]])
    vocab = int(m[k["vocab_size"]])
    r = int(num["vocab_round_to"])
    return Dims(
        n_layers=int(m[k["n_layers"]]), d=d, n_heads=n_heads,
        n_kv=int(m[k["n_kv_heads"]]),
        hd=int(m[k["head_dim"]]) if "head_dim" in k else d // n_heads,
        d_ff=int(m[k["d_ff"]]), vocab=vocab,
        vocab_padded=-(-vocab // r) * r, rope=num["rope"],
        rope_theta=float(m[k["rope_theta"]]) if "rope_theta" in k
        else float(num["rope_theta"]),
        eps=float(num["rms_eps"]),
        act_threshold=float(num["act_threshold"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _master(key, d_in: int, d_out: int) -> jax.Array:
    """Truncated normal at +-2 sigma, sigma = fan_in ** -0.5."""
    std = (1.0 / max(d_in, 1)) ** 0.5
    return (std * jax.random.truncated_normal(
        key, -2.0, 2.0, (d_in, d_out), jnp.float32)).astype(jnp.float32)


def _ternarize(w: jax.Array):
    """Asymmetric TWN ternarization of the bfloat16 view, one threshold
    and one positive / negative scale per output column."""
    wb = w.astype(jnp.bfloat16)
    thr = TWN_FACTOR * jnp.mean(jnp.abs(wb), axis=0, keepdims=True)
    pos, neg = wb > thr, wb < -thr
    codes = jnp.where(pos, 1, jnp.where(neg, -1, 0)).astype(jnp.int8)

    def mean_of(mask):
        num = jnp.sum(jnp.where(mask, jnp.abs(wb), 0.0), axis=0,
                      keepdims=True)
        den = jnp.maximum(jnp.sum(mask, axis=0, keepdims=True), 1)
        return (num / den).astype(jnp.bfloat16)

    return codes, mean_of(pos), mean_of(neg)


def layer_key(key: jax.Array, layer: int) -> jax.Array:
    return named(key, f"period{layer}")


@functools.partial(jax.jit, static_argnums=0)
def layer_weights(dims: Dims, kp: jax.Array):
    """Codes and scales of one layer's seven ternary matmuls, from the
    layer's key."""
    kb = named(kp, "b0")
    out = {}
    for name, d_in, d_out, parent in dims.linears():
        out[name] = _ternarize(_master(named(named(named(kb, parent), name),
                                             "w"), d_in, d_out))
    return out


def embedding_table(dims: Dims, key: jax.Array) -> jax.Array:
    k = named(named(key, "embed"), "emb")
    return (0.02 * jax.random.truncated_normal(
        k, -2.0, 2.0, (dims.vocab_padded, dims.d), jnp.float32)
            ).astype(jnp.float32)


def head_matrix(dims: Dims, key: jax.Array) -> jax.Array:
    return _master(named(named(key, "head"), "w"), dims.d, dims.vocab_padded)


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _rounder(precision: str, stored: bool = False):
    """The rounding to the compute dtype.  ``stored`` marks a value the
    served program writes to memory in bfloat16 (a kernel's output, the
    KV cache, the logits): it is rounded explicitly, so that no compiler
    may carry it on in float32.  Values the program only passes between
    XLA operations are cast as the program casts them."""
    if precision == "served":
        if not stored:
            return lambda x: x.astype(jnp.bfloat16)
        return lambda x: lax.reduce_precision(
            x.astype(jnp.float32), exponent_bits=8,
            mantissa_bits=7).astype(jnp.bfloat16)
    if precision == "control":
        return lambda x: lax.reduce_precision(
            x.astype(jnp.float32), exponent_bits=4,
            mantissa_bits=3).astype(jnp.bfloat16)
    raise ValueError(precision)


def _rms(x, eps, rnd):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return rnd(xf * lax.rsqrt(var + eps))


def _tim(x, w, threshold, rnd):
    """Ternary activations x ternary weights: integer S/T counts per
    activation phase, f32 epilogue, one rounding."""
    codes, pos_s, neg_s = w
    q = jnp.where(x > threshold, 1, jnp.where(x < -threshold, -1, 0))
    wq = codes
    aw = jnp.abs(codes)
    w1 = pos_s.astype(jnp.float32).reshape(-1)
    w2 = neg_s.astype(jnp.float32).reshape(-1)
    c_s = (w1 + w2) * 0.5
    c_t = (w1 - w2) * 0.5

    def phase(mask):
        xp = mask.astype(jnp.int8)
        s = lax.dot(xp, wq, preferred_element_type=jnp.int32)
        t = lax.dot(xp, aw, preferred_element_type=jnp.int32)
        return c_s * s.astype(jnp.float32) + c_t * t.astype(jnp.float32)

    return rnd(phase(q > 0) - phase(q < 0))


def _rope(x, positions, theta, variant, rnd):
    hd = x.shape[-1]
    rd = hd if variant == "standard" else hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = positions[:, None].astype(jnp.float32) * inv
    sin = jnp.sin(ang)[:, None, :]
    cos = jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x[..., :rd].astype(jnp.float32), 2, axis=-1)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return rnd(jnp.concatenate([rot, x[..., rd:].astype(jnp.float32)], -1))


def _attention(q, k, v, rnd, q_block: int = 512):
    """Causal softmax attention over the whole sequence, f32 at highest
    precision, queries in blocks so the scores stay small."""
    n, h, hd = q.shape
    hk = k.shape[1]
    g = h // hk
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    outs = []
    for s0 in range(0, n, q_block):
        qb = q[s0:s0 + q_block].astype(jnp.float32).reshape(-1, hk, g, hd)
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("qhgd,khd->hgqk", qb, kf) * (hd ** -0.5)
            qpos = s0 + jnp.arange(qb.shape[0])
            mask = qpos[:, None] >= jnp.arange(n)[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hgqk,khd->qhgd", p, vf)
        outs.append(o.reshape(-1, h * hd))
    return rnd(jnp.concatenate(outs, 0))


def _kv(w, h, positions, dims, store):
    """A layer's cached K (rotated) and V from its normed input."""
    n, thr = h.shape[0], dims.act_threshold
    k = _tim(h, w["k"], thr, store).reshape(n, dims.n_kv, dims.hd)
    v = _tim(h, w["v"], thr, store).reshape(n, dims.n_kv, dims.hd)
    return _rope(k, positions, dims.rope_theta, dims.rope, store), v


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def layer_apply(w, x, positions, *, dims, precision):
    rnd, store = _rounder(precision), _rounder(precision, stored=True)
    thr = dims.act_threshold
    n = x.shape[0]
    h = _rms(x, dims.eps, rnd)
    q = _tim(h, w["q"], thr, store).reshape(n, dims.n_heads, dims.hd)
    q = _rope(q, positions, dims.rope_theta, dims.rope, rnd)
    k, v = _kv(w, h, positions, dims, store)
    o = _attention(q, k, v, store)
    x = rnd(x + _tim(o, w["o"], thr, store))
    h = _rms(x, dims.eps, rnd)
    g = _tim(h, w["gate"], thr, store)
    u = _tim(h, w["up"], thr, store)
    a = rnd(rnd(jax.nn.silu(g.astype(jnp.float32))) * u)
    d = _tim(a, w["down"], thr, store)
    return store(x + d)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _logits(x, head, *, dims, precision):
    h = _rms(x, dims.eps, _rounder(precision))
    lg = _rounder(precision, stored=True)(
        h.astype(jnp.bfloat16) @ head.astype(jnp.bfloat16))
    if dims.vocab_padded != dims.vocab:
        lg = jnp.where(jnp.arange(dims.vocab_padded) >= dims.vocab,
                       -jnp.inf, lg.astype(jnp.float32))
    return lg.astype(jnp.float32)


def forward_logits(dims: Dims, seed: int, sequences: Sequence[np.ndarray],
                   starts: Sequence[int], pad_to: int,
                   precisions: Sequence[str] = ("served",)
                   ) -> Dict[str, List[np.ndarray]]:
    """Logits (f32 numpy) of every sequence at positions ``starts[i]``
    .. ``len - 2``: the positions whose next token was served.  Every
    sequence is padded at its end to ``pad_to`` tokens (causal: the
    padding changes nothing before it), so one compiled layer serves
    every run.  One pass over the layers computes every precision."""
    key = model_key(seed)
    table = embedding_table(dims, key)
    ids = [jnp.asarray(np.pad(s, (0, pad_to - len(s))), jnp.int32)
           for s in sequences]
    xs = {p: [_rounder(p, stored=True)(table[i]) for i in ids]
          for p in precisions}
    del table
    pos = jnp.arange(pad_to, dtype=jnp.int32)
    for layer in range(dims.n_layers):
        w = layer_weights(dims, layer_key(key, layer))
        for p in precisions:
            xs[p] = [layer_apply(w, x, pos, dims=dims, precision=p)
                     for x in xs[p]]
        del w
    head = head_matrix(dims, key)
    out = {p: [] for p in precisions}
    for p in precisions:
        for x, s, st in zip(xs[p], sequences, starts):
            n = len(s) - 1 - st
            rows = []
            for b in range(st, st + n, LOGIT_BLOCK):
                blk = jax.lax.dynamic_slice_in_dim(
                    jnp.pad(x, ((0, LOGIT_BLOCK), (0, 0))), b, LOGIT_BLOCK)
                rows.append(np.asarray(_logits(blk, head, dims=dims,
                                               precision=p)))
            out[p].append(np.concatenate(rows)[:n])
    return out


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _layer0_kv(w, x, positions, *, dims, precision):
    h = _rms(x, dims.eps, _rounder(precision))
    return _kv(w, h, positions, dims, _rounder(precision, stored=True))


def layer0_kv(dims: Dims, seed: int, sequences: Sequence[np.ndarray],
              pad_to: int, precisions: Sequence[str] = ("served",)
              ) -> Dict[str, List[np.ndarray]]:
    """The first layer's cached K and V of every sequence at every
    position, as one (len, 2, kv heads, head_dim) f32 array: what the
    program's KV cache must hold for these tokens.  Layer 0 depends on
    each position's own token alone, so it is exact to rounding at any
    context length."""
    key = model_key(seed)
    table = embedding_table(dims, key)
    w = layer_weights(dims, layer_key(key, 0))
    pos = jnp.arange(pad_to, dtype=jnp.int32)
    out = {p: [] for p in precisions}
    for s in sequences:
        ids = jnp.asarray(np.pad(s, (0, pad_to - len(s))), jnp.int32)
        for p in precisions:
            k, v = _layer0_kv(w, _rounder(p, stored=True)(table[ids]), pos,
                              dims=dims, precision=p)
            out[p].append(np.stack([np.asarray(k, np.float32),
                                    np.asarray(v, np.float32)], 1)[:len(s)])
    return out
