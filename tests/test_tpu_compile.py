"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The interpret-mode tests check what the Pallas kernels compute; only
the TPU compiler (Mosaic) checks that they lower at all — block tiling,
vector layouts, the integer ops the vector unit has.  The compiler is
installed here and compiles for a chip described by
``get_topology_desc("v5e:2x2")``.  Every kernel variant on the serving
path is compiled at chatglm3-6b widths (d_model 4096, d_ff 13696,
32 heads / 2 KV heads of 128), paged attention also at yi-34b's 56
heads / 8 KV heads, and the engine's paged unified step, with the
model cut to 2 layers, at both models' widths as their benchmark
cells serve them.

The topology is described in a module-scoped fixture, never while the
module is imported: only one process at a time may load the TPU
library, and the worker that runs this file keeps it.  Code that asks
``jax.default_backend()`` still sees the CPU here, so the engine-step
test steers the kernels' dispatch to their TPU route itself.  The
persistent compilation cache is off around the compiles (an entry
written for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import paged_attention as pa
from repro.kernels import tim_matmul as tk
from repro.launch.hlo_analysis import tpu_kernel_calls

D_MODEL, D_FF = 4096, 13696
N_HEADS, N_KV, HEAD_DIM = 32, 2, 128
SLOTS, CHUNK, BLOCK, MAX_LEN = 8, 16, 16, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sds_on(sharding):
    return lambda shape, dtype: _sds(sharding, shape, dtype)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_calls(compiled) -> dict:
    return tpu_kernel_calls(compiled.as_text())


# (name, kwargs, activation bits or None for the two-phase kernel)
_TIM_CASES = [
    ("fused-int8", dict(packed=False), None),
    ("fused-packed", dict(packed=True), None),
    ("bitserial-int2", dict(packed=False), 2),
    ("bitserial-int4", dict(packed=False), 4),
    ("bitserial-int2-packed", dict(packed=True), 2),
    ("fused-n_max", dict(packed=False, n_max=7), None),
]


@pytest.mark.parametrize("name,kw,bits", _TIM_CASES,
                         ids=[c[0] for c in _TIM_CASES])
@pytest.mark.parametrize("m,k,n", [(SLOTS * CHUNK, D_MODEL, D_FF),
                                   (SLOTS, D_FF, D_MODEL)],
                         ids=["up", "down"])
def test_tim_kernel_compiles(one_chip, name, kw, bits, m, k, n):
    s = _sds_on(one_chip)
    packed = kw["packed"]
    w = s((k // 4, n), jnp.uint8) if packed else s((k, n), jnp.int8)
    x = s((m, k), jnp.int8)
    sc = s((n,), jnp.float32)
    one = s((), jnp.float32)
    if bits is None:
        fn = lambda x, w, a, b, i1, i2: tk.tim_matmul_fused_pallas(  # noqa
            x, w, a, b, i1, i2, need_t=True, out_dtype=jnp.bfloat16, **kw)
        compiled = _compile(fn, x, w, sc, sc, one, one)
        kernel = "tim_matmul_fused_packed" if packed else \
            "tim_matmul_fused"
    else:
        fn = lambda x, w, a, b, st: tk.tim_matmul_bitserial_fused_pallas(  # noqa
            x, w, a, b, st, bits=bits, need_t=True,
            out_dtype=jnp.bfloat16, **kw)
        compiled = _compile(fn, x, w, sc, sc, one)
        kernel = "tim_matmul_bitserial"
    assert _kernel_calls(compiled) == {kernel: 1}


@pytest.mark.parametrize("sq", [1, CHUNK])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("n_heads,n_kv", [(N_HEADS, N_KV), (56, 8)],
                         ids=["32q2kv", "56q8kv"])
def test_paged_attention_compiles(one_chip, sq, kv_dtype, n_heads, n_kv):
    """Paged attention at chatglm3-6b's grouping (16 query heads per KV
    head) and yi-34b's (7 per KV head: a 7 * sq row query block)."""
    s = _sds_on(one_chip)
    nblk = MAX_LEN // BLOCK
    nb = SLOTS * nblk + SLOTS
    pool = s((nb, BLOCK, n_kv, HEAD_DIM), jnp.dtype(kv_dtype))
    args = [s((SLOTS, sq, n_heads, HEAD_DIM), jnp.bfloat16), pool, pool,
            s((SLOTS, nblk), jnp.int32), s((SLOTS,), jnp.int32),
            s((SLOTS,), jnp.int32)]
    if kv_dtype == "int8":
        scales = s((nb, BLOCK, n_kv), jnp.bfloat16)

        def fn(q, k, v, t, vl, qo, ks, vs):
            return pa.paged_attention_pallas(
                q, k, v, t, vl, q_offset=qo, k_scale=ks, v_scale=vs,
                interpret=False)
        args += [scales, scales]
    else:
        def fn(q, k, v, t, vl, qo):
            return pa.paged_attention_pallas(q, k, v, t, vl, q_offset=qo,
                                             interpret=False)
    assert _kernel_calls(_compile(fn, *args)) == {"paged_attention": 1}


def test_paged_packed_attention_compiles(one_chip):
    s = _sds_on(one_chip)
    nblk = MAX_LEN // BLOCK
    nb = SLOTS * nblk + SLOTS
    tokens = SLOTS + CHUNK
    pool = s((nb, BLOCK, N_KV, HEAD_DIM), jnp.bfloat16)
    tok = s((tokens,), jnp.int32)

    def fn(q, k, v, t, seg, vl, qo):
        return pa.paged_packed_attention_pallas(q, k, v, t, seg, vl,
                                                q_offset=qo,
                                                interpret=False)
    compiled = _compile(fn, s((tokens, 1, N_HEADS, HEAD_DIM), jnp.bfloat16),
                        pool, pool, s((SLOTS, nblk), jnp.int32), tok, tok,
                        tok)
    assert _kernel_calls(compiled) == {"paged_packed_attention": 1}


def _tim_cfg(n_layers: int, arch: str = "chatglm3-6b", pack: bool = False):
    cfg = get_config(arch).replace(n_layers=n_layers)
    return cfg.replace(ternary=cfg.ternary.replace(
        enabled=True, encoding="asymmetric", act_mode="ternary",
        pack=pack))


# (arch, slots, 2-bit packed codes, layers its benchmark cell serves):
# each benchmark configuration as bench/configs/ serves it
_SERVED = {
    "chatglm3-6b": ("chatglm3-6b", 24, False, 28),
    "yi-34b": ("yi-34b", 16, True, 15),
}


def _with_sharding(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: _sds(sharding, x.shape, x.dtype), tree)


def _compile_engine_step(sharding, slots: int, rows=None,
                         arch: str = "chatglm3-6b", pack: bool = False):
    """The engine's paged unified step at full width (2 layers) under
    the TiM policy, compiled for the described chip; ``rows`` adds the
    scheduled-token rows operand of that length."""
    from repro.models import transformer as tfm
    from repro.serve.block_pool import default_num_blocks
    from repro.serve.engine import make_paged_unified_step, ternarize_model

    cfg = _tim_cfg(2, arch, pack)
    s = _sds_on(sharding)
    params = _with_sharding(jax.eval_shape(
        lambda k: ternarize_model(tfm.init(cfg, k), cfg),
        jax.random.PRNGKey(0)), sharding)
    num_blocks = default_num_blocks(slots, MAX_LEN, BLOCK)
    caches = _with_sharding(jax.eval_shape(
        lambda: tfm.init_paged_caches(cfg, slots, num_blocks, BLOCK)),
        sharding)
    vec = s((slots,), jnp.int32)
    args = (params, {"tokens": s((slots, CHUNK), jnp.int32)}, caches, vec,
            vec, s((slots, MAX_LEN // BLOCK), jnp.int32),
            s((slots, CHUNK), jnp.int32))
    if rows is not None:
        args += (s((rows,), jnp.int32),)
    return jax.jit(make_paged_unified_step(cfg),
                   donate_argnums=(2,)).lower(*args).compile()


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_engine_step_compiles_with_kernels(one_chip, monkeypatch,
                                           precision):
    """The engine's paged unified step at full width (2 layers) under
    the TiM policy: every ternary matmul and the paged attention lower
    to Mosaic kernels, also under an ambient f32 matmul precision (the
    int8 MXU passes must not inherit it)."""
    # the dispatch asks the default backend, which here is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_matmul_precision(precision):
        compiled = _compile_engine_step(one_chip, SLOTS)
    # one layer's calls (the layer loop is a scan): the q, k, v, o,
    # gate, up and down matmuls and the attention
    assert _kernel_calls(compiled) == {"tim_matmul_fused": 7,
                                       "paged_attention": 1}


@pytest.mark.parametrize("model,rows,kernel", [
    ("chatglm3-6b", 64, "tim_matmul_fused"),
    ("yi-34b", 32, "tim_matmul_fused_packed")])
def test_engine_row_step_compiles_with_kernels(one_chip, monkeypatch,
                                               model, rows, kernel):
    """The engine's plain step as it serves each benchmark cell, with
    16-token chunks and a budget of slots + chunk: chatglm3-6b on 24
    slots and 64 scheduled-token rows with int8 codes, yi-34b on 16
    slots and 32 rows (one M tile) with 2-bit packed codes and 8 KV
    heads of 7 query heads.  The token-wise work runs the TiM kernels
    (the packed variant under its own name), and attention stays the
    slot-grid kernel, not the per-token one."""
    from repro.serve.engine import step_rows

    arch, slots, pack, _ = _SERVED[model]
    assert step_rows(slots, CHUNK, slots + CHUNK) == rows
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile_engine_step(one_chip, slots, rows, arch, pack)
    assert _kernel_calls(compiled) == {kernel: 7, "paged_attention": 1}


@pytest.mark.parametrize("model", sorted(_SERVED))
def test_period_conversion_fits_one_chip(one_chip, model):
    """Serving params come from one period of fp32 masters at a time
    (serve/engine.init_serving): one period's conversion, at full width,
    needs a small share of the chip beside the code stack its cell
    serves (chatglm3-6b's 28 int8 periods, yi-34b's 15 packed ones) and
    the fp32 embedding and head."""
    from repro.models import transformer as tfm
    from repro.serve.engine import ternarize_model

    arch, _, pack, n_layers = _SERVED[model]
    cfg = _tim_cfg(n_layers, arch, pack)
    key = _sds(one_chip, (2,), jnp.uint32)
    compiled = _compile(
        lambda k: ternarize_model(tfm.init_period(cfg, k), cfg), key)
    mem = compiled.memory_analysis()
    period_bytes = mem.temp_size_in_bytes + mem.output_size_in_bytes
    stack_bytes = cfg.n_periods * mem.output_size_in_bytes
    embed_head_bytes = 2 * cfg.vocab_padded * cfg.d_model * 4
    assert period_bytes + stack_bytes + embed_head_bytes < 14 * 2 ** 30, (
        period_bytes, stack_bytes, embed_head_bytes)
