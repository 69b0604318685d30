import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512"
                           ).strip()
# CPU-only: the 512 devices are host-platform stand-ins, so this script
# never touches (or holds) an accelerator, even where one is attached.
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count and platform at first init).  Pre-existing XLA_FLAGS (user/CI)
# are preserved — the device-count flag is *appended*.  Everything below
# is normal code.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and extract the roofline terms.

For each supported cell this driver:
  1. builds ShapeDtypeStruct stand-ins for params / optimizer state /
     caches / batch (zero bytes allocated),
  2. jax.jit(step).lower(...).compile() under the 16x16 (single-pod) and
     2x16x16 (multi-pod) meshes,
  3. records memory_analysis (bytes/device), cost_analysis (FLOPs,
     bytes), and the collective-op byte census parsed from the
     optimized HLO,
  4. writes everything to a JSON report consumed by benchmarks/roofline.

Shapes:   train_4k lowers the full train_step (fwd+bwd+AdamW);
          prefill_32k lowers prefill (logits + cache build);
          decode_32k / long_500k lower serve_step (1 token vs KV cache);
          mixed_32k lowers the serving engine's unified chunked-prefill
          step (a (slots, chunk) token grid mixing decode tokens and
          prefill chunks against the shared cache — the continuous-
          batching steady state).

Variants (--variant, '+'-composable) are the §Perf levers:
  baseline      paper-faithful: int8 ternary codes, weight-only matmul
  packed        2-bit packed codes (TPC storage density on HBM)
  fp16dense     no ternary at all (the fp baseline the paper compares to)
  bf16          bf16 master weights
  bc            pin residual-stream batch layout (hint constraints)
  sp            Megatron sequence parallelism (implies bc)
  moe           shard MoE dispatch buffers (experts x capacity->data)
  moefull       replicate experts, shard capacity over data x model
  kvseq         shard the KV-cache sequence dim over `model`
  kv8           int8-quantized KV cache (per-token-per-head scales)
  ternaryact    [T,T] serving: ternary activations through the TiM path
  int2 / int4   bit-serial serving at 2 / 4 activation bits (the fused
                kernels' weight-stream win scales with bits; see the
                per-cell weight_stream report)
  gc8           int8 error-feedback gradient compression
  rematdots     save-dots remat policy
"""
import argparse
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import ArchConfig, SHAPES, ShapeConfig, cell_supported
from repro.distrib import sharding as shd
from repro.launch.mesh import dp_axis_names, make_production_mesh
from repro.models import transformer as tfm
from repro.models.losses import lm_loss
from repro.serve.engine import make_decode_step, make_paged_unified_step, \
    make_prefill_step, make_unified_step, ternarize_model
from repro.train.optimizer import OptConfig, adamw_init, adamw_update

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; weak-type-correct, shardable)
# ---------------------------------------------------------------------------

def batch_specs(cfg: ArchConfig, b: int, s: int) -> Dict[str, SDS]:
    out: Dict[str, SDS] = {}
    if cfg.frontend_dim:
        out["frames"] = SDS((b, s, cfg.frontend_dim), jnp.bfloat16)
    else:
        out["tokens"] = SDS((b, s), jnp.int32)
    if cfg.n_media_tokens:
        out["media"] = SDS((b, cfg.n_media_tokens, cfg.media_dim),
                           jnp.bfloat16)
    return out


def train_batch_specs(cfg: ArchConfig, b: int, s: int) -> Dict[str, SDS]:
    out = batch_specs(cfg, b, s)
    out["labels"] = SDS((b, s), jnp.int32)
    out["mask"] = SDS((b, s), jnp.float32)
    return out


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Public entry: the model-input stand-ins for one cell."""
    if shape.kind == "train":
        return train_batch_specs(cfg, shape.global_batch, shape.seq_len)
    if shape.kind == "prefill":
        return batch_specs(cfg, shape.global_batch, shape.seq_len)
    if shape.kind == "mixed":
        return batch_specs(cfg, shape.global_batch, shape.chunk)
    return batch_specs(cfg, shape.global_batch, 1)  # decode


def param_specs(cfg: ArchConfig, serve: bool, key=None):
    key = jax.random.PRNGKey(0) if key is None else key
    if serve:
        fn = lambda k: ternarize_model(tfm.init(cfg, k), cfg)
    else:
        fn = lambda k: tfm.init(cfg, k)
    return jax.eval_shape(fn, key)


def cache_sds(cfg: ArchConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: tfm.init_caches(cfg, batch, max_len))


def paged_cache_sds(cfg: ArchConfig, batch: int, num_blocks: int,
                    block_size: int):
    return jax.eval_shape(lambda: tfm.init_paged_caches(
        cfg, batch, num_blocks, block_size))


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_train_step(cfg: ArchConfig, grad_compress: bool = False):
    ocfg = OptConfig()

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm_loss(p, cfg, batch), has_aux=True)(params)
        if grad_compress:
            # int8 error-feedback quantization brackets the DP reduce:
            # GSPMD's gradient collectives then move int8 operands
            from repro.distrib.grad_compress import compress_decompress
            err = jax.tree_util.tree_map(
                lambda g: jnp.zeros(g.shape, jnp.float32), grads)
            grads, _ = compress_decompress(grads, err)
        lr = jnp.asarray(3e-4, jnp.float32)
        params, opt_state = adamw_update(ocfg, params, grads, opt_state, lr)
        return params, opt_state, metrics["loss"]

    return train_step


def build_serve_step(cfg: ArchConfig):
    decode = make_decode_step(cfg)

    def serve_step(params, batch, caches, cache_len):
        return decode(params, batch, caches, cache_len)

    return serve_step


# ---------------------------------------------------------------------------
# HLO collective census
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def collective_census(hlo_text: str, n_devices: int) -> Dict[str, Any]:
    """Sum result-shape bytes per collective kind; wire-byte estimates
    use ring formulas (per participating device):
        all-gather:       out * (n-1)/n
        reduce-scatter:   in  * (n-1)/n   (result shape ~= in/n; we see
                                           the result, so * (n-1))
        all-reduce:       2 * size * (n-1)/n
        all-to-all:       size * (n-1)/n
        collective-permute: size
    Group size n is approximated by the mesh axis the op spans; we use
    the census primarily as a *relative* measure across variants.
    """
    counts: Dict[str, int] = {}
    bytes_by_kind: Dict[str, float] = {}
    wire_by_kind: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        if dt not in _DTYPE_BYTES:
            continue
        numel = 1
        if dims:
            for d in dims.split(","):
                numel *= int(d)
        size = numel * _DTYPE_BYTES[dt]
        counts[kind] = counts.get(kind, 0) + 1
        bytes_by_kind[kind] = bytes_by_kind.get(kind, 0) + size
        n = max(n_devices, 2)
        if kind == "all-gather":
            wire = size * (n - 1) / n
        elif kind == "reduce-scatter":
            wire = size * (n - 1)
        elif kind == "all-reduce":
            wire = 2 * size * (n - 1) / n
        elif kind == "all-to-all":
            wire = size * (n - 1) / n
        else:  # collective-permute
            wire = size
        wire_by_kind[kind] = wire_by_kind.get(kind, 0) + wire
    return {
        "counts": counts,
        "result_bytes": bytes_by_kind,
        "wire_bytes_est": wire_by_kind,
        "total_wire_bytes": sum(wire_by_kind.values()),
    }


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def _shardings(tree_pspecs, mesh):
    return jax.tree_util.tree_map(
        lambda ps: NamedSharding(mesh, ps), tree_pspecs,
        is_leaf=lambda x: isinstance(x, P))


def run_cell(arch: str, shape_name: str, mesh: Mesh,
             variant: str = "baseline",
             extra_cfg: Optional[Dict[str, Any]] = None
             ) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "variant": variant,
                "status": "skipped", "reason": reason}

    # variants compose with '+': e.g. 'sp+bf16', 'moe+bf16'
    feats = set(variant.split("+")) - {"baseline"}
    if "packed" in feats:
        cfg = cfg.replace(ternary=cfg.ternary.replace(pack=True))
    if "fp16dense" in feats:
        cfg = cfg.replace(ternary=cfg.ternary.replace(enabled=False),
                          param_dtype="bfloat16")
    if "bf16" in feats:
        cfg = cfg.replace(param_dtype="bfloat16")
    if "rematdots" in feats:
        cfg = cfg.replace(remat="dots")
    if "kv8" in feats:
        cfg = cfg.replace(kv_cache_dtype="int8")
    if "ternaryact" in feats:
        cfg = cfg.replace(ternary=cfg.ternary.replace(
            encoding="asymmetric", act_mode="ternary"))
    if "int2" in feats:
        cfg = cfg.replace(ternary=cfg.ternary.replace(
            encoding="asymmetric", act_mode="int2"))
    if "int4" in feats:
        cfg = cfg.replace(ternary=cfg.ternary.replace(
            encoding="asymmetric", act_mode="int4"))
    if extra_cfg:
        cfg = cfg.replace(**extra_cfg)

    n_dev = mesh.devices.size
    dp = int(np.prod([mesh.shape[a] for a in dp_axis_names(mesh)]))
    batch_shardable = shape.global_batch % max(dp, 1) == 0
    if shape.kind == "long_decode":
        shard_cache = "data"      # batch=1: the idle DP axis takes seq
    elif "kvseq" in feats:
        shard_cache = "model"
    else:
        shard_cache = False
    rules = shd.make_rules(cfg, mesh, batch_shardable, shard_cache,
                           seq_shard="sp" in feats,
                           moe_cap_shard="moe" in feats)
    if "moefull" in feats:
        # tiny experts (granite-moe d_ff=512): replicate expert weights,
        # shard the dispatch capacity over data x model instead
        rules["moe_cap"] = ("data", "model")
        rules["expert_ff"] = None
    hints = rules if feats & {"sp", "moe", "moefull", "bc", "kvseq"} else None

    t0 = time.time()
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": n_dev, "status": "ok",
    }

    spec_tree = tfm.specs(cfg)
    bspec = shd.batch_pspec(rules)

    if shape.kind == "train":
        params_sds = param_specs(cfg, serve=False)
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(params_sds))
        fsdp = n_params > 10_000_000_000
        result["n_params"] = n_params
        result["fsdp"] = fsdp
        p_ps = shd.pspecs_for_params(
            spec_tree, params_sds, rules, mesh,
            fsdp_axes=dp_axis_names(mesh) if fsdp else ())
        opt_sds = jax.eval_shape(adamw_init, params_sds)
        m_ps = shd.zero_shard_tree(p_ps, params_sds, mesh)
        opt_ps = {"step": P(), "m": m_ps, "v": m_ps}
        batch_sds = train_batch_specs(cfg, shape.global_batch,
                                      shape.seq_len)
        batch_ps = jax.tree_util.tree_map(lambda _: bspec, batch_sds)
        step = build_train_step(cfg, grad_compress="gc8" in feats)
        jitted = jax.jit(
            step,
            in_shardings=(p_ps, opt_ps, batch_ps),
            out_shardings=(p_ps, opt_ps, P()))
        args = (params_sds, opt_sds, batch_sds)
    else:
        params_sds = param_specs(cfg, serve=True)
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(params_sds))
        result["n_params_leaves"] = n_params
        # serve: 2-D weight sharding (model x data) when a pure-TP shard
        # would blow HBM — mirrors weight-gathered serving
        wbytes = sum(l.size * l.dtype.itemsize for l in
                     jax.tree_util.tree_leaves(params_sds))
        model_shard_gb = wbytes / max(mesh.shape.get("model", 1), 1) / 2**30
        fsdp_serve = model_shard_gb > 12.0
        result["serve_weight_gb_per_tp_shard"] = round(model_shard_gb, 2)
        result["weights_2d_sharded"] = fsdp_serve
        p_ps = shd.pspecs_for_params(
            spec_tree, params_sds, rules, mesh,
            fsdp_axes=dp_axis_names(mesh) if fsdp_serve else ())

        # fused-kernel HBM weight-stream accounting: the analytic fused
        # vs multi-launch weight traffic for one forward of this cell's
        # row count (kernels/ops.weight_stream_stats per ternary leaf)
        from repro.launch.hlo_analysis import weight_stream_summary
        from repro.serve.engine import weight_stream_report
        mm_rows = shape.global_batch * (
            shape.seq_len if shape.kind == "prefill"
            else shape.chunk if shape.kind == "mixed" else 1)
        result["weight_stream"] = weight_stream_summary(
            weight_stream_report(params_sds, cfg, decode_batch=mm_rows),
            n_dev)

        if shape.kind == "prefill":
            batch_sds = batch_specs(cfg, shape.global_batch, shape.seq_len)
            caches = cache_sds(cfg, shape.global_batch, shape.seq_len)
            c_ps = shd.tree_pspecs(tfm.cache_specs(cfg, shard_cache), rules)
            batch_ps = jax.tree_util.tree_map(lambda _: bspec, batch_sds)
            step = make_prefill_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_ps, batch_ps, c_ps),
                out_shardings=(bspec, c_ps))
            args = (params_sds, batch_sds, caches)
        elif shape.kind == "mixed":
            # the serving engine's unified step: a (slots, chunk) token
            # grid against the shared seq_len cache, per-slot offsets +
            # valid counts.  Canonical fill: every slot decodes 1 token
            # except one streaming a full prefill chunk.  block_size > 0
            # lowers the block-PAGED step (global KV pool + per-slot
            # block tables) and prices cross-request prefix reuse: the
            # cell's hit_rate fraction of the prefill chunk is served
            # from shared blocks, so those tokens never enter the grid's
            # useful-work count (scheduled_tokens) or the model-FLOPs
            # yardstick — the paged roofline row exposes the saving.
            batch_sds = batch_specs(cfg, shape.global_batch, shape.chunk)
            clen = SDS((shape.global_batch,), jnp.int32)
            nnew = SDS((shape.global_batch,), jnp.int32)
            batch_ps = jax.tree_util.tree_map(lambda _: bspec, batch_sds)
            result["grid_tokens"] = shape.global_batch * shape.chunk
            hit = shape.prefix_hit_tokens
            result["scheduled_tokens"] = shape.scheduled_mixed_tokens
            if shape.block_size:
                from repro.serve.block_pool import default_num_blocks
                nblk_seq = shape.seq_len // shape.block_size
                # ServeEngine's default sizing: the engine rejects
                # anything below a full batch + 1 transient CoW block
                num_blocks = default_num_blocks(
                    shape.global_batch, shape.seq_len, shape.block_size)
                result["block_size"] = shape.block_size
                result["num_blocks"] = num_blocks
                result["prefix_hit_rate"] = shape.hit_rate
                result["prefix_hit_tokens"] = hit
                # in-kernel gather pricing: each mixed step attends the
                # full logical context per slot; the XLA-gather route
                # round-trips that KV through HBM copies (write + read
                # on top of the pool read) while the Pallas kernel DMAs
                # blocks pool->VMEM directly.  benchmarks/roofline.py
                # turns this into gather_bytes_saved_per_dev /
                # t_memory_xla_gather_s for the cell.
                result["gather_context_tokens"] = \
                    shape.global_batch * shape.seq_len
                caches = paged_cache_sds(cfg, shape.global_batch,
                                         num_blocks, shape.block_size)
                c_ps = shd.tree_pspecs(
                    tfm.paged_cache_specs(cfg, bool(shard_cache)), rules)
                tbl = SDS((shape.global_batch, nblk_seq), jnp.int32)
                smap = SDS((shape.global_batch, shape.chunk), jnp.int32)
                step = make_paged_unified_step(cfg)
                jitted = jax.jit(
                    step,
                    in_shardings=(p_ps, batch_ps, c_ps, bspec, bspec, bspec,
                         bspec),
                    out_shardings=(bspec, c_ps))
                args = (params_sds, batch_sds, caches, clen, nnew, tbl,
                        smap)
            else:
                caches = cache_sds(cfg, shape.global_batch, shape.seq_len)
                c_ps = shd.tree_pspecs(tfm.cache_specs(cfg, shard_cache),
                                       rules)
                step = make_unified_step(cfg)
                jitted = jax.jit(
                    step,
                    in_shardings=(p_ps, batch_ps, c_ps, bspec, bspec),
                    out_shardings=(bspec, c_ps))
                args = (params_sds, batch_sds, caches, clen, nnew)
        else:
            batch_sds = batch_specs(cfg, shape.global_batch, 1)
            caches = cache_sds(cfg, shape.global_batch, shape.seq_len)
            c_ps = shd.tree_pspecs(tfm.cache_specs(cfg, shard_cache), rules)
            clen = SDS((shape.global_batch,), jnp.int32)
            batch_ps = jax.tree_util.tree_map(lambda _: bspec, batch_sds)
            step = build_serve_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_ps, batch_ps, c_ps, bspec),
                out_shardings=(bspec, c_ps))
            args = (params_sds, batch_sds, caches, clen)

    with jax.set_mesh(mesh), shd.sharding_hints(hints):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    result["lower_s"] = round(t_lower, 1)
    result["compile_s"] = round(t_compile, 1)

    # --- memory analysis ---------------------------------------------------
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            result["memory"] = {
                k: int(getattr(ma, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes")
                if hasattr(ma, k)
            }
    except Exception as e:  # pragma: no cover
        result["memory_error"] = str(e)
    # device-side estimate from input/output shardings
    arg_bytes = sum(l.size * l.dtype.itemsize
                    for l in jax.tree_util.tree_leaves(args))
    result["global_arg_bytes"] = int(arg_bytes)

    # --- cost analysis -------------------------------------------------------
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        if ca:
            result["cost"] = {
                "flops": float(ca.get("flops", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1)),
            }
    except Exception as e:  # pragma: no cover
        result["cost_error"] = str(e)

    # --- loop-aware HLO analysis (FLOPs + collective bytes) -----------------
    from repro.launch.hlo_analysis import analyze_hlo
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    result["hlo"] = analyze_hlo(hlo, n_dev)
    result["collectives"] = collective_census(hlo, n_dev)  # raw (uncorrected)
    result["hlo_bytes"] = len(hlo)
    return result


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="dryrun_report.json")
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))

    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        for arch in archs:
            for shape in shapes:
                tag = f"{arch} x {shape} [{'multi' if multi else 'single'}]" \
                      f" ({args.variant})"
                try:
                    r = run_cell(arch, shape, mesh, args.variant)
                except Exception as e:
                    r = {"arch": arch, "shape": shape,
                         "variant": args.variant,
                         "mesh": "multi" if multi else "single",
                         "status": "error", "error": repr(e)[:500]}
                print(f"[dryrun] {tag}: {r['status']}"
                      + (f" compile={r.get('compile_s')}s"
                         f" flops={r.get('cost', {}).get('flops', 0):.3g}"
                         if r["status"] == "ok" else
                         f" ({r.get('reason', r.get('error', ''))[:120]})"),
                      flush=True)

                results.append(r)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    print(f"[dryrun] wrote {args.out} ({len(results)} cells)")


if __name__ == "__main__":
    main()
