"""Multi-device fused-parity suite (ISSUE-2 tentpole, part 2).

The fused xla routes stack phase / bit-plane patterns along M, which
doubles (or ``bits``-tuples) the per-device M tile under GSPMD.  This
suite proves, on 8 virtual CPU devices (2 data x 4 model):

  * fused=True is bit-identical to fused=False under the mesh (dyadic
    scales make every epilogue product exact, so equality is
    well-defined across launch topologies);
  * the sharded fused result equals the single-logical-device result;
  * the fused path never replicates W: the compiled HLO contains no
    full-shape int8 W tensor (the weight parameter stays model-sharded
    through the stacked dot).

Runs in a SUBPROCESS because the main pytest process is pinned to one
CPU device (jax locks the device count at first init).
"""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.ternary import TernaryScales, quantize_act_ternary, \\
        quantize_act_unsigned
    from repro.core.weights import TernaryWeight, ternarize_weight
    from repro.distrib import sharding as shd
    from repro.kernels import ops

    assert jax.device_count() == 8, jax.device_count()
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    m, k, n = 32, 64, 128

    # dyadic per-column scales: every epilogue product is exact in f32,
    # so bit-for-bit equality across launch topologies is well-defined
    idx = np.arange(n)
    w1 = (1.0 + 0.5 * (idx % 2)) * 2.0 ** ((idx % 5) - 2)
    w2 = (1.0 + 0.5 * ((idx + 1) % 2)) * 2.0 ** (((idx + 2) % 5) - 2)
    scales = TernaryScales(jnp.asarray(w1, jnp.float32),
                           jnp.asarray(w2, jnp.float32), False)
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    tw0 = ternarize_weight(w, "asymmetric", per_channel=True)
    tw = TernaryWeight(tw0.data, scales, False, tw0.k_dim)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    qx, _ = quantize_act_ternary(x)
    sx = TernaryScales(jnp.float32(0.75), jnp.float32(0.375), sym=False)

    # single-logical-device references (default CPU device, no mesh)
    want_fused = np.asarray(ops.tim_matmul(qx, tw, sx, impl="xla",
                                           fused=True))
    want_two = np.asarray(ops.tim_matmul(qx, tw, sx, impl="xla",
                                         fused=False))
    np.testing.assert_array_equal(want_fused, want_two)

    # shard: activations over data (M), weight codes + scales over
    # model (N) — the TP serving layout
    qx_sh = jax.device_put(qx, NamedSharding(mesh, P("data", None)))
    tw_sh = TernaryWeight(
        jax.device_put(tw.data, NamedSharding(mesh, P(None, "model"))),
        TernaryScales(
            jax.device_put(tw.scales.pos, NamedSharding(mesh, P("model"))),
            jax.device_put(tw.scales.neg, NamedSharding(mesh, P("model"))),
            False),
        False, tw.k_dim)

    fused_fn = jax.jit(lambda q, wt: ops.tim_matmul(q, wt, sx, impl="xla",
                                                    fused=True))
    two_fn = jax.jit(lambda q, wt: ops.tim_matmul(q, wt, sx, impl="xla",
                                                  fused=False))
    with jax.set_mesh(mesh), shd.sharding_hints({"batch": "data"}):
        fused_c = fused_fn.lower(qx_sh, tw_sh).compile()
        two_c = two_fn.lower(qx_sh, tw_sh).compile()
    got_fused = np.asarray(fused_c(qx_sh, tw_sh))
    got_two = np.asarray(two_c(qx_sh, tw_sh))

    np.testing.assert_array_equal(got_fused, got_two)
    np.testing.assert_array_equal(got_fused, want_fused)
    print("two-phase fused parity ok")

    # no W replication: a gathered weight would materialize the full
    # (K, N) int8 tensor in the partitioned module; the per-device
    # shard is (K, N/4)
    hlo = fused_c.as_text()
    assert f"s8[{k},{n}]" not in hlo, "fused path replicated W"
    assert f"s8[{k},{n // 4}]" in hlo, "expected model-sharded W tile"
    print("no W replication ok")

    # --- 2-D (fsdp x tp) sharded serving layout --------------------------
    # FSDP serving shards the weight codes over BOTH mesh axes (K over
    # data, N over model) so no single TP shard must hold a full K
    # column block.  The fused single-stream route must (a) stay bit-
    # identical to two-launch and to the single-device result, (b)
    # never materialize the full int8 W, and (c) keep its analytic
    # weight-stream win — weight_stream_report is layout-independent.
    tw_2d = TernaryWeight(
        jax.device_put(tw.data, NamedSharding(mesh, P("data", "model"))),
        TernaryScales(
            jax.device_put(tw.scales.pos, NamedSharding(mesh, P("model"))),
            jax.device_put(tw.scales.neg, NamedSharding(mesh, P("model"))),
            False),
        False, tw.k_dim)
    with jax.set_mesh(mesh), shd.sharding_hints({"batch": "data"}):
        fused_2d = fused_fn.lower(qx_sh, tw_2d).compile()
        two_2d = two_fn.lower(qx_sh, tw_2d).compile()
    got_f2 = np.asarray(fused_2d(qx_sh, tw_2d))
    got_t2 = np.asarray(two_2d(qx_sh, tw_2d))
    np.testing.assert_array_equal(got_f2, got_t2)
    np.testing.assert_array_equal(got_f2, want_fused)
    hlo2 = fused_2d.as_text()
    assert f"s8[{k},{n}]" not in hlo2, "2-D fused path replicated W"

    from repro.configs import get_config
    from repro.serve.engine import weight_stream_report
    cfg_ws = get_config("granite-34b", smoke=True)
    cfg_ws = cfg_ws.replace(ternary=cfg_ws.ternary.replace(
        encoding="asymmetric", act_mode="ternary"))
    rep = weight_stream_report({"layer": {"q": {"w": tw_2d}}}, cfg_ws,
                               decode_batch=m)
    assert rep["weight_bytes_streamed_fused"] > 0
    assert rep["weight_bytes_streamed_unfused"] \\
        == 2 * rep["weight_bytes_streamed_fused"], rep
    print("2-D fsdp x tp fused parity ok")

    # bit-serial (int2 and int4 policy points): planes stack bits x M
    for bits in (2, 4):
        qa, step = quantize_act_unsigned(jnp.abs(x), bits=bits)
        want_bs = np.asarray(ops.tim_matmul_bitserial(
            qa, step, tw, bits=bits, impl="xla", fused=True))
        qa_sh = jax.device_put(qa, NamedSharding(mesh, P("data", None)))
        bs_fn = jax.jit(lambda q, s, wt: ops.tim_matmul_bitserial(
            q, s, wt, bits=bits, impl="xla", fused=True))
        bs2_fn = jax.jit(lambda q, s, wt: ops.tim_matmul_bitserial(
            q, s, wt, bits=bits, impl="xla", fused=False))
        with jax.set_mesh(mesh), shd.sharding_hints({"batch": "data"}):
            bs_c = bs_fn.lower(qa_sh, step, tw_sh).compile()
            got_bs = np.asarray(bs_c(qa_sh, step, tw_sh))
            got_bs2 = np.asarray(bs2_fn(qa_sh, step, tw_sh))
        np.testing.assert_allclose(got_bs, want_bs, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got_bs, got_bs2, rtol=1e-6, atol=1e-6)
        assert f"s8[{k},{n}]" not in bs_c.as_text(), \\
            f"bit-serial bits={bits} replicated W"
        print(f"bit-serial bits={bits} fused parity ok")
""")


def test_multidev_fused_parity():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "two-phase fused parity ok" in proc.stdout
    assert "no W replication ok" in proc.stdout
    assert "2-D fsdp x tp fused parity ok" in proc.stdout
    assert "bit-serial bits=2 fused parity ok" in proc.stdout
    assert "bit-serial bits=4 fused parity ok" in proc.stdout
