"""Optimizer substrate: AdamW math vs a NumPy oracle; schedules; gradient
compression convergence parity."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import adamw, compress


def test_adamw_matches_numpy_reference():
    cfg = adamw.AdamWConfig(lr=1e-2, b1=0.9, b2=0.99, eps=1e-8,
                            weight_decay=0.01, clip_norm=0.0,
                            warmup_steps=0, decay_steps=10**9,
                            min_lr_ratio=1.0)
    gen = np.random.default_rng(0)
    p0 = gen.normal(0, 1, (4, 3)).astype(np.float32)
    params = {"w": jnp.asarray(p0)}
    opt = adamw.init(params)

    m = np.zeros_like(p0)
    v = np.zeros_like(p0)
    p_ref = p0.copy()
    for t in range(1, 6):
        g = gen.normal(0, 1, p0.shape).astype(np.float32)
        params, opt, _ = adamw.update({"w": jnp.asarray(g)}, opt, params, cfg)
        m = 0.9 * m + 0.1 * g
        v = 0.99 * v + 0.01 * g * g
        mh = m / (1 - 0.9 ** t)
        vh = v / (1 - 0.99 ** t)
        p_ref = p_ref - 1e-2 * (mh / (np.sqrt(vh) + 1e-8) + 0.01 * p_ref)
    np.testing.assert_allclose(np.asarray(params["w"]), p_ref, rtol=1e-5,
                               atol=1e-6)


def test_clip_by_global_norm():
    g = {"a": jnp.ones((10,)) * 3.0}
    clipped, gn = adamw.clip_by_global_norm(g, 1.0)
    assert float(gn) == np.sqrt(90.0).astype(np.float32)
    np.testing.assert_allclose(float(adamw.global_norm(clipped)), 1.0,
                               rtol=1e-5)


def test_schedule_shape():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, decay_steps=100,
                            min_lr_ratio=0.1)
    lrs = [float(adamw.schedule(cfg, jnp.int32(s))) for s in
           (0, 5, 10, 55, 100, 200)]
    assert lrs[1] < lrs[2]          # warmup rising
    assert lrs[2] == 1.0            # peak
    assert lrs[3] < lrs[2]          # decaying
    assert abs(lrs[4] - 0.1) < 1e-6  # floor
    assert abs(lrs[5] - 0.1) < 1e-6


def test_quantize_roundtrip_error_bounded():
    gen = np.random.default_rng(1)
    x = jnp.asarray(gen.normal(0, 3, (64,)).astype(np.float32))
    q, s = compress.quantize_int8(x)
    err = np.abs(compress.dequantize(q, s) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_accumulates():
    """EF compression: averaged compressed grads converge to true mean."""
    gen = np.random.default_rng(2)
    g_true = gen.normal(0, 1, (32,)).astype(np.float32)
    residual = {"w": jnp.zeros((32,), jnp.float32)}
    total = np.zeros(32, np.float64)
    n = 50
    for _ in range(n):
        q, s, residual_new = compress.ef_compress_tree(
            {"w": jnp.asarray(g_true)}, residual)
        residual = residual_new
        total += np.asarray(compress.dequantize(q["w"], s["w"]))
    # with error feedback, the *sum* of dequantized grads tracks the sum of
    # true grads to within one quantisation step
    drift = np.abs(total / n - g_true).max()
    assert drift < 0.01, drift


def test_compressed_psum_shard_map():
    """compressed_psum inside shard_map == exact mean within int8 error."""
    import os
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import mesh as meshlib
        from repro.optim import compress
        mesh = meshlib.make_mesh((4,), ("pod",))
        g = jnp.asarray(np.random.default_rng(0).normal(
            0, 1, (4, 64)).astype(np.float32))
        res = jnp.zeros((4, 64), jnp.float32)
        def f(g, r):
            out, r2 = compress.compressed_psum({"w": g[0]}, {"w": r[0]},
                                               "pod")
            return out["w"][None], r2["w"][None]
        fn = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                       out_specs=(P("pod"), P("pod")))
        out, _ = fn(g, res)
        want = np.asarray(g).mean(0)
        got = np.asarray(out)[0]
        err = np.abs(got - want).max()
        print("OK" if err < 0.05 else f"BAD {err}")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=".", timeout=300)
    assert "OK" in out.stdout, out.stdout + out.stderr
