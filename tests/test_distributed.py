"""Multi-device tests (8 fake CPU devices via subprocess: XLA device count
must be set before jax initializes, so these run in child processes)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest


ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        cwd=ROOT,
        env=env,
        timeout=900,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_distributed_rsi_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.distributed_rsi import distributed_rsi
        from repro.core import rsi, synth_spectrum_matrix, vgg_like_spectrum
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        W = synth_spectrum_matrix(jax.random.PRNGKey(0), 256, 512, vgg_like_spectrum(256))
        Wsh = jax.device_put(W, NamedSharding(mesh, P("data", "model")))
        d = distributed_rsi(Wsh, 32, 3, jax.random.PRNGKey(1), mesh)
        s = rsi(W, 32, 3, jax.random.PRNGKey(1))
        ad = (d.U * d.S[None]) @ d.Vt
        as_ = (s.U * s.S[None]) @ s.Vt
        err = float(jnp.linalg.norm(ad - as_) / jnp.linalg.norm(as_))
        assert err < 1e-4, err
        assert d.U.sharding.spec in (P("data", None), P("data")), d.U.sharding
        assert d.Vt.sharding.spec == P(None, "model"), d.Vt.sharding
        print("OK", err)
    """)
    assert "OK" in out


def test_moe_expert_parallel_matches_local():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_arch
        from repro.models import moe
        from repro.sharding.rules import MeshRules, use_rules
        import dataclasses
        cfg = get_arch("phi3.5-moe-42b-a6.6b", reduced=True)
        cfg = dataclasses.replace(cfg, n_experts=8, capacity_factor=8.0)  # no drops
        p = moe.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
        ref, aux_ref = moe._moe_local(p, x, cfg)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        rules = MeshRules(mesh)
        with use_rules(rules):
            got, aux = jax.jit(lambda p, x: moe.moe_forward(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)
        # aux is a per-data-shard estimator in EP mode (mean of per-shard
        # load-balance terms) vs the global estimator locally: close, not equal
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=0.3)
        print("OK")
    """)
    assert "OK" in out


def test_pipeline_parallel_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.train.pipeline_parallel import gpipe_apply
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
        L, d = 8, 16
        ws = jax.random.normal(jax.random.PRNGKey(0), (L, d, d)) / d**0.5
        def block(w, x):
            return jnp.tanh(x @ w)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
        # sequential reference
        ref = x
        for i in range(L):
            ref = block(ws[i], ref)
        fn = gpipe_apply(lambda lp, h: block(lp["w"], h), mesh, n_microbatches=4)
        got = jax.jit(fn)({"w": ws}, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    """Save on a 2x4 mesh, restore on 8x1 — the elastic restart path."""
    out = _run("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import checkpointer as ckpt
        from jax.sharding import AxisType
        m1 = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        m2 = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        W = jnp.arange(64*32, dtype=jnp.float32).reshape(64, 32)
        state = {"w": jax.device_put(W, NamedSharding(m1, P("data", "model")))}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(state, d, 3)
            sh2 = {"w": NamedSharding(m2, P("data", "model"))}
            restored, _ = ckpt.restore(state, d, shardings=sh2)
            np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(W))
            assert restored["w"].sharding.mesh.shape["data"] == 8
        print("OK")
    """)
    assert "OK" in out


def test_powersgd_compressed_allreduce():
    """Compressed DP all-reduce approximates the dense mean and cuts bytes."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from repro.core.gradient_compression import (
            PowerSGDConfig, init_powersgd, compress_allreduce, comm_bytes)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        cfg = PowerSGDConfig(rank=8, min_size=1024)
        # shared low-rank signal + small per-device noise: a rank-8 sketch of
        # the MEAN must capture the signal (pure-noise means are full-rank and
        # only converge via error feedback over steps)
        key = jax.random.PRNGKey(0)
        u = jax.random.normal(key, (64, 8)); v = jax.random.normal(jax.random.PRNGKey(2), (8, 96))
        noise = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (8, 64, 96))
        grads_per_dev = (u @ v)[None] + noise  # (8, 64, 96)
        state = init_powersgd({"w": grads_per_dev[0]}, jax.random.PRNGKey(1), cfg)
        def body(g, st):
            out, st2 = compress_allreduce({"w": g}, st, "data", cfg)
            return out["w"], None
        f = jax.shard_map(lambda g: body(g[0], state)[0][None],
                          mesh=mesh,
                          in_specs=jax.sharding.PartitionSpec("data"),
                          out_specs=jax.sharding.PartitionSpec("data"),
                          check_vma=False)
        got = f(grads_per_dev)
        dense_mean = jnp.mean(grads_per_dev, axis=0)
        # error feedback handles the residual over steps; single step should
        # still correlate strongly for these low-rank grads
        corr = float(jnp.sum(got[0]*dense_mean) /
                     (jnp.linalg.norm(got[0])*jnp.linalg.norm(dense_mean)+1e-9))
        assert corr > 0.7, corr
        dense_b, comp_b = comm_bytes({"w": grads_per_dev[0]}, cfg)
        assert comp_b < dense_b / 3
        print("OK", corr)
    """)
    assert "OK" in out
