"""Substrate tests: optimizer, train step, checkpoint, fault tolerance, data."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import zstandard

from repro.configs.registry import get_arch
from repro.models.model import build_model
from repro.train import optimizer as opt_mod
from repro.train.train_step import TrainState, init_train_state, make_train_step
from repro.checkpoint import checkpointer as ckpt
from repro.runtime.fault_tolerance import (
    ElasticReshard,
    RetryableStep,
    StepWatchdog,
    TrainLoopRunner,
)
from repro.data.synthetic import SyntheticLM, markov_tokens


def quadratic_loss(params, _batch):
    return jnp.sum(jnp.square(params["w"] - 3.0)) + jnp.square(params["b"] + 1.0)[0]


@pytest.mark.parametrize("opt_name", ["adamw", "adafactor", "sgdm"])
def test_optimizer_converges_quadratic(opt_name):
    opt = {
        "adamw": opt_mod.adamw(opt_mod.constant_schedule(0.1), weight_decay=0.0),
        # adafactor's RMS-normalized updates need a decaying lr to settle
        "adafactor": opt_mod.adafactor(opt_mod.linear_schedule(0.5, 1, 300)),
        "sgdm": opt_mod.sgdm(opt_mod.constant_schedule(0.05)),
    }[opt_name]
    params = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((1,))}
    state = opt.init(params)
    step = jnp.zeros((), jnp.int32)
    for i in range(300):
        grads = jax.grad(quadratic_loss)(params, None)
        updates, state = opt.update(grads, state, params, step + i)
        params = opt_mod.apply_updates(params, updates)
    assert float(quadratic_loss(params, None)) < 1e-2


def test_train_step_reduces_loss():
    cfg = get_arch("llama3.2-1b", reduced=True)
    model = build_model(cfg)
    opt = opt_mod.adamw(opt_mod.cosine_schedule(3e-3, 10, 200), weight_decay=0.01)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(model, opt))
    data = SyntheticLM(cfg, batch=8, seq=32, seed=0)
    losses = []
    for i in range(30):
        batch = jax.tree_util.tree_map(jnp.asarray, data.at_step(i))
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses


def test_grad_accumulation_matches_full_batch():
    cfg = get_arch("mamba2-130m", reduced=True)
    model = build_model(cfg)
    opt = opt_mod.sgdm(opt_mod.constant_schedule(0.1), momentum=0.0)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    data = SyntheticLM(cfg, batch=8, seq=16, seed=1)
    batch = jax.tree_util.tree_map(jnp.asarray, data.at_step(0))
    s1, m1 = jax.jit(make_train_step(model, opt, accum_steps=1))(state, batch)
    s2, m2 = jax.jit(make_train_step(model, opt, accum_steps=4))(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    l1 = jax.tree_util.tree_leaves(s1.params)
    l2 = jax.tree_util.tree_leaves(s2.params)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-2, rtol=2e-2
        )


def test_checkpoint_roundtrip_and_atomicity():
    cfg = get_arch("llama3.2-1b", reduced=True)
    model = build_model(cfg)
    opt = opt_mod.adamw(opt_mod.constant_schedule(1e-3))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(state, d, 7, extra={"arch": cfg.name})
        # stale tmp dir from a "crashed" save must be ignored + cleaned
        os.makedirs(os.path.join(d, "step_9.tmp"), exist_ok=True)
        assert ckpt.latest_step(d) == 7
        restored, manifest = ckpt.restore(state, d)
        assert manifest["extra"]["arch"] == cfg.name
        for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_detects_corruption():
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(state, d, 1)
        leaf = os.path.join(d, "step_1", "leaf_00000.npy.zst")
        with open(leaf, "wb") as f:
            f.write(zstandard.ZstdCompressor().compress(b"\x00" * 64))
        with pytest.raises(IOError):
            ckpt.restore(state, d)


def test_async_checkpointer_retention():
    state = {"w": jnp.ones((8,))}
    with tempfile.TemporaryDirectory() as d:
        c = ckpt.Checkpointer(d, keep=2)
        for s in (1, 2, 3, 4):
            c.save_async(state, s)
        c.wait()
        steps = sorted(
            int(x.split("_")[1]) for x in os.listdir(d) if x.startswith("step_")
        )
        assert steps == [3, 4]


def test_restart_resumes_identically():
    """Crash at step 5, restore from checkpoint at 4, resume -> same state as
    an uninterrupted run (determinism of data + step)."""
    cfg = get_arch("mamba2-130m", reduced=True)
    model = build_model(cfg)
    opt = opt_mod.adamw(opt_mod.constant_schedule(1e-3))
    data = SyntheticLM(cfg, batch=4, seq=16, seed=3)
    step_fn = jax.jit(make_train_step(model, opt))

    def fresh():
        return init_train_state(model, opt, jax.random.PRNGKey(0))

    with tempfile.TemporaryDirectory() as d:
        c = ckpt.Checkpointer(d, keep=3)
        runner = TrainLoopRunner(step_fn, data.at_step, c, save_every=2)
        # uninterrupted reference
        ref_state, _ = TrainLoopRunner(step_fn, data.at_step, None, save_every=10**9).run(
            fresh(), 8, shard_fn=lambda b: jax.tree_util.tree_map(jnp.asarray, b)
        )
        # interrupted run
        with pytest.raises(RuntimeError):
            runner.run(
                fresh(),
                8,
                shard_fn=lambda b: jax.tree_util.tree_map(jnp.asarray, b),
                fail_at=lambda s: s == 5,
            )
        c.wait()
        last = ckpt.latest_step(d)
        assert last == 4
        restored, _ = ckpt.restore(fresh(), d)
        resumed, _ = TrainLoopRunner(step_fn, data.at_step, None, save_every=10**9).run(
            restored,
            8,
            shard_fn=lambda b: jax.tree_util.tree_map(jnp.asarray, b),
            start_step=last,
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(ref_state.params),
            jax.tree_util.tree_leaves(resumed.params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_watchdog_flags_stragglers():
    w = StepWatchdog(straggler_factor=2.0)
    for i in range(10):
        w.observe(i, 1.0)
    assert w.observe(10, 5.0) is True
    assert 10 in w.straggler_steps
    assert w.observe(11, 1.1) is False


def test_retryable_step():
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("link flap")
        return x + 1

    r = RetryableStep(flaky, max_retries=3)
    assert r(1) == 2
    assert r.total_retries == 2


def test_data_determinism():
    a = markov_tokens(0, 5, 4, 16, 1000)
    b = markov_tokens(0, 5, 4, 16, 1000)
    c = markov_tokens(0, 6, 4, 16, 1000)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    assert a.min() >= 0 and a.max() < 1000
