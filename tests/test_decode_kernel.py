"""Flash-decode kernel suite: Pallas kernel vs the dense einsum oracle.

Everything runs in interpret mode on CPU (the same contract as the other
kernel tests): parity across GQA ratios, ragged per-slot ``n_valid``,
sliding-window ``rotate_mask``, the fully-masked-row zero guard, and the
dispatch-table routing that picks the kernel by shape/platform.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.models.attention import decode_attention
from repro.runtime import dispatch
from repro.runtime.dispatch import DECODE_MIN_SEQ, DispatchConfig, use_dispatch

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / shape[-1] ** 0.25).astype(dtype)


def _inputs(B, S, KV, G, hd, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], (B, 1, KV * G, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("G", [1, 4, 8])  # GQA ratio H/KV
def test_decode_kernel_gqa_ratios(G, dtype):
    B, S, KV, hd = 2, 64, 2, 16
    q, k, v = _inputs(B, S, KV, G, hd, dtype)
    valid = jnp.arange(S)[None, :] < jnp.array([[S], [S // 2]])
    got = decode_attention_pallas(q, k, v, valid, bs=32, interpret=True)
    want = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("bs", [8, 16, 64])
def test_decode_kernel_ragged_n_valid(bs):
    """Per-slot n_valid masking is STRICT: poison beyond each slot's valid
    prefix must never leak, for any block size (incl. bs > S)."""
    B, S, KV, G, hd = 4, 48, 2, 4, 16
    dtype = jnp.float32
    q, k, v = _inputs(B, S, KV, G, hd, dtype, seed=1)
    n_valid = jnp.array([1, 17, 48, 5], jnp.int32)
    valid = jnp.arange(S)[None, :] < n_valid[:, None]
    tail = ~valid[:, :, None, None]
    k_poison = jnp.where(tail, jnp.asarray(1e4, dtype), k)
    v_poison = jnp.where(tail, jnp.asarray(1e4, dtype), v)
    got = decode_attention_pallas(q, k_poison, v_poison, valid, bs=bs, interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))
    want = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL[dtype])


def test_decode_kernel_rotate_mask_ring():
    """Sliding-window ring masks (arbitrary (B, S) validity patterns, not
    just prefixes) are honored position-by-position."""
    B, S, KV, G, hd = 3, 32, 1, 4, 16
    dtype = jnp.float32
    q, k, v = _inputs(B, S, KV, G, hd, dtype, seed=2)
    rng = np.random.default_rng(0)
    rotate = jnp.asarray(rng.integers(0, 2, size=(B, S)).astype(bool))
    rotate = rotate.at[:, 0].set(True)  # keep every row non-empty here
    got = decode_attention_pallas(q, k, v, rotate, bs=16, interpret=True)
    want = ref.decode_attention_ref(q, k, v, rotate)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_fully_masked_rows_are_zero(dtype):
    """Regression: a slot whose valid mask is all-False (empty/inactive pool
    slot) must produce ZEROS — not NaN, not a uniform average of garbage —
    from BOTH the kernel and the dense reference, while live rows are
    untouched."""
    B, S, KV, G, hd = 3, 16, 2, 2, 8
    q, k, v = _inputs(B, S, KV, G, hd, dtype, seed=3)
    n_valid = jnp.array([0, 7, 0], jnp.int32)
    valid = jnp.arange(S)[None, :] < n_valid[:, None]

    for got in (
        ref.decode_attention_ref(q, k, v, valid),
        decode_attention_pallas(q, k, v, valid, bs=8, interpret=True),
    ):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[0], np.zeros_like(got[0]))
        np.testing.assert_array_equal(got[2], np.zeros_like(got[2]))
        assert np.abs(got[1]).sum() > 0  # the live row still attends

    # the model-layer entry point (n_valid / rotate_mask forms) gets the
    # same guard
    via_n_valid = decode_attention(q, k, v, n_valid)
    via_mask = decode_attention(q, k, v, 0, rotate_mask=valid)
    assert np.isfinite(np.asarray(via_n_valid, np.float32)).all()
    np.testing.assert_array_equal(
        np.asarray(via_n_valid, np.float32)[0], np.zeros((1, KV * G, hd), np.float32)
    )
    np.testing.assert_array_equal(
        np.asarray(via_mask, np.float32), np.asarray(via_n_valid, np.float32)
    )


def test_decode_kernel_odd_seq_falls_back_to_small_blocks():
    """S not divisible by the requested block: the wrapper shrinks bs until
    it tiles, staying exact."""
    B, S, KV, G, hd = 2, 24, 2, 2, 16  # 24 -> bs 16 -> 8
    q, k, v = _inputs(B, S, KV, G, hd, jnp.float32, seed=4)
    valid = jnp.arange(S)[None, :] < jnp.array([[24], [9]])
    got = decode_attention_pallas(q, k, v, valid, bs=16, interpret=True)
    want = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL[jnp.float32])


# --------------------------------------------------------------------------- #
# dispatch routing
# --------------------------------------------------------------------------- #
def test_choose_decode_path_auto_table():
    q_shape, kv_deep, kv_shallow = (4, 1, 8, 64), (4, 2048, 2, 64), (4, 64, 2, 64)
    cfg = DispatchConfig()
    # auto: kernel on TPU for deep caches, einsum for shallow or off-TPU
    assert dispatch.choose_decode_path(q_shape, kv_deep, config=cfg, platform="tpu") == "pallas"
    assert dispatch.choose_decode_path(q_shape, kv_shallow, config=cfg, platform="tpu") == "xla"
    assert dispatch.choose_decode_path(q_shape, kv_deep, config=cfg, platform="cpu") == "xla"
    assert kv_shallow[1] < DECODE_MIN_SEQ <= kv_deep[1]
    # a depth that is not a multiple of 128 cannot be tiled on the chip
    kv_misaligned = (4, 1000, 2, 64)
    assert dispatch.choose_decode_path(q_shape, kv_misaligned, config=cfg, platform="tpu") == "xla"
    # pins override the table everywhere
    pinned = DispatchConfig(backend="pallas")
    assert dispatch.choose_decode_path(q_shape, kv_shallow, config=pinned, platform="cpu") == "pallas"
    per_op = DispatchConfig(overrides=(("decode_attention", "xla"),))
    assert dispatch.choose_decode_path(q_shape, kv_deep, config=per_op, platform="tpu") == "xla"


def test_decode_attention_dispatch_entry_counts_and_matches():
    """The dispatch entry point routes to the kernel under a pallas pin
    (interpret mode on CPU), matches the reference, and records a hit."""
    B, S, KV, G, hd = 2, 32, 2, 4, 16
    q, k, v = _inputs(B, S, KV, G, hd, jnp.float32, seed=5)
    valid = jnp.arange(S)[None, :] < jnp.array([[32], [11]])
    dispatch.reset_counters()
    with use_dispatch(backend="pallas"):
        got = dispatch.decode_attention(q, k, v, valid)
    want = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    hits = dispatch.counters_by_path()
    assert hits.get(("decode_attention", "pallas"), 0) >= 1


def test_engine_decode_runs_through_dispatch_counter():
    """End-to-end: a fused engine block records decode_attention sites in
    the dispatch counters (one per scanned attention call site)."""
    from repro.configs.registry import get_arch
    from repro.models.model import build_model
    from repro.serving import Engine, Request

    cfg = get_arch("llama3.2-1b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dispatch.reset_counters()
    eng = Engine(model, params, n_slots=2, max_len=16, decode_block=4)
    eng.submit(Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=5))
    while eng.has_work:
        eng.step()
    hits = dispatch.counters_by_path()
    assert hits.get(("decode_attention", "xla"), 0) >= 1  # CPU auto -> einsum
