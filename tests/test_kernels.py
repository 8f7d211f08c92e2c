"""Per-kernel allclose tests vs the ref.py jnp oracles (interpret mode),
sweeping shapes and dtypes, plus hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.lowrank_matmul import lowrank_matmul_pallas
from repro.kernels.sketch_matmul import sketch_matmul_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.flash_attention import flash_attention_pallas

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / shape[-1] ** 0.25).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,K,N", [(64, 128, 32), (100, 257, 65), (256, 512, 128), (33, 70, 200)]
)
def test_sketch_matmul_allclose(M, K, N, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a, b = _rand(k1, (M, K), dtype), _rand(k2, (K, N), dtype)
    got = sketch_matmul_pallas(a, b, bm=32, bn=32, bk=64, interpret=True)
    want = ref.sketch_matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,r,N", [(64, 128, 16, 64), (100, 250, 32, 48), (256, 512, 64, 128)])
def test_lowrank_matmul_allclose(M, K, r, N, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x, A, B = _rand(ks[0], (M, K), dtype), _rand(ks[1], (K, r), dtype), _rand(ks[2], (r, N), dtype)
    got = lowrank_matmul_pallas(x, A, B, bm=32, bk=64, interpret=True)
    want = ref.lowrank_matmul_ref(x, A, B)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_lowrank_matmul_wrapper_batched():
    from repro.runtime.dispatch import use_dispatch

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = _rand(ks[0], (2, 5, 96), jnp.float32)
    A = _rand(ks[1], (96, 8), jnp.float32)
    B = _rand(ks[2], (8, 40), jnp.float32)
    # pin the Pallas backend: auto on CPU would route to the two-GEMM
    # fallback, which IS the reference — the test would compare ref to ref
    with use_dispatch(backend="pallas"):
        got = ops.lowrank_matmul(x, A, B)
    want = ref.lowrank_matmul_ref(x.reshape(-1, 96), A, B).reshape(2, 5, 40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,nh,hd,s,chunk", [(2, 64, 4, 16, 16, 16), (1, 128, 2, 8, 32, 32), (2, 96, 3, 16, 8, 32)])
def test_ssd_scan_allclose(B, L, nh, hd, s, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = _rand(ks[0], (B, L, nh, hd), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, nh), jnp.float32))
    B_in = _rand(ks[2], (B, L, s), dtype)
    C_in = _rand(ks[3], (B, L, s), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (nh,), jnp.float32) * 0.3)
    xbar = (x.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    got_y, got_state = ssd_scan_pallas(x, dt, B_in, C_in, A, chunk=chunk, interpret=True)
    want_y, want_state = ref.ssd_scan_ref(xbar, dt, B_in, C_in, A)
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32),
        rtol=0.06 if dtype == jnp.bfloat16 else 1e-4,
        atol=0.06 if dtype == jnp.bfloat16 else 1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(got_state), np.asarray(want_state),
        rtol=0.06 if dtype == jnp.bfloat16 else 1e-4,
        atol=0.06 if dtype == jnp.bfloat16 else 1e-4,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,S,H,hd", [(1, 64, 2, 16), (2, 128, 4, 32)])
def test_flash_attention_allclose(B, S, H, hd, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, H, hd), dtype)
    v = _rand(ks[2], (B, S, H, hd), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, bq=32, bkv=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    M=st.integers(8, 80),
    K=st.integers(8, 120),
    r=st.integers(1, 16),
    N=st.integers(8, 64),
)
def test_lowrank_matmul_property(seed, M, K, r, N):
    """Property: fused kernel == two exact matmuls for arbitrary shapes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x, A, B = (
        jax.random.normal(ks[0], (M, K)),
        jax.random.normal(ks[1], (K, r)),
        jax.random.normal(ks[2], (r, N)),
    )
    got = lowrank_matmul_pallas(x, A, B, bm=16, bk=32, interpret=True)
    want = (x @ A) @ B
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_ragged_n_valid(dtype):
    """Regression: ``decode_attention`` masks strictly by PER-SEQUENCE
    n_valid.  Sequences of different lengths share one cache tensor; stale
    garbage beyond each sequence's n_valid must never leak into its output
    (the continuous-batching invariant)."""
    from repro.models.attention import decode_attention

    B, S, KV, G, hd = 3, 12, 2, 2, 16
    H = KV * G
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (B, 1, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    n_valid = jnp.array([3, 12, 7], jnp.int32)

    # poison every slot past each sequence's n_valid with huge values: if the
    # mask were batch-wide (or off by one), the softmax would latch onto them
    tail = jnp.arange(S)[None, :, None, None] >= n_valid[:, None, None, None]
    k_poison = jnp.where(tail, jnp.asarray(1e4, dtype), k)
    v_poison = jnp.where(tail, jnp.asarray(1e4, dtype), v)

    got = decode_attention(q, k_poison, v_poison, n_valid)
    assert bool(jnp.all(jnp.isfinite(got)))
    # per-sequence reference: each row attends over ONLY its valid prefix
    for b in range(B):
        nb = int(n_valid[b])
        want = decode_attention(
            q[b : b + 1], k[b : b + 1, :nb], v[b : b + 1, :nb], nb
        )
        np.testing.assert_allclose(
            np.asarray(got[b : b + 1], np.float32),
            np.asarray(want, np.float32),
            **TOL[dtype],
        )
    # scalar n_valid (the classic fixed-shape path) still broadcasts
    uniform = decode_attention(q, k, v, 5)
    uniform_vec = decode_attention(q, k, v, jnp.full((B,), 5, jnp.int32))
    np.testing.assert_array_equal(np.asarray(uniform), np.asarray(uniform_vec))


def test_kernel_flops_match_roofline_model():
    """rsi_flops bookkeeping consistency (used by the benchmark layer)."""
    from repro.core.rsi import rsi_flops

    assert rsi_flops(4096, 25088, 200, 2) > rsi_flops(4096, 25088, 200, 1)
    assert rsi_flops(100, 100, 10, 1) > 0
