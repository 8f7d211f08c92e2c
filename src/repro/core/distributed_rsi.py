"""Mesh-distributed RSI via shard_map.

Compresses a weight matrix that is *already sharded* across a (data, model)
mesh — the situation on a real pod, where e.g. qwen2-72b's 8192 x 29568 FFN
kernels live FSDP x TP sharded and must never be gathered to one host.

Layout (per shard_map block):
    W    : P(row_axis, col_axis)   block (C/dp, D/tp)
    Omega: P(col_axis, None)       block (D/tp, l)
    X    : P(row_axis, None)       block (C/dp, l)
    Y    : P(col_axis, None)       block (D/tp, l)

Communication per power iteration (the TPU-native part — see DESIGN.md §4):
    * psum over col_axis of the partial X      — (C/dp)·l words
    * psum over row_axis of the partial Y      — (D/tp)·l words
    * two psums of l x l Gram matrices         — CholeskyQR2
No tall matrix is ever gathered; the only replicated objects are l x l.

The epilogue SVD uses the Gram trick (G = Y^T Y psum -> eigh, l x l), so the
result factors come back *already sharded*: U as P(row_axis, None), Vt as
P(None, col_axis) — exactly the specs a TP-sharded LowRankLinear wants.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.rsi import RSIResult

__all__ = ["distributed_rsi", "distributed_rsi_factors"]


def _psum(x, axis_name):
    if axis_name is None:
        return x
    return jax.lax.psum(x, axis_name)


def _dist_cholesky_qr(X, row_axis, *, eps=0.0):
    """CholeskyQR with the Gram matrix psum-reduced over the row shards."""
    x32 = X.astype(jnp.float32)
    g = _psum(x32.T @ x32, row_axis)
    if eps:
        g = g + eps * jnp.trace(g) / g.shape[0] * jnp.eye(g.shape[0], dtype=g.dtype)
    r = jnp.linalg.cholesky(g.T).T
    q = jax.scipy.linalg.solve_triangular(r.T, x32.T, lower=True).T
    return q.astype(X.dtype)


def _dist_cholesky_qr2(X, row_axis):
    return _dist_cholesky_qr(_dist_cholesky_qr(X, row_axis, eps=1e-12), row_axis)


def _rsi_block(W, omega, *, k, q, row_axis, col_axis):
    """shard_map body.  W block (c, d); omega block (d, l)."""

    def mm(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)

    Y = omega.astype(jnp.float32)
    W32 = W.astype(jnp.float32)
    X = None
    for _ in range(q):
        X = _psum(mm(W32, Y), col_axis)  # (c, l) summed over D shards
        X = _dist_cholesky_qr2(X, row_axis)
        Y = _psum(mm(W32.T, X), row_axis)  # (d, l) summed over C shards

    G = _psum(Y.T @ Y, col_axis)  # (l, l) replicated
    evals, u_hat = jnp.linalg.eigh(G)
    evals = jnp.maximum(evals, 0.0)
    order = jnp.argsort(-evals)
    evals, u_hat = evals[order], u_hat[:, order]
    S = jnp.sqrt(evals)
    s_safe = jnp.where(S > 0, S, 1.0)
    V = Y @ (u_hat / s_safe[None, :])  # (d, l) sharded on D
    U = X @ u_hat  # (c, l) sharded on C
    return U[:, :k].astype(W.dtype), S[:k].astype(W.dtype), V[:, :k].T.astype(W.dtype)


def distributed_rsi(
    W: jax.Array,
    k: int,
    q: int,
    key: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    row_axis: str | Sequence[str] | None = "data",
    col_axis: str | Sequence[str] | None = "model",
    oversample: int = 0,
) -> RSIResult:
    """Distributed Algorithm 3.1 for a (C, D) matrix sharded P(row_axis, col_axis)."""
    C, D = W.shape
    ell = min(k + oversample, min(C, D))
    omega = jax.random.normal(key, (D, ell), dtype=jnp.float32).astype(W.dtype)

    body = functools.partial(
        _rsi_block, k=k, q=q, row_axis=row_axis, col_axis=col_axis
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(col_axis, None)),
        out_specs=(P(row_axis, None), P(), P(None, col_axis)),
    )
    U, S, Vt = fn(W, omega)
    return RSIResult(U=U, S=S, Vt=Vt)


def distributed_rsi_factors(W, k, q, key, mesh, **kw):
    """Sharded factored form A (C,k) P(row,None), B (k,D) P(None,col)."""
    res = distributed_rsi(W, k, q, key, mesh, **kw)
    root_s = jnp.sqrt(jnp.maximum(res.S.astype(jnp.float32), 0.0)).astype(W.dtype)
    return res.U * root_s[None, :], root_s[:, None] * res.Vt
