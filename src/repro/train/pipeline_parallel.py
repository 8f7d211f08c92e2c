"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

The multi-pod mesh's "pod" axis defaults to pure data parallelism; this
module provides the alternative: stage-partitioned layers with microbatched
activation streaming via ``lax.ppermute`` inside shard_map.  Backward is
plain autodiff through the pipeline loop (ppermute is differentiable), i.e.
GPipe scheduling with full activation stash — the 1F1B schedule is left as a
scheduling optimization knob (see EXPERIMENTS.md §Perf notes).

Usage: layers stacked on axis 0 with n_layers % n_stages == 0; each stage
owns a contiguous slice (in_spec P("pod") on the layer axis).  Microbatches
stream through stages; outputs are collected on the last stage and broadcast
with a psum so every pod exits with the full result (what the loss needs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["gpipe_apply", "pipeline_forward"]


def _stage_body(block_fn, stage_params, x):
    """Run this stage's slice of layers (scan over the local stack)."""

    def step(h, lp):
        return block_fn(lp, h), None

    y, _ = jax.lax.scan(step, x, stage_params)
    return y


def pipeline_forward(block_fn, params_stack, x_mb, *, axis: str = "pod"):
    """shard_map body: params_stack (L/S, ...) local slice; x_mb (M, b, ...)
    microbatches (replicated input).  Returns (M, b, ...) outputs."""
    S = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    M = x_mb.shape[0]
    T = M + S - 1  # total pipeline ticks
    perm = [(i, (i + 1) % S) for i in range(S)]

    buf = jnp.zeros_like(x_mb[0])  # activation arriving from the previous stage
    outs = jnp.zeros_like(x_mb)

    def tick(t, carry):
        buf, outs = carry
        mb_in = t - stage  # microbatch index entering this stage at tick t
        feed = jax.lax.dynamic_index_in_dim(x_mb, jnp.clip(t, 0, M - 1), keepdims=False)
        x_in = jnp.where(stage == 0, feed, buf)
        active = (mb_in >= 0) & (mb_in < M)
        y = _stage_body(block_fn, params_stack, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # collect finished microbatch on the last stage
        out_idx = jnp.clip(mb_in, 0, M - 1)
        take = active & (stage == S - 1)
        cur = jax.lax.dynamic_index_in_dim(outs, out_idx, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(take, y, cur), out_idx, axis=0
        )
        buf = jax.lax.ppermute(y, axis, perm)
        return buf, outs

    _, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
    # broadcast the last stage's collected outputs to all stages
    last = jnp.zeros((S,), outs.dtype).at[S - 1].set(1.0)
    outs = jax.lax.psum(outs * last[stage], axis)
    return outs


def gpipe_apply(block_fn, mesh, *, n_microbatches: int, axis: str = "pod"):
    """Returns fn(params_stack, x) running the stacked blocks as a pipeline.

    params_stack: (L, ...) with L % n_stages == 0, sharded P(axis) on dim 0.
    x: (B, ...) with B % n_microbatches == 0 (replicated across `axis`).
    """

    def fn(params_stack, x):
        B = x.shape[0]
        mb = x.reshape((n_microbatches, B // n_microbatches) + x.shape[1:])

        body = functools.partial(pipeline_forward, block_fn, axis=axis)
        param_spec = jax.tree_util.tree_map(lambda _: P(axis), params_stack)
        out = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(param_spec, P()),
            out_specs=P(),
            check_vma=False,
        )(params_stack, mb)
        return out.reshape((B,) + x.shape[1:])

    return fn
