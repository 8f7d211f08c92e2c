"""Pallas TPU kernel: flash-decode — one-token GQA attention over a KV cache.

The serving decode hot path: every active slot attends its single new query
against its whole cache row.  The kernel streams the cache's SEQUENCE dim
through VMEM in blocks (split-KV online softmax: running (m, l, acc) live in
scratch across the sequential grid axis), so no (B, H, S) score tensor is
ever materialized and the cache itself is never copied or transposed — the
BlockSpec index maps read (bs, KV, hd) tiles straight out of the
(B, S, KV, hd) pool layout.

Tiling: the grid is (B, S/bs) and each program carries ALL KV heads of one
sequence block.  Mosaic requires the last two dims of every block to be
(8, 128)-divisible or equal to the array's; a block with one KV head out of
KV > 1 breaks that rule on the second-minor axis, so the (KV, hd) pair is
always taken whole.  The body loops over the KV heads statically and, per
head, computes all ``G = H // KV`` query heads that share it, so the
(G, hd) @ (hd, bs) score matmul reads K once per KV head, not per query head.

Masking is STRICT per slot.  The flat kernel takes a ``valid`` (B, S) mask
(built by the caller from per-slot ``n_valid`` or a ring-buffer
``rotate_mask``), passed as (B, 1, S) so its block is (1, bs) in the last
two dims.  The paged kernel builds the mask in the body from ``n_valid``,
a scalar-prefetch operand, and a position iota.  Either mask gates both the
scores and the probabilities.  Fully-masked rows — empty or inactive slots
in the continuous-batching pool — produce ZEROS, not NaN: probabilities are
re-masked after the exp so the running denominator stays 0 (``exp(s - m)``
alone would be 1 on all-masked rows where m == NEG_INF).

The dense einsum in kernels/ref.decode_attention_ref (wrapped by
models/attention.decode_attention) is the parity oracle; backend selection
lives in runtime/dispatch.py like every other op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "decode_attention_kernel",
    "decode_attention_pallas",
    "paged_decode_attention_pallas",
]

NEG_INF = -1e30

# Declared worst-case block dims for the static VMEM gate
# (repro.analysis pallas-contract).  G = query heads per KV head, KV = KV
# heads (carried whole in every block), hd/vd = head dims, page = KV page
# size.  Growing a model config past these must come back here — the budget
# math below is checked against them in CI.
VMEM_ANALYSIS_BOUNDS = {"G": 16, "KV": 16, "hd": 256, "vd": 256, "page": 128}


def _attend_block(q_ref, k_ref, v_ref, live, m_ref, l_ref, acc_ref, *, scale):
    """One online-softmax step over a (bs, KV, hd) block, every KV head.

    ``live`` is the (1, bs) validity of the block's positions.  Scratch
    holds one (G, 1) / (G, 1) / (G, vd) running (m, l, acc) per KV head.
    """
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h]  # (G, hd)
        k = k_ref[0, :, h, :]  # (bs, hd)
        v = v_ref[0, :, h, :]  # (bs, vd)
        # Same dtype discipline as the reference: scale in fp32, cast back to
        # the cache dtype, accumulate scores in fp32 on the MXU.
        qs = (q.astype(jnp.float32) * scale).astype(k.dtype)
        s = jax.lax.dot_general(
            qs, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, bs)
        s = jnp.where(live, s, NEG_INF)

        m_prev, l_prev = m_ref[h], l_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # exp(s - m) is 1, not 0, on fully-masked rows (m == NEG_INF);
        # re-masking keeps l at 0 there so empty slots flush to zeros.
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[h] = m_new


def _init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _flush(o_ref, l_ref, acc_ref):
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_kernel(
    q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float, n_s: int
):
    j = pl.program_id(1)
    pl.when(j == 0)(lambda: _init_scratch(m_ref, l_ref, acc_ref))
    live = valid_ref[0] != 0  # (1, bs)
    _attend_block(q_ref, k_ref, v_ref, live, m_ref, l_ref, acc_ref, scale=scale)
    pl.when(j == n_s - 1)(lambda: _flush(o_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention_pallas(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S, KV, hd)
    v_cache: jax.Array,  # (B, S, KV, vd)
    valid: jax.Array,  # (B, S) bool — per-slot cache validity mask
    *,
    bs: int = 256,
    interpret: bool = False,
):
    """Flat-cache flash-decode.  On the chip the sequence block must be
    128-aligned or the whole of S (the mask's lane dim): S a multiple of 128,
    or S <= bs.  runtime/dispatch only routes such shapes here.  ``bs`` 256
    keeps a whole-KV (bs, KV, hd) block inside the VMEM budget for KV <= 16.
    """
    B, one, H, hd = q.shape
    if one != 1:
        raise ValueError(f"decode query must be one token, got q {q.shape}")
    S, KV = k_cache.shape[1], k_cache.shape[2]
    vd = v_cache.shape[-1]
    if H % KV:
        raise ValueError(f"H={H} not a multiple of KV={KV}")
    if valid.shape != (B, S):
        raise ValueError(f"valid mask {valid.shape} != (B, S)=({B}, {S})")
    G = H // KV
    bs_ = min(bs, S)
    while S % bs_:
        bs_ //= 2
    qg = q.reshape(B, KV, G, hd)
    valid_i = valid.astype(jnp.int32).reshape(B, 1, S)
    grid = (B, S // bs_)

    out = pl.pallas_call(
        functools.partial(decode_attention_kernel, scale=hd**-0.5, n_s=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs_, KV, hd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, bs_, KV, vd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, bs_), lambda b, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, vd), lambda b, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, vd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, vd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k_cache, v_cache, valid_i)
    return out.reshape(B, 1, H, vd)


def _paged_decode_kernel(
    bt_ref, nv_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, n_s, page
):
    # The block table was consumed by the BlockSpec index maps (scalar
    # prefetch); n_valid masks in the body.  Otherwise this is EXACTLY the
    # flat split-KV online softmax — one page of the slot's cache per
    # sequential grid step.
    del bt_ref
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _init_scratch(m_ref, l_ref, acc_ref))
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    live = pos < nv_ref[b]  # (1, page)
    _attend_block(q_ref, k_ref, v_ref, live, m_ref, l_ref, acc_ref, scale=scale)
    pl.when(j == n_s - 1)(lambda: _flush(o_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jax.Array,  # (B, 1, H, hd)
    k_pool: jax.Array,  # (P, page, KV, hd) physical pages
    v_pool: jax.Array,  # (P, page, KV, vd)
    block_table: jax.Array,  # (B, n_tbl) int32 page ids
    n_valid: jax.Array,  # (B,) int32 valid logical positions per slot
    *,
    interpret: bool = False,
):
    """Block-table flash-decode: one-token GQA attention over a PAGED cache.

    Identical split-KV online-softmax / GQA-tiling structure to
    :func:`decode_attention_pallas`, but the cache's sequence dim is
    virtualized: the grid's sequential axis walks the slot's BLOCK TABLE
    (one fixed-size page per step), and the K/V BlockSpec index maps — with
    the table as a scalar-prefetch operand — DMA each (page, KV, hd) page
    straight out of the shared physical pool.  No (B, S, KV, hd) per-slot
    gather is ever materialized, which is the whole point: the flat engine's
    worst-case per-slot reservation becomes a pool of pages shared by every
    slot.

    Entries of ``block_table`` beyond a slot's allocation may point at the
    pool's trash page; ``n_valid`` (the second scalar-prefetch operand)
    masks their positions, so trash contents are never observed
    (fully-masked rows produce zeros, same contract as the flat kernel).
    The gather-einsum oracle is kernels/ref.paged_decode_attention_ref.
    """
    B, one, H, hd = q.shape
    if one != 1:
        raise ValueError(f"decode query must be one token, got q {q.shape}")
    P, page, KV, _ = k_pool.shape
    vd = v_pool.shape[-1]
    if H % KV:
        raise ValueError(f"H={H} not a multiple of KV={KV}")
    if block_table.shape[0] != B:
        raise ValueError(f"block_table {block_table.shape} != (B, n_tbl), B={B}")
    G = H // KV
    n_tbl = block_table.shape[1]
    qg = q.reshape(B, KV, G, hd)
    grid = (B, n_tbl)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table steers the K/V maps; n_valid masks
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, j, bt, nv: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd), lambda b, j, bt, nv: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, vd), lambda b, j, bt, nv: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, vd), lambda b, j, bt, nv: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, vd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=hd**-0.5, n_s=n_tbl, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, vd), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), n_valid.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(B, 1, H, vd)
