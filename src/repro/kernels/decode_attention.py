"""Pallas TPU kernel: flash-decode — one-token GQA attention over a KV cache.

The serving decode hot path: every active slot attends its single new query
against its cache row.  Both kernels stream the cache's SEQUENCE dim
through VMEM in blocks (split-KV online softmax: a running (m, l, acc) per
KV head), so no (B, H, S) score tensor is ever materialized and the cache
itself is never copied or transposed.

Flat kernel: the grid is (B, S/bs), the running state lives in scratch
across the sequential axis, and the BlockSpec index maps read (bs, KV, hd)
tiles straight out of the (B, S, KV, hd) cache.  Each program carries ALL
KV heads of one sequence block.  Mosaic requires the last two dims of every
block to be (8, 128)-divisible or equal to the array's; a block with one KV
head out of KV > 1 breaks that rule on the second-minor axis, so the
(KV, hd) pair is always taken whole.  The body loops over the KV heads
statically and, per head, computes all ``G = H // KV`` query heads that
share it, so the (G, hd) @ (hd, bs) score matmul reads K once per KV head,
not per query head.

Paged kernel: the grid is (B,), one step per slot, and the slot's pages are
copied out of the shared (P, page, KV, hd) pool by hand, double-buffered,
through its block table.  The walk stops at the slot's last live page,
``max(ceil(n_valid / page), 1)`` pages: a decode step pays for the pages
its slots hold, not for ``B x n_tbl``.  A (B, n_tbl) grid could stop the
copies too: an index map clamped to the last live page names, on every
later step, the block already resident, and the pipeline issues no copy
for a block index that repeats.  But each of those steps would still cost
the grid's fixed per-step overhead, which at a few live pages in a deep
table is most of the kernel's time; the per-slot loop costs none.  The
same per-head math runs on each page, in the same order, so the result is
bit for bit the one a walk over the whole table gives (a page wholly past
``n_valid`` would leave (m, l, acc) exactly as they were).

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


def _scaled_q(q, dtype, scale):
    # Same dtype discipline as the reference: scale in fp32, cast back to
    # the cache dtype, accumulate scores in fp32 on the MXU.
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _softmax_step(qs, k, v, live, m_prev, l_prev, acc_prev):
    """One online-softmax step of one KV head over a (bs, hd) block.

    ``qs`` is the head's (G, hd) scaled query, ``live`` the (1, bs)
    validity of the block's positions; returns the updated running
    (G, 1) max, (G, 1) denominator and (G, vd) numerator.
    """
    s = jax.lax.dot_general(
        qs, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (G, bs)
    s = jnp.where(live, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # exp(s - m) is 1, not 0, on fully-masked rows (m == NEG_INF);
    # re-masking keeps l at 0 there so empty slots flush to zeros.
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc_new


def _attend_block(q_ref, k_ref, v_ref, live, m_ref, l_ref, acc_ref, *, scale):
    """One online-softmax step over a (bs, KV, hd) block, every KV head.

    ``live`` is the (1, bs) validity of the block's positions.  Scratch
    holds one (G, 1) / (G, 1) / (G, vd) running (m, l, acc) per KV head.
    """
    for h in range(q_ref.shape[1]):
        k = k_ref[0, :, h, :]  # (bs, hd)
        v = v_ref[0, :, h, :]  # (bs, vd)
        m_ref[h], l_ref[h], acc_ref[h] = _softmax_step(
            _scaled_q(q_ref[0, h], k.dtype, scale), k, v, live,
            m_ref[h], l_ref[h], acc_ref[h],
        )


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
    bt_ref, nv_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, k_sem, v_sem, buf_ref,
    *, scale, page, n_tbl,
):
    # One grid step per slot.  The slot's pages up to its last live one are
    # copied HBM -> VMEM by hand into two alternating buffers: page i + 1
    # (or, after the slot's last page, the NEXT slot's first) is in flight
    # while page i is attended.  The running (m, l, acc) of every KV head
    # and the scaled queries stay loop values, not VMEM round-trips.
    b, n_b = pl.program_id(0), pl.num_programs(0)
    KV, G = q_ref.shape[1], q_ref.shape[2]
    vd = o_ref.shape[-1]

    def copies(s, i, buf):
        page_id = bt_ref[s, i]
        return (
            pltpu.make_async_copy(k_hbm.at[page_id], k_buf.at[buf], k_sem.at[buf]),
            pltpu.make_async_copy(v_hbm.at[page_id], v_buf.at[buf], v_sem.at[buf]),
        )

    def start(s, i, buf):
        for c in copies(s, i, buf):
            c.start()

    @pl.when(b == 0)
    def _():
        buf_ref[0] = 0
        start(0, 0, 0)

    nv = nv_ref[b]
    # pages through the slot's last live one; a slot with nothing valid
    # still walks (and fully masks) one page, so every slot flushes
    n_pages = jnp.minimum(jnp.maximum(pl.cdiv(nv, page), 1), n_tbl)
    qs = [_scaled_q(q_ref[0, h], k_buf.dtype, scale) for h in range(KV)]

    def attend_page(i, carry):
        buf, state = carry
        nxt = 1 - buf

        @pl.when(i + 1 < n_pages)
        def _():
            start(b, i + 1, nxt)

        @pl.when((i + 1 == n_pages) & (b + 1 < n_b))
        def _():
            start(b + 1, 0, nxt)

        for c in copies(b, i, buf):
            c.wait()
        live = i * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1) < nv
        state = [
            _softmax_step(qs[h], k_buf[buf, :, h, :], v_buf[buf, :, h, :], live, *state[h])
            for h in range(KV)
        ]
        return nxt, state

    empty = (
        jnp.full((G, 1), NEG_INF, jnp.float32),
        jnp.zeros((G, 1), jnp.float32),
        jnp.zeros((G, vd), jnp.float32),
    )
    buf_ref[0], state = jax.lax.fori_loop(
        0, n_pages, attend_page, (buf_ref[0], [empty] * KV)
    )
    for h, (_, l, acc) in enumerate(state):
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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

    The split-KV online softmax and GQA tiling of
    :func:`decode_attention_pallas`, over a cache whose sequence dim is
    virtualized: each slot walks its BLOCK TABLE one fixed-size page at a
    time, copying each (page, KV, hd) page straight out of the shared
    physical pool.  No (B, S, KV, hd) per-slot gather is ever materialized,
    which is the whole point: the flat engine's worst-case per-slot
    reservation becomes a pool of pages shared by every slot.

    The walk stops at each slot's last live page, ``max(ceil(n_valid /
    page), 1)`` pages, with ``n_valid`` and the block table as
    scalar-prefetch operands: pages past it are neither copied nor
    attended, so a decode step costs the pages its slots hold, not
    ``B x n_tbl`` (the module docstring says why the grid is one step per
    slot and not one per page).  Skipping a page that lies wholly past
    ``n_valid`` changes no output bit (its scores would be masked, its
    probabilities 0, the running max and sums multiplied by exactly 1), so
    the result is the one a walk over the whole table gives.

    Entries of ``block_table`` beyond a slot's allocation may point at the
    pool's trash page; ``n_valid`` masks positions past it inside the last
    live page, so trash contents are never observed (fully-masked rows
    produce zeros, same contract as the flat kernel).  The gather-einsum
    oracle is kernels/ref.paged_decode_attention_ref.
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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table and n_valid steer the page walk
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, bt, nv: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KV, G, vd), lambda b, bt, nv: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page, KV, hd), k_pool.dtype),
            pltpu.VMEM((2, page, KV, vd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),  # buffer that holds the next slot's first page
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=hd**-0.5, page=page, n_tbl=n_tbl),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, vd), q.dtype),
        # slots run in order: each one's last page prefetches the next's first
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table.astype(jnp.int32), n_valid.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(B, 1, H, vd)
