"""Paged (block-table) flash-decode kernel suite.

Same contract as tests/test_decode_kernel.py, interpret mode on CPU: the
Pallas kernel that gathers K/V through a block table must match the
gather-einsum oracle — which itself must be BIT-identical to the flat
dense reference when the pages reassemble the same cache — across GQA
ratios, ragged ``n_valid`` crossing page boundaries, fully-masked rows,
and the dispatch routing that picks the kernel by shape/platform.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.runtime import dispatch
from repro.runtime.dispatch import DECODE_MIN_SEQ, DispatchConfig, use_dispatch

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / shape[-1] ** 0.25).astype(dtype)


def _paged_inputs(B, n_tbl, page, KV, G, hd, dtype, seed=0, poison=1e4):
    """A flat cache and its paged twin: pages placed at PERMUTED physical
    ids (so tests catch any reliance on contiguity), plus a trailing trash
    page full of poison."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    S = n_tbl * page
    q = _rand(ks[0], (B, 1, KV * G, hd), dtype)
    flat_k = _rand(ks[1], (B, S, KV, hd), dtype)
    flat_v = _rand(ks[2], (B, S, KV, hd), dtype)
    P = B * n_tbl + 1
    rng = np.random.default_rng(seed)
    bt = rng.permutation(P - 1).reshape(B, n_tbl).astype(np.int32)
    k_pool = np.full((P, page, KV, hd), poison, np.float32).astype(dtype)
    v_pool = np.full((P, page, KV, hd), poison, np.float32).astype(dtype)
    for b in range(B):
        for j in range(n_tbl):
            k_pool[bt[b, j]] = np.asarray(flat_k)[b, j * page : (j + 1) * page]
            v_pool[bt[b, j]] = np.asarray(flat_v)[b, j * page : (j + 1) * page]
    return q, flat_k, flat_v, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(bt)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("G", [1, 4, 8])  # GQA ratio H/KV
def test_paged_kernel_gqa_ratios(G, dtype):
    B, n_tbl, page, KV, hd = 2, 4, 16, 2, 16
    q, fk, fv, kp, vp, bt = _paged_inputs(B, n_tbl, page, KV, G, hd, dtype)
    n_valid = jnp.array([n_tbl * page, n_tbl * page // 2], jnp.int32)
    got = paged_decode_attention_pallas(q, kp, vp, bt, n_valid, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, n_valid)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_paged_ref_bit_identical_to_flat():
    """The gather oracle reassembles EXACTLY the flat cache: outputs are
    bit-identical to the dense flat reference — the property that makes the
    paged engine's greedy tokens match the flat engine's."""
    B, n_tbl, page, KV, G, hd = 3, 4, 8, 2, 4, 16
    q, fk, fv, kp, vp, bt = _paged_inputs(B, n_tbl, page, KV, G, hd, jnp.float32, seed=1)
    S = n_tbl * page
    n_valid = jnp.array([S, 11, 27], jnp.int32)
    valid = jnp.arange(S)[None, :] < n_valid[:, None]
    flat = ref.decode_attention_ref(q, fk, fv, valid)
    paged = ref.paged_decode_attention_ref(q, kp, vp, bt, n_valid)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(flat))


@pytest.mark.parametrize("n_valid_vals", [(1, 17, 48, 5), (16, 32, 33, 31)])
def test_paged_kernel_ragged_n_valid_crosses_pages(n_valid_vals):
    """Ragged per-slot validity, including boundaries INSIDE and exactly AT
    page edges: poison beyond each slot's valid prefix (and in the trash
    page every unallocated table entry points at) must never leak."""
    B, n_tbl, page, KV, G, hd = 4, 3, 16, 2, 4, 16  # S = 48
    q, fk, fv, kp, vp, bt = _paged_inputs(B, n_tbl, page, KV, G, hd, jnp.float32, seed=2)
    S = n_tbl * page
    n_valid = jnp.array(n_valid_vals, jnp.int32)
    # poison the invalid tail of every slot's pages, flat-and-paged alike
    valid = jnp.arange(S)[None, :] < n_valid[:, None]
    kp_host, vp_host = np.array(kp), np.array(vp)
    for b in range(B):
        for j in range(n_tbl):
            keep = np.asarray(valid)[b, j * page : (j + 1) * page]
            kp_host[int(bt[b, j])][~keep] = 1e4
            vp_host[int(bt[b, j])][~keep] = 1e4
    got = paged_decode_attention_pallas(
        q, jnp.asarray(kp_host), jnp.asarray(vp_host), bt, n_valid, interpret=True
    )
    assert bool(jnp.all(jnp.isfinite(got)))
    want = ref.decode_attention_ref(q, fk, fv, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL[jnp.float32])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_fully_masked_rows_are_zero(dtype):
    """A slot with n_valid == 0 (every table entry on trash) produces ZEROS
    from both the kernel and the gather oracle; live rows are untouched."""
    B, n_tbl, page, KV, G, hd = 3, 2, 8, 2, 2, 8
    q, fk, fv, kp, vp, bt = _paged_inputs(B, n_tbl, page, KV, G, hd, dtype, seed=3)
    trash = kp.shape[0] - 1
    bt = bt.at[0].set(trash).at[2].set(trash)  # dead slots point at trash
    n_valid = jnp.array([0, 7, 0], jnp.int32)
    for got in (
        ref.paged_decode_attention_ref(q, kp, vp, bt, n_valid),
        paged_decode_attention_pallas(q, kp, vp, bt, n_valid, interpret=True),
    ):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[0], np.zeros_like(got[0]))
        np.testing.assert_array_equal(got[2], np.zeros_like(got[2]))
        assert np.abs(got[1]).sum() > 0


def _engine_layout(n_tbl, page, KV, G, hd, dtype, seed):
    """One slot for each n_valid in (0, 1, page - 1, page, page + 1, full),
    laid out as the engine lays them: live pages at scattered physical ids;
    table entries past them alternate between the trash page (finite
    poison, as dummy-row scatters leave it) and in-range pages that no slot
    owns, filled with NaN.  A walk that copied and attended one of those
    would turn the slot's output NaN (0 * NaN in the PV matmul).  The
    n_valid 0 slot's row is all trash.  Returns the kernel's inputs and
    the pools with NaN made finite poison, for the oracles."""
    n_valid = np.array([0, 1, page - 1, page, page + 1, n_tbl * page], np.int32)
    B = n_valid.size
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], (B, 1, KV * G, hd), dtype)
    n_live = -(-n_valid // page)
    P = 2 * B * n_tbl + 1  # live pages, unowned pages, trash
    trash = P - 1
    rng = np.random.default_rng(seed)
    ids = iter(rng.permutation(P - 1).tolist())
    k_pool = np.full((P, page, KV, hd), 1e4, np.float32)
    v_pool = np.full((P, page, KV, hd), 1e4, np.float32)
    k_live = np.asarray(_rand(ks[1], (P, page, KV, hd), jnp.float32))
    v_live = np.asarray(_rand(ks[2], (P, page, KV, hd), jnp.float32))
    bt = np.full((B, n_tbl), trash, np.int32)
    unowned = []
    for b in range(B):
        for j in range(n_live[b]):
            bt[b, j] = pid = next(ids)
            k_pool[pid], v_pool[pid] = k_live[pid], v_live[pid]
        if n_valid[b]:
            for j in range(n_live[b], n_tbl, 2):
                bt[b, j] = pid = next(ids)
                unowned.append(pid)
    k_nan, v_nan = k_pool.copy(), v_pool.copy()
    k_nan[unowned] = v_nan[unowned] = np.nan
    return (
        q,
        jnp.asarray(k_nan, dtype), jnp.asarray(v_nan, dtype),
        jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype),
        jnp.asarray(bt), jnp.asarray(n_valid),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("G", [1, 3, 4, 8])
def test_paged_kernel_walks_only_live_pages(G, dtype):
    """Pages past a slot's last live page are never copied or attended:
    NaN there leaves every output finite, the n_valid 0 row is zeros, and
    the result matches the gather oracle.  In float32 it is bit for bit
    what a walk over the whole table gives (the flat kernel, one page per
    block, over the gathered cache).  An odd table length (5) makes the
    double-buffered walk end on both buffers."""
    n_tbl, page, KV, hd = 5, 16, 2, 16
    q, k_nan, v_nan, kp, vp, bt, n_valid = _engine_layout(n_tbl, page, KV, G, hd, dtype, seed=G)
    got = paged_decode_attention_pallas(q, k_nan, v_nan, bt, n_valid, interpret=True)
    got32 = np.asarray(got, np.float32)
    assert np.isfinite(got32).all()
    np.testing.assert_array_equal(got32[0], np.zeros_like(got32[0]))
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, n_valid)
    np.testing.assert_allclose(got32, np.asarray(want, np.float32), **TOL[dtype])
    if dtype == jnp.float32:
        S = n_tbl * page
        valid = jnp.arange(S)[None, :] < n_valid[:, None]
        whole = decode_attention_pallas(
            q, ref.gather_pages(kp, bt), ref.gather_pages(vp, bt), valid,
            bs=page, interpret=True,
        )
        np.testing.assert_array_equal(got32, np.asarray(whole))


# --------------------------------------------------------------------------- #
# dispatch routing
# --------------------------------------------------------------------------- #
def test_choose_paged_decode_path_auto_table():
    q_shape = (4, 1, 8, 64)
    pool = (64, 64, 2, 64)  # page = 64
    cfg = DispatchConfig()
    deep, shallow = 32, 1  # 32 * 64 = 2048 logical >= DECODE_MIN_SEQ > 64
    assert dispatch.choose_paged_decode_path(q_shape, pool, deep, config=cfg, platform="tpu") == "pallas"
    assert dispatch.choose_paged_decode_path(q_shape, pool, shallow, config=cfg, platform="tpu") == "xla"
    assert dispatch.choose_paged_decode_path(q_shape, pool, deep, config=cfg, platform="cpu") == "xla"
    assert shallow * pool[1] < DECODE_MIN_SEQ <= deep * pool[1]
    pinned = DispatchConfig(backend="pallas")
    assert dispatch.choose_paged_decode_path(q_shape, pool, shallow, config=pinned, platform="cpu") == "pallas"
    per_op = DispatchConfig(overrides=(("paged_decode_attention", "xla"),))
    assert dispatch.choose_paged_decode_path(q_shape, pool, deep, config=per_op, platform="tpu") == "xla"


def test_paged_dispatch_entry_counts_and_matches():
    B, n_tbl, page, KV, G, hd = 2, 4, 8, 2, 4, 16
    q, fk, fv, kp, vp, bt = _paged_inputs(B, n_tbl, page, KV, G, hd, jnp.float32, seed=5)
    n_valid = jnp.array([32, 11], jnp.int32)
    dispatch.reset_counters()
    with use_dispatch(backend="pallas"):
        got = dispatch.paged_decode_attention(q, kp, vp, bt, n_valid)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, n_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    hits = dispatch.counters_by_path()
    assert hits.get(("paged_decode_attention", "pallas"), 0) >= 1


def test_paged_engine_decode_runs_through_dispatch_counter():
    """End-to-end: a paged fused engine block records paged_decode_attention
    sites (and the flat op is NOT used for the paged pool)."""
    from repro.configs.registry import get_arch
    from repro.models.model import build_model
    from repro.serving import Engine, Request

    cfg = get_arch("llama3.2-1b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dispatch.reset_counters()
    eng = Engine(model, params, n_slots=2, max_len=16, decode_block=4, page_size=4)
    eng.submit(Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=5))
    while eng.has_work:
        eng.step()
    hits = dispatch.counters_by_path()
    assert hits.get(("paged_decode_attention", "xla"), 0) >= 1  # CPU auto -> gather
    assert hits.get(("decode_attention", "xla"), 0) == 0


def test_engine_counts_attention_pages_walked():
    """One fused block: every slot's row walks ceil((pos + 1) / page) pages
    of its table, per decode step; a whole-table walk would take
    n_slots * max_pages."""
    from repro.configs.registry import get_arch
    from repro.models.model import build_model
    from repro.serving import Engine, Request

    cfg = get_arch("llama3.2-1b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, n_slots=3, max_len=16, decode_block=4, page_size=4)
    for n in (6, 9):
        eng.submit(Request(prompt=np.arange(n, dtype=np.int32), max_new_tokens=5))
    eng.step()  # prefill both, then one fused block of 4 decode steps
    assert eng.host_syncs == 1 and eng.max_pages == 4
    # positions at launch 6, 9 and an empty slot at 0: pages 2 + 3 + 1
    assert eng.attn_pages_walked == 4 * (2 + 3 + 1)
    assert eng.attn_pages_offered == 4 * 3 * 4
    eng.reset_counters()
    assert eng.attn_pages_walked == eng.attn_pages_offered == 0
