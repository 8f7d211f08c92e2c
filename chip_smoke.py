#!/usr/bin/env python3
"""Smoke test: the paged, RSI-compressed serving path on one TPU chip.

    python chip_smoke.py [--seed 0]

Builds minitron-4b at its published widths, with 16 of its 32 layers and
random weights from ``--seed``, and serves 8 requests through
``repro.serving.Engine`` (paged pool of 128-token pages, 256-token prefill
chunks, prefix sharing, 8 slots) twice: from the dense checkpoint, then from
the checkpoint that ``compress_tree`` compresses on the chip (alpha 0.3,
q 4).  It fails unless every request ends ``ok`` with its 32 tokens, the
dispatcher took the Pallas paged decode kernel and a fused low-rank kernel
with no interpret mode, and those kernels agree with their
``kernels/ref.py`` references on the chip at the smoke's own shapes.

It needs a TPU: where JAX finds none it exits non-zero and prints no result.
The last line of its output is one JSON object naming the device.  Times
and memory printed on earlier lines are for reading only, not speed claims.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ARCH = "minitron-4b"
N_LAYERS = 16  # of 32: dense weights + factors + RSI temporaries fit 16 GB
N_SLOTS = 8
PAGE = 128
CHUNK = 256
SHARED_PREFIX = 256  # two full pages, shared by 4 of the 8 prompts
PROMPT_RANGE = (64, 1024)
NEW_TOKENS = 32
ALPHA, Q = 0.3, 4


def fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


def tpu_devices():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devices[0].platform}")
    return devices


def agree(name, got, want, bound):
    """Elementwise |got - want| <= bound, all finite; prints the margin."""
    import numpy as np

    got, want, bound = (np.asarray(a, np.float32) for a in (got, want, bound))
    err = np.abs(got - want)
    if not np.isfinite(got).all():
        fail(f"{name}: non-finite output")
    if not (err <= bound).all():
        i = np.unravel_index(np.argmax(err - bound), err.shape)
        fail(f"{name}: |err| {err[i]} > bound {bound[i]} at {i}")
    print(f"[kernel] {name}: max |err| {err.max():.4g}, "
          f"max |err|/bound {(err / bound).max():.3g}", flush=True)


def check_kernels(cfg, seed, max_len):
    """The paged decode and fused low-rank kernels against kernels/ref.py,
    compiled for the chip (interpret off), at the smoke's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.core import CompressionPolicy
    from repro.kernels import ref
    from repro.kernels.decode_attention import paged_decode_attention_pallas
    from repro.kernels.lowrank_matmul import (
        lowrank_matmul_batched_pallas,
        lowrank_matmul_pallas,
    )

    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 8)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_tbl = -(-max_len // PAGE)
    P = N_SLOTS * n_tbl + 1  # the engine's pool: every slot's pages + trash
    q = jax.random.normal(ks[0], (N_SLOTS, 1, H, hd), bf16)
    kp = jax.random.normal(ks[1], (P, PAGE, KV, hd), bf16)
    vp = jax.random.normal(ks[2], (P, PAGE, KV, hd), bf16)
    bt = jax.random.permutation(ks[3], P - 1)[: N_SLOTS * n_tbl]
    bt = bt.reshape(N_SLOTS, n_tbl).astype(jnp.int32)
    # a dead slot, page edges, and the deepest table
    n_valid = jnp.array([0, 1, PAGE - 1, PAGE, PAGE + 1, 600, max_len, n_tbl * PAGE], jnp.int32)
    got = paged_decode_attention_pallas(q, kp, vp, bt, n_valid)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, n_valid)
    # Both round the softmax probabilities to bf16 before the PV matmul, at
    # different points (kernel: unnormalized, per page; reference: once,
    # normalized), each at most 2^-8 relative, so they differ by at most
    # 2^-7 * max|v|; rounding each output to bf16 adds 2^-7 * |out|.  The
    # bound is twice that, for f32 sums taken in a different order.
    agree("paged_decode_attention", got, want,
          2.0**-6 * (jnp.max(jnp.abs(vp)) + jnp.abs(want.astype(jnp.float32))))

    d = cfg.d_model
    r = CompressionPolicy(alpha=ALPHA, q=Q).rank_for(d, d)

    def lowrank_bound(x, A, B, want):
        # Both round the f32 (x @ A) to bf16, whose sums run in a different
        # order, so an element may land one bf16 ulp (2^-7 relative) apart;
        # through B that moves y by at most 2^-7 * (|t| @ |B|).  Rounding y
        # to bf16 adds one ulp, 2^-7 * |y|.  The bound is twice that sum.
        t = jnp.matmul(x, A, preferred_element_type=jnp.float32)
        tb = jnp.matmul(jnp.abs(t), jnp.abs(B).astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
        return 2.0**-6 * (tb + jnp.abs(want.astype(jnp.float32)))

    # factors scaled like compress_tree's (A = U S^1/2, B = S^1/2 V^T) on a
    # unit-scale weight, so y ~ O(1)
    A = (jax.random.normal(ks[4], (N_LAYERS, d, r), jnp.float32) * d**-0.5).astype(bf16)
    B = (jax.random.normal(ks[5], (N_LAYERS, r, d), jnp.float32) * r**-0.5).astype(bf16)
    for M in (N_SLOTS, CHUNK):  # one decode step, one prefill chunk
        x = jax.random.normal(ks[6], (M, d), bf16)
        got = lowrank_matmul_pallas(x, A[0], B[0])
        want = ref.lowrank_matmul_ref(x, A[0], B[0])
        agree(f"lowrank_matmul M={M} K={d} r={r} N={d}", got, want,
              lowrank_bound(x, A[0], B[0], want))
    xs = jax.random.normal(ks[7], (N_LAYERS, N_SLOTS, d), bf16)
    got = lowrank_matmul_batched_pallas(xs, A, B)
    want = ref.lowrank_matmul_ref(xs, A, B)
    agree(f"lowrank_matmul_batched L={N_LAYERS} M={N_SLOTS}", got, want,
          lowrank_bound(xs, A, B, want))


def make_prompts(seed, vocab):
    """8 prompts of 64-1024 tokens; the first 4 share a 256-token prefix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_RANGE
    shared_lens = rng.integers(SHARED_PREFIX + PAGE, hi + 1, size=4)
    own_lens = rng.integers(lo, hi + 1, size=4)
    shared_lens[-1], own_lens[0] = hi, lo  # both ends of the range run
    prefix = rng.integers(0, vocab, SHARED_PREFIX)
    shared = [
        np.concatenate([prefix, rng.integers(0, vocab, n - SHARED_PREFIX)])
        for n in shared_lens
    ]
    own = [rng.integers(0, vocab, n) for n in own_lens]
    # serve() runs the first sharer alone, to warm the prefix pages
    return shared + own


def serve(label, model, params, prompts, max_len):
    """Serve ``prompts`` through the engine; check results and kernel paths."""
    import numpy as np

    from repro.runtime import dispatch
    from repro.runtime.dispatch import DispatchConfig
    from repro.serving import Engine, Request

    dcfg = DispatchConfig.from_arch(model.cfg)
    if dcfg.interpret_mode():
        fail(f"{label}: dispatch would run Pallas kernels in interpret mode")
    dispatch.reset_counters()
    eng = Engine(
        model, params, n_slots=N_SLOTS, max_len=max_len, dispatch=dcfg,
        page_size=PAGE, prefill_chunk=CHUNK, share_prefix=True,
    )

    def requests():
        return [Request(prompt=p, max_new_tokens=NEW_TOKENS) for p in prompts]

    t0 = time.perf_counter()
    reqs = requests()
    done = eng.run(reqs[:1]) + eng.run(reqs[1:])
    first_s = time.perf_counter() - t0
    if len(done) != len(prompts):
        fail(f"{label}: {len(done)} of {len(prompts)} requests finished")
    for r in done:
        # the engine quarantines (status "error") a slot whose decode logits
        # go non-finite, so "ok" with every token means finite logits
        if r.status != "ok" or len(r.tokens) != NEW_TOKENS:
            fail(f"{label}: request {r.uid} ended {r.status} ({r.error}) "
                 f"with {len(r.tokens)} of {NEW_TOKENS} tokens")
    if eng.skipped_prefill_tokens < 3 * SHARED_PREFIX:
        fail(f"{label}: prefix sharing skipped only "
             f"{eng.skipped_prefill_tokens} prompt tokens")
    hits = dispatch.counters_by_path()
    if not hits.get(("paged_decode_attention", "pallas")):
        fail(f"{label}: decode did not take the Pallas paged kernel: {hits}")
    if label == "rsi" and not (
        hits.get(("lowrank_matmul", "fused")) or hits.get(("lowrank_matmul", "fused_batched"))
    ):
        fail(f"{label}: no low-rank site took a fused kernel: {hits}")

    # the same traffic again on warm programs, from a cold prefix index
    eng.reset_prefix_cache()
    t0 = time.perf_counter()
    again = requests()
    eng.run(again[:1])
    eng.run(again[1:])
    steady_s = time.perf_counter() - t0
    n_tok = len(prompts) * NEW_TOKENS
    print(f"[{label}] {len(done)} requests ok, {n_tok} tokens; first run "
          f"{first_s:.3f} s (compiles included), warm rerun {steady_s:.3f} s "
          f"= {n_tok / steady_s:.1f} tok/s; prompt tokens skipped by sharing "
          f"{eng.skipped_prefill_tokens}", flush=True)
    print(f"[{label}] dispatch paths: "
          + ", ".join(f"{op}/{path} x{n}" for (op, path), n in sorted(hits.items())),
          flush=True)
    print(f"[{label}] first sequence: {np.asarray(done[0].tokens[:8]).tolist()}",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = tpu_devices()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax

    from repro.configs.registry import get_arch
    from repro.core import CompressionPolicy, compress_tree
    from repro.models.model import build_model
    from repro.runtime.compile_cache import use_compile_cache

    print(f"[cache] compile cache at {use_compile_cache()}", flush=True)
    full = get_arch(ARCH)
    cfg = dataclasses.replace(full, n_layers=N_LAYERS)
    print(f"[model] {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}); cut: n_layers {full.n_layers} -> "
          f"{cfg.n_layers}; random weights from seed {args.seed}", flush=True)
    prompts = make_prompts(args.seed, cfg.vocab)
    max_len = max(p.size for p in prompts) + NEW_TOKENS
    print(f"[traffic] prompt lengths {[int(p.size) for p in prompts]}, "
          f"{NEW_TOKENS} new tokens each", flush=True)

    check_kernels(cfg, args.seed, max_len)

    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(args.seed)))
    print(f"[init] {sum(x.size for x in jax.tree_util.tree_leaves(params)) / 1e9:.3f} "
          f"B params in {time.perf_counter() - t0:.1f} s", flush=True)
    serve("dense", model, params, prompts, max_len)

    t0 = time.perf_counter()
    policy = CompressionPolicy(alpha=ALPHA, q=Q)
    params, _, report = compress_tree(params, policy, jax.random.PRNGKey(args.seed + 1))
    jax.block_until_ready(params)
    if not any(layer.compressed for layer in report.layers):
        fail("compress_tree compressed nothing")
    print(f"[compress] {report.summary()} in {time.perf_counter() - t0:.1f} s",
          flush=True)
    serve("rsi", model, params, prompts, max_len)

    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[memory] peak_bytes_in_use {stats['peak_bytes_in_use']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
