"""Serving-throughput benchmark: dense vs RSI-compressed decode (measured).

CPU wall-clock, reduced llama config — the RELATIVE throughput and agreement
numbers support EXPERIMENTS.md §Perf C2 (weight compression as a serving
lever).  Emits name,us_per_call,derived CSV rows.

``--sweep-backends`` additionally runs the compressed model once per kernel
backend (auto / xla / pallas / reference) through the unified dispatch
runtime and emits one CSV row per backend, annotated with the dispatcher's
hit counters — i.e. which execution path (fused / fused_batched / two_gemm /
dense) every linear in the compiled program actually took.

``--trace poisson`` replays a Poisson arrival trace through the continuous-
batching engine (repro.serving.Engine): requests with random prompt/output
lengths arrive at ``--rate`` req/s, queue for cache slots, and share FUSED
decode blocks (``--decode-block`` tokens per host round-trip); the row
reports tok/s, p50/p95 request latency, mean ttft, tokens-per-host-sync,
and decode-batch utilization (emitted tokens / executed decode-step rows) —
the two columns that make the fused-loop win visible in the CI artifact.
``--arch`` takes a comma list so one invocation can cover several reduced
archs.

``--page-size`` runs the trace on the PAGED engine (``--kv-pages`` sizes
the pool, ``--prefill-chunk`` enables chunked prefill); ``--long-frac``
mixes long prompts into the trace and adds a TTFT-p95-over-short-requests
column; ``--compare-paged`` runs each arch twice at equal KV bytes — flat
pool, then a paged pool backing twice the slots — and gates the paged row
against the flat one in the same run (more admitted concurrency, no
throughput loss, bounded short-request TTFT).

``--shared-prefix`` replays a common-system-prompt trace (every request =
``--sys-prompt-len`` shared tokens + a random suffix) TWICE on the paged
engine at equal KV bytes — prefix sharing off, then on (``+shared`` row)
— and gates the same-run contract: strictly fewer peak pages (shared
prefix pages counted once), strictly more peak-admitted concurrency
(page-gated admission banks the savings), throughput within tolerance,
and bit-identical greedy tokens per request.

``--trace sessions`` replays MULTI-TURN conversations (each turn's prompt
is the whole conversation so far plus new user tokens; per-tenant shared
system prompts) TWICE on the paged engine — prefix sharing off, then on
(``+shared`` row) — and gates the same-run session-cache contract:
follow-up turns re-prefill strictly fewer prompt tokens and see strictly
lower TTFT (decode-filled pages registered at slot release are matched
read-only), greedy tokens are bit-identical, and pages stay within the
pool under the ``--warm-cache-pages`` LRU eviction budget.  New columns:
re-prefilled / skipped prompt tokens, follow-up TTFT, evictions, cached
pages.

``--json BENCH_serving.json`` additionally writes the trace rows as a JSON
result document, and ``--check-baseline benchmarks/baselines/
BENCH_serving.json --tolerance 0.5`` compares tok/s and utilization against
a checked-in baseline, exiting non-zero on regression (the CI perf-smoke
step).

    PYTHONPATH=src python benchmarks/serving.py [--sweep-backends]
    PYTHONPATH=src python benchmarks/serving.py --trace poisson \
        --arch llama3.2-1b,mamba2-130m --rate 20 --n-requests 16 \
        [--csv serving_trace.csv] [--json BENCH_serving.json] \
        [--check-baseline benchmarks/baselines/BENCH_serving.json]
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.core import CompressionPolicy, compress_tree, spectralize_params
from repro.data.synthetic import SyntheticLM
from repro.models.model import build_model
from repro.runtime import dispatch
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.dispatch import BACKENDS, DispatchConfig, use_dispatch


def _setup(batch: int, prompt: int):
    cfg = get_arch("llama3.2-1b", reduced=True)
    model = build_model(cfg)
    params = spectralize_params(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(9))
    data = SyntheticLM(cfg, batch=batch, seq=prompt, kind="serve")
    bt = {k: jnp.asarray(v) for k, v in data.at_step(0).items()}
    return cfg, model, params, bt


def _bench(model, p, bt, prompt: int, gen: int):
    max_len = prompt + gen

    # Fresh closures per bench run: pjit's global jaxpr cache is keyed on the
    # function object, and the dispatch policy is ambient trace-time state —
    # reusing `model.decode_step` across backends would silently reuse the
    # FIRST backend's traced program (same idiom as serve_step.make_*_step).
    def prefill_fn(p, b):
        return model.prefill(p, b, max_len)

    def decode_fn(p, c, t, pos):
        return model.decode_step(p, c, t, pos)

    logits, cache = jax.jit(prefill_fn)(p, bt)
    step = jax.jit(decode_fn)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    # warm
    l2, c2 = step(p, cache, tok, jnp.int32(prompt))
    jax.block_until_ready(l2)
    t0 = time.perf_counter()
    toks = [tok]
    c = cache
    for i in range(gen):
        logits, c = step(p, c, toks[-1], jnp.int32(prompt + i))
        toks.append(jnp.argmax(logits, -1)[:, None].astype(jnp.int32))
    jax.block_until_ready(toks[-1])
    dt = time.perf_counter() - t0
    return np.concatenate([np.asarray(t) for t in toks[1:]], axis=1), dt


def run(alphas=(0.4, 0.2), q: int = 4, batch: int = 8, prompt: int = 16, gen: int = 16):
    cfg, model, params, bt = _setup(batch, prompt)

    ref, t_dense = _bench(model, params, bt, prompt, gen)
    rows = [dict(name="dense", alpha=0.0, seconds=t_dense, tok_s=batch * gen / t_dense, agree=1.0, ratio=1.0)]
    for alpha in alphas:
        cp, _, rep = compress_tree(
            params, CompressionPolicy(alpha=alpha, q=q, min_dim=32), jax.random.PRNGKey(1)
        )
        out, dt = _bench(model, cp, bt, prompt, gen)
        rows.append(
            dict(
                name=f"alpha={alpha}",
                alpha=alpha,
                seconds=dt,
                tok_s=batch * gen / dt,
                agree=float((out == ref).mean()),
                ratio=rep.ratio,
            )
        )
    return rows


def _hits_summary() -> str:
    """'path=count' pairs for the lowrank op, plus dense-linear sites."""
    agg = dispatch.counters_by_path()
    parts = [
        f"{path}={n}" for (op, path), n in sorted(agg.items()) if op == "lowrank_matmul"
    ]
    dense_n = sum(n for (op, _), n in agg.items() if op == "dense")
    if dense_n:
        parts.append(f"dense_linear={dense_n}")
    return "|".join(parts) if parts else "none"


def run_backend_sweep(
    alpha: float = 0.4, q: int = 4, batch: int = 4, prompt: int = 16, gen: int = 8
):
    """One row per dispatch backend for the SAME compressed checkpoint.

    Each backend gets a fresh trace (fresh jit closures), so the dispatcher's
    trace-time counters describe exactly the paths in that backend's program.
    """
    cfg, model, params, bt = _setup(batch, prompt)
    cp, _, rep = compress_tree(
        params, CompressionPolicy(alpha=alpha, q=q, min_dim=32), jax.random.PRNGKey(1)
    )
    rows = []
    ref = None
    for backend in BACKENDS:
        dispatch.reset_counters()
        with use_dispatch(DispatchConfig(backend=backend)):
            out, dt = _bench(model, cp, bt, prompt, gen)
        if ref is None:
            ref = out
        rows.append(
            dict(
                name=f"backend={backend}",
                alpha=alpha,
                seconds=dt,
                tok_s=batch * gen / dt,
                agree=float((out == ref).mean()),
                ratio=rep.ratio,
                hits=_hits_summary(),
            )
        )
    return rows


def run_trace(
    archs=("llama3.2-1b",),
    *,
    rate: float = 20.0,
    n_requests: int = 16,
    n_slots: int = 4,
    prompt_range=(4, 16),
    gen_range=(4, 16),
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
    alpha: float = 0.0,
    q: int = 4,
    decode_block: int = 8,
    warmup: bool = True,
    page_size: int = 0,
    kv_pages: int = 0,
    prefill_chunk: int = 0,
    long_frac: float = 0.0,
    long_prompt_range=(48, 64),
    max_len: int = 0,
    share_prefix: bool = False,
    sys_prompt_len: int = 0,
    row_suffix: str = "",
):
    """Replay a Poisson arrival trace through the continuous engine.

    One row per arch: tok/s over the busy window plus p50/p95 request
    latency (submit -> final token), mean time-to-first-token, tokens per
    host sync (``decode_block`` amortization), decode-batch utilization
    (emitted tokens / executed decode-step rows), peak admitted concurrency,
    and KV-memory accounting — capacity vs PEAK BYTES ACTUALLY RESIDENT
    (allocated pages in paged mode; a flat pool is fully committed up
    front).  Arrival times are exponential inter-arrivals at ``rate``
    req/s; prompt and output lengths are uniform over the given ranges —
    so the trace exercises ragged admission, exhaustion queueing, and
    mid-stream slot reuse rather than one synchronized batch.

    ``page_size`` switches the engine to the paged KV pool (``kv_pages``
    sizes it; 0 = flat-equivalent capacity) and ``prefill_chunk`` enables
    chunked prefill.  ``long_frac`` > 0 makes that fraction of requests
    draw prompts from ``long_prompt_range`` instead (the long-prompt mixed
    trace): the row then also reports TTFT p95 over the SHORT requests
    alone — the queue-behind-a-long-prefill number chunked prefill bounds.

    ``sys_prompt_len`` > 0 prepends the SAME ``sys_prompt_len`` random
    tokens to every prompt (the system-prompt traffic pattern);
    ``share_prefix`` turns on the engine's refcounted copy-on-write
    prefix sharing over that trace.  Rows then also report
    ``shared_hits`` (pages mapped read-only instead of re-allocated) and
    ``cow_forks``, and greedy rows stash per-request tokens for the
    same-run parity gate (:func:`check_shared_rows`).

    ``warmup`` (default on) replays throwaway requests through the SAME
    engine before the clock starts, so the row measures steady-state
    serving throughput rather than jit compile time (which on the reduced
    CPU configs is seconds — an order of magnitude more than the decode
    work itself, and identical across engine designs).  The prefix cache
    is cleared between warmup sub-runs (so every prefill bucket actually
    compiles instead of being skipped by a warm match) and once more at
    the warmup boundary, so the timed run starts cold and deterministic.
    """
    from repro.data.synthetic import modality_extras
    from repro.serving import Engine, Request, SamplingParams
    from repro.serving.engine import percentile

    rows = []
    for arch in archs:
        cfg = get_arch(arch, reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        if alpha > 0:
            params, _, _ = compress_tree(
                params, CompressionPolicy(alpha=alpha, q=q, min_dim=32), jax.random.PRNGKey(1)
            )
        rng = np.random.default_rng(seed)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests)).tolist()
        top_prompt = max(prompt_range[1], long_prompt_range[1] if long_frac > 0 else 0)
        eff_max_len = max_len or (sys_prompt_len + top_prompt + gen_range[1])
        # the shared system prompt is drawn ONCE (same seed path whether
        # sharing is on or off, so paired rows replay identical traffic)
        sys_tokens = (
            rng.integers(0, cfg.vocab, size=(sys_prompt_len,)).astype(np.int32)
            if sys_prompt_len
            else None
        )
        reqs, is_long = [], []
        for i in range(n_requests):
            sp = SamplingParams(temperature=temperature, top_k=top_k, seed=seed + i)
            long = long_frac > 0 and rng.random() < long_frac
            rng_range = long_prompt_range if long else prompt_range
            is_long.append(long)
            tail = rng.integers(
                0, cfg.vocab, size=(int(rng.integers(*rng_range)),)
            ).astype(np.int32)
            reqs.append(
                Request(
                    prompt=tail if sys_tokens is None else np.concatenate([sys_tokens, tail]),
                    max_new_tokens=int(rng.integers(*gen_range)),
                    sampling=sp,
                    extras=modality_extras(cfg, rng),
                )
            )
        eng = Engine(
            model, params, n_slots=n_slots, max_len=eff_max_len,
            decode_block=decode_block,
            page_size=page_size or None,
            kv_pages=kv_pages or None,
            prefill_chunk=prefill_chunk or None,
            share_prefix=share_prefix,
        )
        if warmup:
            # Compile OUTSIDE the clock.  Admission buckets micro-batch
            # shapes (rows to the next power of two capped at n_slots,
            # prompt lengths to power-of-two buckets — or the EXACT length
            # for recurrent families), so replaying every distinct prompt
            # length the trace will actually use, at every reachable group
            # size (powers of two below n_slots, plus the n_slots cap
            # itself, which is the admitted group size under saturation
            # even when n_slots is not a power of two), hits every prefill
            # program plus the fused decode block.  The timed replay then
            # measures serving, not XLA.
            #
            # Paged engine: the paged decode block and page-pool prefill
            # scatter compile once per (group, prompt-bucket) shape exactly
            # like the flat ones — the bucket sweep below covers them, and
            # page-COUNT enumeration collapses into it because every paged
            # program is block-table-steered at a single static shape
            # (ceil(max_len / page) table entries; page count is runtime
            # data, not a compile-time shape).  Chunked prefill adds ONE
            # more program — the fixed (1, prefill_chunk) chunk — which any
            # single long warmup prompt compiles; chunk count is again
            # runtime data.  Prompts longer than the chunk bypass grouped
            # prefill, so those lengths warm up as singletons.
            wrng = np.random.default_rng(seed + 1)
            wsp = SamplingParams(temperature=temperature, top_k=top_k, seed=seed)
            chunking = prefill_chunk and eng.model.prefill_chunk is not None
            all_lens = sorted({r.prompt.size for r in reqs})
            lens = [n for n in all_lens if not (chunking and n > prefill_chunk)]
            chunk_lens = [n for n in all_lens if chunking and n > prefill_chunk]
            gs, g = [], 1
            while g < n_slots:
                gs.append(g)
                g *= 2
            gs.append(n_slots)
            for g in gs:
                for n in lens:
                    # cleared per sub-run: a warm prefix match would SKIP
                    # the grouped prefill this bucket exists to compile
                    eng.reset_prefix_cache()
                    eng.run(
                        [
                            Request(
                                prompt=wrng.integers(0, cfg.vocab, size=(int(n),)),
                                max_new_tokens=2,
                                sampling=wsp,
                                extras=modality_extras(cfg, wrng),
                            )
                            for _ in range(g)
                        ]
                    )
            if chunk_lens:  # one ragged-tail chunked prompt compiles the rest
                eng.reset_prefix_cache()
                eng.run(
                    [
                        Request(
                            prompt=wrng.integers(0, cfg.vocab, size=(int(chunk_lens[-1]),)),
                            max_new_tokens=2,
                            sampling=wsp,
                            extras=modality_extras(cfg, wrng),
                        )
                    ]
                )
            if share_prefix and sys_prompt_len and eng._share:
                # mid-prompt prefill shapes: a donor/follower pair compiles
                # the shared-tail chunk program, and an exact-page-boundary
                # pair (identical prompts, length a page multiple) compiles
                # the COW fork copy — both are runtime-steered after that
                eng.reset_prefix_cache()
                wsys = wrng.integers(0, cfg.vocab, size=(sys_prompt_len,)).astype(np.int32)

                def sysreq(extra: int):
                    tail = wrng.integers(0, cfg.vocab, size=(extra,)).astype(np.int32)
                    return Request(
                        prompt=np.concatenate([wsys, tail]),
                        max_new_tokens=2,
                        sampling=wsp,
                        extras=modality_extras(cfg, wrng),
                    )

                eng.run([sysreq(2)])
                eng.run([sysreq(3)])  # matches -> shared-tail chunk program
                page = eng.page_size
                blen = -(-(sys_prompt_len + 1) // page) * page
                bprompt = wrng.integers(0, cfg.vocab, size=(blen,)).astype(np.int32)
                for _ in range(2):  # second run fully matches -> COW program
                    eng.run(
                        [Request(prompt=bprompt.copy(), max_new_tokens=2,
                                 sampling=wsp, extras=modality_extras(cfg, wrng))]
                    )
            # timed run starts with a COLD prefix cache either way: the
            # sharing row's wins come from the trace itself, not warmup
            eng.reset_prefix_cache()
            eng.reset_counters()
        t0 = time.perf_counter()
        done = eng.run(reqs, arrivals=arrivals)
        dt = time.perf_counter() - t0
        assert len(done) == n_requests, (len(done), n_requests)
        n_tok = sum(len(r.tokens) for r in done)
        lats = sorted(r.latency for r in done)
        p50, p95 = percentile(lats, 0.5), percentile(lats, 0.95)
        ttft = float(np.mean([r.ttft for r in done]))
        uid_long = {r.uid for r, lg in zip(reqs, is_long) if lg}
        short_ttfts = sorted(r.ttft for r in done if r.uid not in uid_long)
        row = dict(
            name=f"trace={arch}{row_suffix}",
            arch=f"{arch}{row_suffix}",
            seconds=dt,
            tok_s=n_tok / dt,
            p50_ms=p50 * 1e3,
            p95_ms=p95 * 1e3,
            ttft_ms=ttft * 1e3,
            n_requests=n_requests,
            decode_steps=eng.steps,
            host_syncs=eng.host_syncs,
            tok_per_sync=eng.tokens_per_sync,
            util=eng.batch_utilization,
            peak_active=eng.peak_active,
            kv_bytes_cap=eng.kv_bytes_capacity,
            kv_bytes_peak=eng.kv_bytes_peak,
            pages_peak=eng.peak_pages_in_use,
            prefill_chunks=eng.prefill_chunks,
            shared_hits=eng.shared_page_hits,
            cow_forks=eng.cow_forks,
            # whether sharing was EFFECTIVE for this arch (paged leaves +
            # a mid-prompt prefill entry) — the gate skips inert archs
            # (mamba/SWA/vlm/audio) instead of failing their zero hits
            share_supported=int(getattr(eng, "_share", False)),
        )
        if short_ttfts:
            row["ttft_p95_short_ms"] = percentile(short_ttfts, 0.95) * 1e3
        if temperature == 0.0:
            # per-request emitted tokens, in submission order: the same-run
            # shared-vs-unshared parity gate (underscore keys never reach
            # the CSV/JSON outputs)
            row["_tokens"] = [list(r.tokens) for r in reqs]
        rows.append(row)
    return rows


def run_sessions_trace(
    archs=("llama3.2-1b",),
    *,
    n_sessions: int = 4,
    turns_range=(3, 5),
    user_range=(3, 6),
    gen_range=(3, 6),
    sys_prompt_len: int = 8,
    rate: float = 8.0,
    think_time: float = 0.01,
    n_slots: int = 4,
    seed: int = 0,
    alpha: float = 0.0,
    q: int = 4,
    decode_block: int = 8,
    page_size: int = 4,
    kv_pages: int = 0,
    prefill_chunk: int = 8,
    warm_cache_pages: int = 0,
    share_prefix: bool = False,
    temperature: float = 0.0,
    top_k: int = 0,
    warmup: bool = True,
    row_suffix: str = "",
):
    """Replay MULTI-TURN conversations through the continuous engine.

    Each session is ``turns_range`` chat turns: turn t's prompt is the
    ENTIRE conversation so far (per-tenant system prompt, then every
    earlier user turn and model reply) plus ``user_range`` new user
    tokens — so follow-up prompts strictly extend the previous turn's
    prompt + reply, which is exactly the traffic shape session-cache
    registration (decode-filled pages indexed at slot release) exists
    for.  Sessions arrive Poisson at ``rate``/s; a follow-up turn is
    submitted ``think_time`` seconds after its reply lands.  Half the
    sessions share each tenant's system prompt (``n_sessions // 2``
    tenants), so cross-session prefix sharing engages too.

    Because each turn's prompt embeds the previous reply, the trace
    cannot be pre-built — the drive loop below submits turns online as
    replies complete.  With greedy sampling the replies (and therefore
    the full trace) are IDENTICAL whether sharing is on or off, which is
    what makes the same-run gate (:func:`check_sessions_rows`) able to
    demand bit-identical tokens between the two rows.

    Row columns beyond the Poisson trace's: ``reprefill_tok`` (prompt
    tokens follow-up turns actually re-prefilled — the number session
    caching exists to shrink), ``skipped_tok`` (prompt tokens skipped
    because their K/V was already resident), ``followup_ttft_ms`` (mean
    TTFT over turns >= 2), ``evictions`` and ``cached_pages`` (the
    allocator's warm-cache policy at work).

    ``prefill_chunk`` should stay BELOW ``sys_prompt_len + user_range[0]``
    so every prompt routes through the single fixed-shape chunk program —
    that bounds compiles to one prefill program no matter how long the
    conversations grow.
    """
    from repro.serving import Engine, Request, SamplingParams
    from repro.serving.engine import percentile

    rows = []
    for arch in archs:
        cfg = get_arch(arch, reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        if alpha > 0:
            params, _, _ = compress_tree(
                params, CompressionPolicy(alpha=alpha, q=q, min_dim=32), jax.random.PRNGKey(1)
            )
        # trace material is drawn ONCE per row from the same seed path, so
        # paired rows (sharing off/on) replay identical traffic: session
        # arrivals, per-turn user tokens and reply budgets, tenant prompts
        rng = np.random.default_rng(seed)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_sessions)).tolist()
        n_turns = [int(rng.integers(turns_range[0], turns_range[1] + 1))
                   for _ in range(n_sessions)]
        user_toks = [
            [rng.integers(0, cfg.vocab,
                          size=(int(rng.integers(user_range[0], user_range[1] + 1)),)
                          ).astype(np.int32) for _ in range(n_turns[s])]
            for s in range(n_sessions)
        ]
        gen_lens = [
            [int(rng.integers(gen_range[0], gen_range[1] + 1))
             for _ in range(n_turns[s])]
            for s in range(n_sessions)
        ]
        n_tenants = max(1, n_sessions // 2)
        tenant_sys = [
            rng.integers(0, cfg.vocab, size=(sys_prompt_len,)).astype(np.int32)
            for _ in range(n_tenants)
        ]
        # the longest conversation bounds max_len (prompt + reply of its
        # final turn = the whole session transcript)
        max_len = max(
            sys_prompt_len
            + sum(u.size for u in user_toks[s]) + sum(gen_lens[s])
            for s in range(n_sessions)
        )
        max_pages = -(-max_len // page_size)
        eff_kv_pages = kv_pages or n_slots * max_pages
        eng = Engine(
            model, params, n_slots=n_slots, max_len=max_len,
            decode_block=decode_block, page_size=page_size,
            kv_pages=eff_kv_pages,
            prefill_chunk=prefill_chunk or None,
            share_prefix=share_prefix,
            warm_cache_pages=warm_cache_pages or None,
        )
        supported = bool(getattr(eng, "_share", share_prefix)) if share_prefix else (
            eng.model.prefill_chunk is not None and eng._has_pages
        )
        if warmup:
            # every prompt is longer than the chunk (see docstring), so ONE
            # long chunked prompt compiles the only prefill program; the
            # shared pair + page-boundary pair compile the shared-tail
            # entry and the COW fork copy (run_trace's warmup idiom)
            wrng = np.random.default_rng(seed + 1)
            wsp = SamplingParams(temperature=temperature, top_k=top_k, seed=seed)
            eng.run([Request(
                prompt=wrng.integers(0, cfg.vocab, size=(max_len - 4,)).astype(np.int32),
                max_new_tokens=2, sampling=wsp,
            )])
            if share_prefix and getattr(eng, "_share", False):
                eng.reset_prefix_cache()
                wsys = wrng.integers(0, cfg.vocab, size=(sys_prompt_len + 4,)).astype(np.int32)
                for extra in (2, 3):
                    tail = wrng.integers(0, cfg.vocab, size=(extra,)).astype(np.int32)
                    eng.run([Request(prompt=np.concatenate([wsys, tail]),
                                     max_new_tokens=2, sampling=wsp)])
                blen = -(-(sys_prompt_len + 5) // page_size) * page_size
                bprompt = wrng.integers(0, cfg.vocab, size=(blen,)).astype(np.int32)
                for _ in range(2):  # second run fully matches -> COW program
                    eng.run([Request(prompt=bprompt.copy(), max_new_tokens=2,
                                     sampling=wsp)])
            eng.reset_prefix_cache()
            eng.reset_counters()

        # ---- online drive loop: turn t+1's prompt embeds turn t's reply
        ready_at = list(arrivals)  # next submit time per session (None = done)
        turn = [0] * n_sessions
        ctx = [tenant_sys[s % n_tenants].copy() for s in range(n_sessions)]
        in_flight: dict = {}  # uid -> session
        finished: list = [[None] * n_turns[s] for s in range(n_sessions)]
        t0 = time.perf_counter()
        while any(r is not None for r in ready_at) or eng.has_work:
            now = time.perf_counter() - t0
            for s in range(n_sessions):
                if ready_at[s] is not None and ready_at[s] <= now and s not in in_flight.values():
                    prompt = np.concatenate([ctx[s], user_toks[s][turn[s]]])
                    req = Request(
                        prompt=prompt,
                        max_new_tokens=gen_lens[s][turn[s]],
                        sampling=SamplingParams(
                            temperature=temperature, top_k=top_k,
                            seed=seed + 131 * s + turn[s],
                        ),
                    )
                    eng.submit(req)
                    in_flight[req.uid] = s
                    ready_at[s] = None  # waiting on the reply
            if eng.has_work:
                for r in eng.step():
                    s = in_flight.pop(r.uid)
                    finished[s][turn[s]] = r
                    ctx[s] = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
                    turn[s] += 1
                    if turn[s] < n_turns[s]:
                        ready_at[s] = (time.perf_counter() - t0) + think_time
                continue
            nxt = min((t for t in ready_at if t is not None), default=None)
            if nxt is not None:
                wait = nxt - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
        dt = time.perf_counter() - t0
        done = [r for per in finished for r in per]
        assert all(r is not None for r in done)
        followups = [r for s in range(n_sessions) for r in finished[s][1:]]
        n_tok = sum(len(r.tokens) for r in done)
        lats = sorted(r.latency for r in done)
        ttfts = sorted(r.ttft for r in followups)
        row = dict(
            name=f"sessions={arch}{row_suffix}",
            arch=f"{arch}{row_suffix}",
            seconds=dt,
            tok_s=n_tok / dt,
            p50_ms=percentile(lats, 0.5) * 1e3,
            p95_ms=percentile(lats, 0.95) * 1e3,
            ttft_ms=float(np.mean([r.ttft for r in done])) * 1e3,
            followup_ttft_ms=float(np.mean(ttfts)) * 1e3 if ttfts else 0.0,
            # prompt tokens follow-up turns actually RE-PREFILLED: their
            # whole context minus what matched resident pages
            reprefill_tok=sum(r.prompt.size - r.prefill_skipped for r in followups),
            skipped_tok=eng.skipped_prefill_tokens,
            evictions=eng.prefix_evictions,
            cached_pages=eng.prefix_cached_pages,
            n_requests=len(done),
            n_sessions=n_sessions,
            decode_steps=eng.steps,
            host_syncs=eng.host_syncs,
            tok_per_sync=eng.tokens_per_sync,
            util=eng.batch_utilization,
            peak_active=eng.peak_active,
            kv_bytes_cap=eng.kv_bytes_capacity,
            kv_bytes_peak=eng.kv_bytes_peak,
            pages_peak=eng.peak_pages_in_use,
            kv_pages=eff_kv_pages if eng.paged else 0,
            prefill_chunks=eng.prefill_chunks,
            shared_hits=eng.shared_page_hits,
            cow_forks=eng.cow_forks,
            share_supported=int(supported),
        )
        if temperature == 0.0:
            # (session, turn)-ordered emitted tokens: the same-run parity
            # gate currency (underscore keys never reach CSV/JSON)
            row["_tokens"] = [list(r.tokens) for r in done]
        rows.append(row)
    return rows


def check_sessions_rows(rows, *, tolerance: float = 0.3) -> int:
    """Same-run sharing-off-vs-on gates for the sessions trace.

    Pairs ``X`` with ``X+shared``; both replayed the IDENTICAL multi-turn
    trace (greedy replies make the traffic deterministic).  Deterministic
    counters gate with NO slack: follow-up turns must re-prefill strictly
    FEWER prompt tokens (decode-filled pages matched read-only), sharing
    must have skipped something, pages_peak must respect the pool, and
    greedy tokens must be bit-identical (sharing relocates bytes, never
    changes what is attended).  Follow-up TTFT — a timing number, but the
    one the mechanism exists to cut, and on the same machine the
    avoided re-prefill work dwarfs scheduler noise — must be strictly
    lower.  Throughput holds within ``tolerance``.  Returns #violations.
    """
    by_arch = {r["arch"]: r for r in rows if "arch" in r}
    failures = 0
    for arch, shared in by_arch.items():
        if not arch.endswith("+shared"):
            continue
        base = by_arch.get(arch[: -len("+shared")])
        if base is None:
            continue
        label = arch[: -len("+shared")]
        if not shared.get("share_supported"):
            print(
                f"[perf-smoke] {label} sessions shared-vs-unshared: "
                f"sharing inert for this arch, gates skipped"
            )
            continue
        checks = [
            ("reprefill_tok", shared["reprefill_tok"] < base["reprefill_tok"],
             f"{shared['reprefill_tok']} < {base['reprefill_tok']}"),
            ("skipped_tok", shared["skipped_tok"] > 0,
             f"{shared['skipped_tok']} > 0"),
            ("followup_ttft_ms",
             shared["followup_ttft_ms"] < base["followup_ttft_ms"],
             f"{shared['followup_ttft_ms']:.1f} < {base['followup_ttft_ms']:.1f}"),
            ("pages_peak", shared["pages_peak"] <= shared["kv_pages"],
             f"{shared['pages_peak']} <= {shared['kv_pages']}"),
            ("tok_s", shared["tok_s"] >= base["tok_s"] * (1.0 - tolerance),
             f"{shared['tok_s']:.1f} >= {base['tok_s']:.1f} - {tolerance:.0%}"),
        ]
        if base.get("_tokens") is not None and shared.get("_tokens") is not None:
            checks.append(
                ("greedy_parity", shared["_tokens"] == base["_tokens"],
                 "bit-identical tokens per (session, turn)")
            )
        for metric, ok, detail in checks:
            print(
                f"[perf-smoke] {label} sessions {metric}: {detail} "
                f"{'OK' if ok else 'VIOLATION'}"
            )
            failures += 0 if ok else 1
    return failures


def run_overload_trace(
    archs=("llama3.2-1b",),
    *,
    rate: float = 2000.0,
    n_requests: int = 24,
    n_slots: int = 3,
    prompt_range=(6, 12),
    gen_range=(24, 32),
    deadline_ms: float = 300.0,
    tiers=(1.0, 0.5),
    tier_q: int = 2,
    seed: int = 0,
    alpha: float = 0.5,
    q: int = 2,
    decode_block: int = 4,
    page_size: int = 4,
    kv_pages: int = 0,
    temperature: float = 0.0,
    top_k: int = 0,
    warmup: bool = True,
    inject: str = "",
):
    """Replay one BURST trace twice — plain FIFO, then tiered admission —
    and gate overload behavior in the same run (:func:`check_overload_rows`).

    ``rate`` far above service rate piles ``n_requests`` onto ``n_slots``
    slots at once, so the FIFO row's tail requests wait out the whole
    backlog: its p95 TTFT is the makespan.  The tiered row arms the full
    overload stack on the SAME traffic: every request carries a deadline
    of ``min(deadline_ms, 0.45 * FIFO makespan)`` — same-run-relative so
    it binds on any runner speed (waiters not admitted in time shed with
    a structured :class:`RejectedOverload`), admission degrades new requests to deeper
    rank tiers under queue/page pressure (each degraded response carries
    the tier's spectral-bound certificate), and a sprinkling of
    priority-1 requests exercises page-reclaiming preemption.  Quality
    sheds before latency does — the row reports how much of each.

    ``inject="nan"`` adds a third row: the FIFO trace re-run with a
    :class:`FaultInjector` poisoning one request's logits to NaN
    mid-decode.  The gate demands exactly that request quarantined
    (status ``"error"``, tokens a clean prefix) and every OTHER request
    bit-identical to the uninjected FIFO row — a numerical blow-up in one
    slot must never leak into the rest of the batch.

    Needs ``alpha`` > 0: tiers are prefix slices of the compressed
    factors, so an uncompressed checkpoint has nothing to slice.
    """
    from repro.data.synthetic import modality_extras
    from repro.runtime.fault_tolerance import FaultInjector
    from repro.serving import Engine, Request, SamplingParams
    from repro.serving.engine import AdmissionPolicy, percentile

    assert alpha > 0, "overload trace needs a compressed checkpoint (--alpha)"
    rows = []
    for arch in archs:
        cfg = get_arch(arch, reduced=True)
        model = build_model(cfg)
        params = spectralize_params(
            model.init(jax.random.PRNGKey(seed)), jax.random.PRNGKey(9)
        )
        params, _, _ = compress_tree(
            params, CompressionPolicy(alpha=alpha, q=q, min_dim=16),
            jax.random.PRNGKey(1),
        )
        rng = np.random.default_rng(seed)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests)).tolist()
        max_len = prompt_range[1] + gen_range[1]
        eff_pages = kv_pages or n_slots * (-(-max_len // page_size))
        trace = []
        for i in range(n_requests):
            trace.append(dict(
                prompt=rng.integers(
                    0, cfg.vocab, size=(int(rng.integers(*prompt_range)),)
                ).astype(np.int32),
                max_new=int(rng.integers(*gen_range)),
                # a few high-priority requests exercise preemption: when one
                # reaches the queue head it may reclaim a lower-priority slot
                priority=1 if i % 8 == 3 else 0,
            ))

        def build_reqs(*, deadline=None, priorities: bool):
            out = []
            for i, t in enumerate(trace):
                out.append(Request(
                    prompt=t["prompt"].copy(),
                    max_new_tokens=t["max_new"],
                    sampling=SamplingParams(
                        temperature=temperature, top_k=top_k, seed=seed + i
                    ),
                    extras=modality_extras(cfg, np.random.default_rng(seed + i)),
                    deadline_ms=deadline,
                    min_tier=len(tiers) - 1,
                    priority=t["priority"] if priorities else 0,
                ))
            return out

        def build_engine(*, tiered: bool, injector=None):
            return Engine(
                model, params, n_slots=n_slots, max_len=max_len,
                decode_block=decode_block, page_size=page_size,
                kv_pages=eff_pages,
                share_prefix=tiered,  # preempted K/V re-indexes as warm cache
                tiers=tiers if tiered else None, tier_q=tier_q,
                admission=AdmissionPolicy(
                    n_tiers=len(tiers),
                    degrade_queue_depth=max(2, n_slots),
                    degrade_free_frac=0.5,
                ) if tiered else None,
                preempt=tiered,
                injector=injector,
            )

        def warm(eng, *, tiered: bool):
            # compile outside the clock: every admission group size at each
            # trace prompt bucket, per tier (prefill programs + the fused
            # block), plus one continuation-length prompt so a preemption
            # resume mid-trace does not hit a cold bucket
            wrng = np.random.default_rng(seed + 1)
            wsp = SamplingParams(temperature=temperature, top_k=top_k, seed=seed)
            lens = sorted({t["prompt"].size for t in trace})
            lens.append(min(max_len - 2, prompt_range[1] + gen_range[0]))
            gs, g = [], 1
            while g < n_slots:
                gs.append(g)
                g *= 2
            gs.append(n_slots)
            for tier in range(len(tiers) if tiered else 1):
                for g in gs:
                    for n in lens:
                        eng.reset_prefix_cache()
                        eng.run([
                            Request(
                                prompt=wrng.integers(0, cfg.vocab, size=(int(n),)),
                                max_new_tokens=2, sampling=wsp,
                                extras=modality_extras(cfg, wrng),
                                tier=tier,
                            )
                            for _ in range(g)
                        ])
            eng.reset_prefix_cache()
            eng.reset_counters()

        def replay(eng, reqs, *, label, arm=None, deadline=None):
            if warmup:
                warm(eng, tiered=eng.tiers != (1.0,))
            if arm is not None:
                arm(eng)  # post-warmup: uid counter and step clock are live
            t0 = time.perf_counter()
            done = eng.run(reqs, arrivals=arrivals)
            dt = time.perf_counter() - t0
            assert len(done) == n_requests, (len(done), n_requests)
            ok = [r for r in done if r.status == "ok"]
            shed = [r for r in done if r.status == "shed"]
            errored = [r for r in done if r.status == "error"]
            ttfts = sorted(r.ttft for r in ok)
            lats = sorted(r.latency for r in ok)
            n_tok = sum(len(r.tokens) for r in done)
            cert_bounds = [
                c.prob_deviation_bound
                for c in eng.tier_certificates
                if c is not None
            ]
            row = dict(
                name=f"overload={arch}+{label}",
                arch=f"{arch}+{label}",
                seconds=dt,
                tok_s=n_tok / dt,
                p50_ms=percentile(lats, 0.5) * 1e3 if lats else 0.0,
                p95_ms=percentile(lats, 0.95) * 1e3 if lats else 0.0,
                ttft_ms=float(np.mean(ttfts)) * 1e3 if ttfts else 0.0,
                p95_ttft_ms=percentile(ttfts, 0.95) * 1e3 if ttfts else 0.0,
                completed=len(ok),
                shed=len(shed),
                errored=len(errored),
                degraded=eng.degraded_admissions,
                preempted=eng.preemptions,
                quarantined=eng.quarantined,
                cert_bound=max(cert_bounds) if cert_bounds else 0.0,
                n_requests=n_requests,
                decode_steps=eng.steps,
                host_syncs=eng.host_syncs,
                tok_per_sync=eng.tokens_per_sync,
                util=eng.batch_utilization,
                peak_active=eng.peak_active,
                kv_bytes_cap=eng.kv_bytes_capacity,
                kv_bytes_peak=eng.kv_bytes_peak,
                pages_peak=eng.peak_pages_in_use,
                prefill_chunks=eng.prefill_chunks,
                shared_hits=eng.shared_page_hits,
                cow_forks=eng.cow_forks,
                # same-run gate currency (underscore keys never reach
                # CSV/JSON): structured-rejection compliance + greedy tokens
                _shed_structured=all(
                    r.rejected is not None
                    and r.rejected.uid == r.uid
                    and r.rejected.reason == "deadline-expired"
                    and r.rejected.waited_ms >= deadline
                    for r in shed
                ),
                _status=[r.status for r in done],
                _tokens=(
                    [list(r.tokens) for r in reqs] if temperature == 0.0 else None
                ),
            )
            return row

        fifo_row = replay(build_engine(tiered=False),
                          build_reqs(priorities=False),
                          label="fifo")
        rows.append(fifo_row)
        # the deadline must BIND on this runner or the tiered row gates
        # nothing: an absolute wall-clock deadline a fast machine drains
        # the whole burst under never expires.  ``deadline_ms`` is a
        # ceiling — the effective deadline is capped at just under half
        # the measured FIFO makespan, i.e. the wait the backlog tail is
        # guaranteed to exceed under FIFO pacing, whatever this runner's
        # speed.
        eff_deadline = min(deadline_ms, fifo_row["seconds"] * 1e3 * 0.45)
        rows.append(replay(build_engine(tiered=True),
                           build_reqs(deadline=eff_deadline, priorities=True),
                           label="tiered", deadline=eff_deadline))
        if inject == "nan":
            # poison the FIRST trace request at its SECOND decode token:
            # admitted in the first step (so the first fused block, where
            # steps_done == 0, covers the poison step), with one clean token
            # already emitted (so the prefix gate has a prefix to check).
            # Armed POST-warmup: warmup consumes uids and the step clock
            # resets at the warmup boundary.
            injector = FaultInjector()

            def arm(eng):
                injector.nan_logits = (eng._next_uid, min(1, decode_block - 1))
                eng.injector = injector

            row = replay(build_engine(tiered=False),
                         build_reqs(priorities=False),
                         label="inject-nan", arm=arm)
            row["_fired"] = injector.fired.get("nan_logits", 0)
            rows.append(row)
    return rows


def check_overload_rows(rows) -> int:
    """Same-run FIFO-vs-tiered (and optional fault-injection) gates.

    Both rows replayed the IDENTICAL burst on the same machine, so the
    comparisons are deterministic where they can be and same-run-relative
    where timing is involved:

    - the FIFO row completes everything and sheds nothing (no policy);
    - the tiered row sheds at least one deadline-expired waiter, every
      shed request carries a structured rejection whose ``waited_ms``
      proves the deadline really expired, and p95 TTFT over its COMPLETED
      requests is strictly below the FIFO row's (the backlog tail the
      deadline cut off);
    - at least one admission was degraded to a deeper tier, and the
      deepest tier's certificate bound is finite and positive (quality
      shed is REPORTED, not silent);
    - the inject row (when present) quarantines exactly the poisoned
      request — status ``"error"``, tokens a clean PREFIX of the
      uninjected run's — and every other request is bit-identical.
    """
    by_arch = {r["arch"]: r for r in rows if "arch" in r}
    failures = 0
    for arch, tiered in by_arch.items():
        if not arch.endswith("+tiered"):
            continue
        label = arch[: -len("+tiered")]
        fifo = by_arch.get(f"{label}+fifo")
        if fifo is None:
            continue
        checks = [
            ("fifo_completes_all", fifo["completed"] == fifo["n_requests"],
             f"{fifo['completed']} == {fifo['n_requests']}"),
            ("fifo_sheds_nothing", fifo["shed"] == 0, f"{fifo['shed']} == 0"),
            ("tiered_sheds", tiered["shed"] > 0, f"{tiered['shed']} > 0"),
            ("tiered_completes_some", tiered["completed"] > 0,
             f"{tiered['completed']} > 0"),
            ("shed_structured", bool(tiered["_shed_structured"]),
             "every shed request carries a deadline-expired rejection"),
            ("p95_ttft_ms",
             tiered["p95_ttft_ms"] < fifo["p95_ttft_ms"],
             f"{tiered['p95_ttft_ms']:.1f} < {fifo['p95_ttft_ms']:.1f}"),
            ("degraded", tiered["degraded"] > 0, f"{tiered['degraded']} > 0"),
            ("cert_bound",
             0.0 < tiered["cert_bound"] < float("inf"),
             f"0 < {tiered['cert_bound']:.3g} < inf"),
        ]
        inj = by_arch.get(f"{label}+inject-nan")
        if inj is not None:
            n_err = sum(1 for s in inj["_status"] if s == "error")
            bad = [i for i, s in enumerate(inj["_status"]) if s == "error"]
            prefix_ok = others_ok = True
            if inj.get("_tokens") is not None and fifo.get("_tokens") is not None:
                for i, (got, want) in enumerate(zip(inj["_tokens"], fifo["_tokens"])):
                    if i in bad:
                        prefix_ok &= 0 < len(got) < len(want) and got == want[: len(got)]
                    else:
                        others_ok &= got == want
            checks += [
                ("inject_fired", inj["_fired"] == 1, f"{inj['_fired']} == 1"),
                ("quarantined_exactly_one",
                 inj["quarantined"] == 1 and n_err == 1,
                 f"quarantined={inj['quarantined']} errored={n_err}"),
                ("poisoned_prefix", prefix_ok,
                 "poisoned tokens are a clean prefix of the uninjected run"),
                ("others_bit_identical", others_ok,
                 "every other request matches the uninjected run"),
            ]
        for metric, ok, detail in checks:
            print(
                f"[perf-smoke] {label} overload {metric}: {detail} "
                f"{'OK' if ok else 'VIOLATION'}"
            )
            failures += 0 if ok else 1
    return failures


def run_failover_trace(
    archs=("llama3.2-1b",),
    *,
    rate: float = 200.0,
    n_requests: int = 12,
    n_slots: int = 2,
    n_replicas: int = 2,
    prompt_range=(3, 7),
    gen_range=(12, 16),
    sys_prompt_len: int = 8,
    page_size: int = 4,
    decode_block: int = 4,
    heartbeat_ms: float = 150.0,
    max_failovers: int = 3,
    kill_step: int = 6,
    seed: int = 0,
    temperature: float = 0.0,
    top_k: int = 0,
    warmup: bool = True,
    inject: str = "",
):
    """Replay one system-prompt burst three ways — a single engine (the
    bit-exactness reference), a healthy N-replica cluster, and (with
    ``inject="kill_replica"``) the same cluster with replica 0 killed
    mid-burst — and gate the failover contract in the same run
    (:func:`check_failover_rows`).

    The trace is SYSTEM-PROMPT traffic on a sharing engine:
    ``sys_prompt_len`` spans ≥ 2 full pages, so by kill time every
    replica's prefix index holds the shared prefix — a failover
    continuation re-routed to the survivor matches those pages read-only
    (``prefill_skipped > 0``) and the trace demonstrably exercises the
    PREFIX-MATCH resume path, not just cold re-prefill.

    Greedy determinism is gated per COMPUTE PATH.  Requests that never
    failed over must be bit-identical to the single-engine replay (same
    path, no excuse).  A failed-over request's credited prefix must be
    bit-identical up to the kill point, and its resumed tail is verified
    by REPLAYING the exact continuation on the reference engine in the
    same run — the resume must reproduce, bit for bit, what any healthy
    engine emits for that continuation.  (Prefill-written and
    decode-written KV differ in low-order bits — a property the engine's
    merged preemption path shares — so the resumed tail may legitimately
    diverge from the UNINTERRUPTED replay at an argmax near-tie; the
    replay check is the strongest bit-exactness the engine actually
    guarantees, and it is checked, not assumed.)

    All gates are same-run relative (two cluster rows share this
    machine's load); the only wall-clock allowance is the detection
    window — a killed replica's waiters cannot get their first token
    before the heartbeat deadline expires, so the kill row's p95 TTFT
    gate adds a ``heartbeat_ms``-proportional term.  Replica threads
    contend for the same CPU, so the cluster rows raise the straggler
    kill floor (``straggler_min_s=2.0``) — slow-device detection has its
    own unit tests; this trace must not false-kill under CI load.
    """
    from repro.analysis import sanitize
    from repro.data.synthetic import modality_extras
    from repro.runtime.fault_tolerance import FaultInjector
    from repro.serving import Cluster, Engine, Request, SamplingParams
    from repro.serving.engine import percentile
    from repro.serving.scheduler import FailoverBudget

    assert sys_prompt_len >= 2 * page_size, (
        "sys prompt must span >= 2 full pages so survivors prefix-match"
    )
    rows = []
    for arch in archs:
        cfg = get_arch(arch, reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests)).tolist()
        max_len = sys_prompt_len + prompt_range[1] + gen_range[1]
        sys_prompt = rng.integers(0, cfg.vocab, size=(sys_prompt_len,)).astype(
            np.int32
        )
        trace = []
        for i in range(n_requests):
            user = rng.integers(
                0, cfg.vocab, size=(int(rng.integers(*prompt_range)),)
            ).astype(np.int32)
            trace.append(dict(
                prompt=np.concatenate([sys_prompt, user]),
                max_new=int(rng.integers(*gen_range)),
            ))

        def build_reqs():
            return [
                Request(
                    prompt=t["prompt"].copy(),
                    max_new_tokens=t["max_new"],
                    sampling=SamplingParams(
                        temperature=temperature, top_k=top_k, seed=seed + i
                    ),
                    extras=modality_extras(cfg, np.random.default_rng(seed + i)),
                )
                for i, t in enumerate(trace)
            ]

        def make_engine(_rid=0):
            # chunk == page: every prompt exceeds it, so ALL prefill rides
            # the one compiled (1, C) chunk program — failover resumes
            # (arbitrary prompt+emitted lengths) never hit a cold bucket
            return Engine(
                model, params, n_slots=n_slots, max_len=max_len,
                decode_block=decode_block, page_size=page_size,
                prefill_chunk=page_size, share_prefix=True,
            )

        def warm(eng):
            wrng = np.random.default_rng(seed + 1)
            wsp = SamplingParams(temperature=temperature, top_k=top_k, seed=seed)
            for g in (1, n_slots):
                eng.run([
                    Request(
                        prompt=wrng.integers(
                            0, cfg.vocab, size=(sys_prompt_len + prompt_range[1],)
                        ),
                        max_new_tokens=2, sampling=wsp,
                        extras=modality_extras(cfg, wrng),
                    )
                    for _ in range(g)
                ])
            eng.reset_prefix_cache()
            eng.reset_counters()

        def summarize(label, done, reqs, engines, dt, clu=None, fired=0):
            assert len(done) == n_requests, (label, len(done), n_requests)
            ok = [r for r in done if r.status == "ok"]
            shed = [r for r in done if r.status == "shed"]
            errored = [r for r in done if r.status == "error"]
            ttfts = sorted(r.ttft for r in ok if r.ttft is not None)
            lats = sorted(r.latency for r in ok if r.latency is not None)
            n_tok = sum(len(r.tokens) for r in done)
            syncs = sum(e.host_syncs for e in engines)
            row = dict(
                name=f"failover={arch}+{label}",
                arch=f"{arch}+{label}",
                seconds=dt,
                tok_s=n_tok / dt,
                p50_ms=percentile(lats, 0.5) * 1e3 if lats else 0.0,
                p95_ms=percentile(lats, 0.95) * 1e3 if lats else 0.0,
                ttft_ms=float(np.mean(ttfts)) * 1e3 if ttfts else 0.0,
                p95_ttft_ms=percentile(ttfts, 0.95) * 1e3 if ttfts else 0.0,
                completed=len(ok),
                shed=len(shed),
                errored=len(errored),
                n_requests=n_requests,
                decode_steps=sum(e.steps for e in engines),
                host_syncs=syncs,
                tok_per_sync=(
                    sum(e.decoded_tokens for e in engines) / max(syncs, 1)
                ),
                util=float(np.mean([e.batch_utilization for e in engines])),
                peak_active=max(e.peak_active for e in engines),
                kv_bytes_cap=sum(e.kv_bytes_capacity for e in engines),
                kv_bytes_peak=sum(e.kv_bytes_peak for e in engines),
                pages_peak=max(e.peak_pages_in_use for e in engines),
                prefill_chunks=sum(e.prefill_chunks for e in engines),
                shared_hits=sum(e.shared_page_hits for e in engines),
                cow_forks=sum(e.cow_forks for e in engines),
                replicas=len(engines),
                heartbeat_ms=heartbeat_ms,
                failovers=clu.failovers if clu else 0,
                failovers_prefix_match=clu.failovers_prefix_match if clu else 0,
                replica_lost=clu.replica_deaths if clu else 0,
                heartbeat_misses=clu.heartbeat_misses if clu else 0,
                # same-run gate currency (underscore keys never reach
                # CSV/JSON)
                _status=[r.status for r in done],
                _tokens=(
                    [list(r.tokens) for r in reqs]
                    if temperature == 0.0 else None
                ),
                _rejects_structured=all(
                    r.rejected is not None and r.rejected.uid == r.uid
                    for r in shed
                ),
                _fired=fired,
                _failed_over=(
                    [r.uid in clu.resume_points for r in reqs]
                    if clu else [False] * len(reqs)
                ),
                _splits=(
                    {i: list(clu.resume_points[r.uid])
                     for i, r in enumerate(reqs) if r.uid in clu.resume_points}
                    if clu else {}
                ),
                _resume_bad=0,
            )
            return row

        # --- reference: one engine, no cluster, same trace -------------
        eng = make_engine()
        if warmup:
            warm(eng)
        reqs_single = build_reqs()
        t0 = time.perf_counter()
        done = eng.run(reqs_single, arrivals=arrivals)
        dt = time.perf_counter() - t0
        single_tokens = [list(r.tokens) for r in reqs_single]
        rows.append(summarize("single", done, reqs_single, [eng], dt))

        # --- the cluster rows: healthy, then with a replica killed -----
        def cluster_row(label, injector=None):
            clu = Cluster(
                make_engine, n_replicas,
                heartbeat_ms=heartbeat_ms,
                budget=FailoverBudget(max_failovers=max_failovers,
                                      base_ms=10.0),
                injector=injector,
                straggler_min_s=2.0,
            )
            if warmup:
                for rep in clu.replicas:
                    warm(rep.eng)
            reqs = build_reqs()
            t0 = time.perf_counter()
            done = clu.run(reqs, arrivals=arrivals, timeout_s=120.0)
            dt = time.perf_counter() - t0
            clu.close()
            if sanitize.enabled():
                # REPRO_SANITIZE=1: any guarded-attribute access that raced
                # during the trace was recorded, not raised; fail loud here.
                sanitize.check()
            fired = injector.fired.get("kill_replica", 0) if injector else 0
            row = summarize(
                label, done, reqs, [r.eng for r in clu.replicas], dt,
                clu=clu, fired=fired,
            )
            return row, reqs

        def verify_resumes(row, kreqs):
            """Replay each failed-over request's continuation(s) on the
            reference engine: the credited prefix must match the single
            replay bit-for-bit up to the first split, and every resumed
            tail must be exactly what the healthy engine emits for that
            continuation.  Returns the number of corrupt streams."""
            bad = 0
            eng.reset_prefix_cache()
            eng.reset_counters()
            for i, req in enumerate(kreqs):
                splits = row["_splits"].get(i)
                if not splits or req.status != "ok":
                    continue
                chain = list(req.tokens)
                if chain[: splits[0]] != single_tokens[i][: splits[0]]:
                    bad += 1
                    continue
                bounds = splits + [len(chain)]
                for j, k in enumerate(splits):
                    end = bounds[j + 1]
                    cont = Request(
                        prompt=np.concatenate(
                            [trace[i]["prompt"],
                             np.asarray(chain[:k], np.int32)]
                        ),
                        max_new_tokens=trace[i]["max_new"] - k,
                        sampling=SamplingParams(
                            temperature=temperature, top_k=top_k,
                            seed=seed + i,
                        ),
                        extras=modality_extras(
                            cfg, np.random.default_rng(seed + i)
                        ),
                    )
                    eng.run([cont])
                    if chain[k:end] != list(cont.tokens)[: end - k]:
                        bad += 1
                        break
            return bad

        hrow, _ = cluster_row("cluster")
        rows.append(hrow)
        if inject == "kill_replica":
            krow, kreqs = cluster_row(
                "cluster-kill",
                injector=FaultInjector(kill_replica=(0, kill_step)),
            )
            if temperature == 0.0:
                krow["_resume_bad"] = verify_resumes(krow, kreqs)
            rows.append(krow)
    return rows


def check_failover_rows(rows, *, tolerance: float = 0.5) -> int:
    """Same-run single-vs-cluster-vs-kill gates (the --trace failover
    contract).

    - the single row and the healthy cluster row complete everything,
      bit-identically (greedy: distribution across replicas must not
      change a single token);
    - the kill row loses exactly one replica to the injected fault and
      ZERO requests silently: every request completes or carries a
      structured rejection;
    - kill-row requests that never failed over are bit-identical to the
      single replay; failed-over requests carry a bit-identical credited
      prefix and a resumed tail bit-identical to the reference engine's
      replay of the same continuation (``_resume_bad == 0`` — see
      :func:`run_failover_trace` on per-compute-path determinism);
    - at least one failover happened and at least one resumed through a
      prefix match on the survivor (``prefill_skipped > 0``);
    - kill-row p95 TTFT over completed requests stays within
      ``tolerance`` of the healthy row, plus a detection allowance of
      4 x ``heartbeat_ms`` (a killed replica's waiters cannot be
      re-routed before the deadline expires — that window is the cost of
      detection, not a regression).
    """
    by_arch = {r["arch"]: r for r in rows if "arch" in r}
    failures = 0
    for arch, kill in by_arch.items():
        if not arch.endswith("+cluster-kill"):
            continue
        label = arch[: -len("+cluster-kill")]
        single = by_arch.get(f"{label}+single")
        healthy = by_arch.get(f"{label}+cluster")
        if single is None or healthy is None:
            continue
        checks = [
            ("single_completes_all",
             single["completed"] == single["n_requests"],
             f"{single['completed']} == {single['n_requests']}"),
            ("healthy_completes_all",
             healthy["completed"] == healthy["n_requests"]
             and healthy["replica_lost"] == 0,
             f"{healthy['completed']} == {healthy['n_requests']}, "
             f"deaths={healthy['replica_lost']}"),
            ("kill_fired", kill["_fired"] == 1, f"{kill['_fired']} == 1"),
            ("kill_replica_died", kill["replica_lost"] >= 1,
             f"{kill['replica_lost']} >= 1"),
            ("zero_silently_lost",
             len(kill["_status"]) == kill["n_requests"]
             and bool(kill["_rejects_structured"]),
             "every request completed or carries a structured rejection"),
            ("failover_observed", kill["failovers"] >= 1,
             f"{kill['failovers']} >= 1"),
            ("prefix_match_failover", kill["failovers_prefix_match"] >= 1,
             f"{kill['failovers_prefix_match']} >= 1"),
        ]
        if healthy.get("_tokens") is not None:
            checks.append(
                ("healthy_bit_identical",
                 healthy["_tokens"] == single["_tokens"],
                 "healthy cluster tokens match the single-engine replay")
            )
        if kill.get("_tokens") is not None and single.get("_tokens") is not None:
            exact = all(
                got == want
                for got, want, status, failed in zip(
                    kill["_tokens"], single["_tokens"], kill["_status"],
                    kill["_failed_over"],
                )
                if status == "ok" and not failed
            )
            checks.append(
                ("unfailed_bit_identical", exact,
                 "requests that never failed over match the single replay")
            )
            checks.append(
                ("failover_resume_exact", kill["_resume_bad"] == 0,
                 f"{kill['_resume_bad']} corrupt resumed stream(s): every "
                 "credited prefix and replayed continuation must match")
            )
        allowance = 4.0 * kill["heartbeat_ms"]
        ceil = healthy["p95_ttft_ms"] * (1.0 + tolerance) + allowance
        checks.append(
            ("p95_ttft_ms", kill["p95_ttft_ms"] <= ceil,
             f"{kill['p95_ttft_ms']:.1f} <= {healthy['p95_ttft_ms']:.1f} "
             f"+ {tolerance:.0%} + {allowance:.0f}ms detection")
        )
        for metric, ok, detail in checks:
            print(
                f"[perf-smoke] {label} failover {metric}: {detail} "
                f"{'OK' if ok else 'VIOLATION'}"
            )
            failures += 0 if ok else 1
    return failures


def write_json(rows, json_path, *, config=None):
    """Write trace rows as the BENCH_serving.json result document."""
    keys = (
        "tok_s", "p50_ms", "p95_ms", "ttft_ms", "ttft_p95_short_ms",
        "followup_ttft_ms", "reprefill_tok", "skipped_tok", "evictions",
        "cached_pages", "n_sessions", "kv_pages",
        "n_requests", "decode_steps", "host_syncs", "tok_per_sync", "util",
        "peak_active", "kv_bytes_cap", "kv_bytes_peak", "pages_peak",
        "prefill_chunks", "shared_hits", "cow_forks", "share_supported",
        "p95_ttft_ms", "completed", "shed", "errored", "degraded",
        "preempted", "quarantined", "cert_bound",
        "replicas", "heartbeat_ms", "failovers", "failovers_prefix_match",
        "replica_lost", "heartbeat_misses",
    )
    # failover rows also carry "shed", so sniff their own key first
    if any("failovers" in r for r in rows):
        kind = "failover_trace"
    elif any("reprefill_tok" in r for r in rows):
        kind = "sessions_trace"
    elif any("shed" in r for r in rows):
        kind = "overload_trace"
    else:
        kind = "poisson_trace"
    doc = {
        "kind": kind,
        "config": config or {},
        "rows": {
            r["arch"]: {k: r[k] for k in keys if k in r}
            for r in rows
            if "arch" in r
        },
    }
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def check_baseline(rows, baseline_path, *, tolerance: float) -> int:
    """Compare trace rows to a checked-in baseline; return #regressions.

    tok/s regresses if current < baseline * (1 - tolerance); decode-batch
    utilization likewise; TTFT p95 over short requests (the long-prompt
    mixed trace: present when both sides report it) regresses UPWARD —
    current > baseline * (1 + tolerance) — since chunked prefill exists
    precisely to bound it.  Throughput on shared CI runners is noisy, so
    the tolerance is deliberately generous — the gate exists to catch the
    "decode got order-of-magnitude slower / the batch went idle / a long
    prefill stalls everyone again" class of regression, not 5% drift.
    Archs missing from the baseline are skipped with a note (so adding an
    arch to the trace never breaks CI).
    """
    with open(baseline_path) as f:
        base = json.load(f)["rows"]
    failures = 0
    for r in rows:
        arch = r.get("arch")
        if arch is None:
            continue
        if arch not in base:
            print(f"[perf-smoke] {arch}: no baseline entry, skipping")
            continue
        for metric in ("tok_s", "util"):
            floor = base[arch][metric] * (1.0 - tolerance)
            ok = r[metric] >= floor
            print(
                f"[perf-smoke] {arch} {metric}: current={r[metric]:.3f} "
                f"baseline={base[arch][metric]:.3f} floor={floor:.3f} "
                f"{'OK' if ok else 'REGRESSION'}"
            )
            failures += 0 if ok else 1
        if "ttft_p95_short_ms" in r and "ttft_p95_short_ms" in base[arch]:
            # informational only: absolute TTFT tracks runner load too
            # tightly to gate — the binding TTFT gate is the SAME-RUN
            # paged-vs-flat comparison in check_paged_rows
            print(
                f"[perf-smoke] {arch} ttft_p95_short_ms: "
                f"current={r['ttft_p95_short_ms']:.1f} "
                f"baseline={base[arch]['ttft_p95_short_ms']:.1f} (info)"
            )
    return failures


def check_paged_rows(rows, *, tolerance: float = 0.3) -> int:
    """Same-run flat-vs-paged gates (the --compare-paged contract).

    Both rows ran back-to-back on the SAME machine under the same load, so
    these comparisons are robust where absolute wall-clock floors are not:
    at equal KV bytes the paged engine must (a) admit strictly more
    concurrent requests (peak_active — a deterministic count, gated with
    NO slack), (b) not lose throughput, and (c) hold TTFT p95 for short
    requests at or below the flat engine's while a long prompt is
    prefilling (that bound is the entire point of chunked prefill).  The
    two timing-based checks still see half-trace noise (a noisy neighbor
    can land on one half only), so they get ``tolerance`` slack — tighter
    than the cross-machine baseline floors, but not zero.  Returns
    #violations.
    """
    by_arch = {r["arch"]: r for r in rows if "arch" in r}
    failures = 0
    for arch, flat in by_arch.items():
        paged = by_arch.get(f"{arch}+paged")
        if paged is None or arch.endswith("+paged"):
            continue
        checks = [
            ("peak_active", paged["peak_active"] > flat["peak_active"],
             f"{paged['peak_active']} > {flat['peak_active']}"),
            ("tok_s",
             paged["tok_s"] >= flat["tok_s"] * (1.0 - tolerance),
             f"{paged['tok_s']:.1f} >= {flat['tok_s']:.1f} - {tolerance:.0%}"),
        ]
        if "ttft_p95_short_ms" in paged and "ttft_p95_short_ms" in flat:
            checks.append(
                ("ttft_p95_short_ms",
                 paged["ttft_p95_short_ms"]
                 <= flat["ttft_p95_short_ms"] * (1.0 + tolerance),
                 f"{paged['ttft_p95_short_ms']:.1f} <= "
                 f"{flat['ttft_p95_short_ms']:.1f} + {tolerance:.0%}")
            )
        for metric, ok, detail in checks:
            print(
                f"[perf-smoke] {arch} paged-vs-flat {metric}: {detail} "
                f"{'OK' if ok else 'VIOLATION'}"
            )
            failures += 0 if ok else 1
    return failures


def check_shared_rows(rows, *, tolerance: float = 0.3) -> int:
    """Same-run unshared-vs-shared gates (the --shared-prefix contract).

    Both rows replay the IDENTICAL system-prompt trace through the paged
    engine at equal KV bytes, pairing ``X`` with ``X+shared``.  Two gates
    are deterministic counts and get NO slack: the sharing engine must
    peak strictly FEWER pages (a prefix page backing many slots occupies
    one page of HBM) and strictly MORE admitted concurrency (page-gated
    admission banks exactly those savings); it must also actually have
    shared something (hit counter), hold throughput within ``tolerance``
    (a timing number — same machine, but half-trace noise is real), and —
    for greedy traces — emit bit-identical tokens per request (sharing
    relocates bytes, never changes what is attended).  Returns #violations.

    The strict peak gates presuppose the trace actually SATURATES the
    page pool (arrivals far faster than service, as the CI config's burst
    rate guarantees): a trickle that never queues on pages peaks both
    rows identically and gates nothing.
    """
    by_arch = {r["arch"]: r for r in rows if "arch" in r}
    failures = 0
    for arch, shared in by_arch.items():
        if not arch.endswith("+shared"):
            continue
        base = by_arch.get(arch[: -len("+shared")])
        if base is None:
            continue
        if not shared.get("share_supported"):
            # sharing is documented-inert for this family (no paged
            # leaves or no mid-prompt prefill): identical rows are the
            # CORRECT outcome, not a regression
            print(
                f"[perf-smoke] {arch[: -len('+shared')]} shared-vs-unshared: "
                f"sharing inert for this arch, gates skipped"
            )
            continue
        checks = [
            ("pages_peak", shared["pages_peak"] < base["pages_peak"],
             f"{shared['pages_peak']} < {base['pages_peak']}"),
            ("peak_active", shared["peak_active"] > base["peak_active"],
             f"{shared['peak_active']} > {base['peak_active']}"),
            ("shared_hits", shared["shared_hits"] > 0,
             f"{shared['shared_hits']} > 0"),
            ("tok_s", shared["tok_s"] >= base["tok_s"] * (1.0 - tolerance),
             f"{shared['tok_s']:.1f} >= {base['tok_s']:.1f} - {tolerance:.0%}"),
        ]
        if base.get("_tokens") is not None and shared.get("_tokens") is not None:
            checks.append(
                ("greedy_parity", shared["_tokens"] == base["_tokens"],
                 "bit-identical tokens per request")
            )
        for metric, ok, detail in checks:
            print(
                f"[perf-smoke] {arch[: -len('+shared')]} shared-vs-unshared "
                f"{metric}: {detail} {'OK' if ok else 'VIOLATION'}"
            )
            failures += 0 if ok else 1
    return failures


def emit_csv(rows, csv_path=None):
    lines = []
    for r in rows:
        if "p50_ms" in r:  # trace rows
            extra = ""
            if "ttft_p95_short_ms" in r:
                extra = f";ttft_p95_short_ms={r['ttft_p95_short_ms']:.0f}"
            if "reprefill_tok" in r:  # sessions-trace columns
                extra += (
                    f";followup_ttft_ms={r['followup_ttft_ms']:.0f}"
                    f";reprefill_tok={r['reprefill_tok']}"
                    f";skipped_tok={r['skipped_tok']}"
                    f";evictions={r['evictions']}"
                    f";cached_pages={r['cached_pages']}"
                )
            if "failovers" in r:  # failover-trace columns
                extra += (
                    f";p95_ttft_ms={r['p95_ttft_ms']:.0f}"
                    f";completed={r['completed']}"
                    f";shed={r['shed']}"
                    f";replicas={r['replicas']}"
                    f";failovers={r['failovers']}"
                    f";prefix_match={r['failovers_prefix_match']}"
                    f";replica_lost={r['replica_lost']}"
                    f";heartbeat_misses={r['heartbeat_misses']}"
                )
            elif "shed" in r:  # overload-trace columns
                extra += (
                    f";p95_ttft_ms={r['p95_ttft_ms']:.0f}"
                    f";completed={r['completed']}"
                    f";shed={r['shed']}"
                    f";degraded={r['degraded']}"
                    f";preempted={r['preempted']}"
                    f";quarantined={r['quarantined']}"
                    f";cert_bound={r['cert_bound']:.4g}"
                )
            lines.append(
                f"serving/{r['name']},{r['seconds']*1e6:.0f},"
                f"tok_s={r['tok_s']:.1f};p50_ms={r['p50_ms']:.0f};"
                f"p95_ms={r['p95_ms']:.0f};ttft_ms={r['ttft_ms']:.0f};"
                f"n_req={r['n_requests']};decode_steps={r['decode_steps']};"
                f"host_syncs={r['host_syncs']};"
                f"tok_per_sync={r['tok_per_sync']:.1f};util={r['util']:.3f};"
                f"peak_active={r['peak_active']};"
                f"kv_bytes_peak={r['kv_bytes_peak']};"
                f"kv_bytes_cap={r['kv_bytes_cap']};"
                f"pages_peak={r['pages_peak']};"
                f"prefill_chunks={r['prefill_chunks']};"
                f"shared_hits={r['shared_hits']};"
                f"cow_forks={r['cow_forks']}"
                f"{extra}"
            )
        else:
            extra = f";hits={r['hits']}" if "hits" in r else ""
            lines.append(
                f"serving/{r['name']},{r['seconds']*1e6:.0f},"
                f"tok_s={r['tok_s']:.1f};agree={r['agree']:.3f};ratio={r['ratio']:.3f}"
                f"{extra}"
            )
    out = "\n".join(lines)
    print(out)
    if csv_path:
        # trace rows carry the WHOLE replay's wall-clock, not per-call time
        header = "name,total_us,derived" if any("p50_ms" in r for r in rows) else "name,us_per_call,derived"
        with open(csv_path, "w") as f:
            f.write(header + "\n" + out + "\n")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--sweep-backends",
        action="store_true",
        help="run the compressed model once per kernel backend and report "
        "per-backend throughput + dispatcher hit counts",
    )
    ap.add_argument(
        "--trace",
        choices=["poisson", "sessions", "overload", "failover"],
        default=None,
        help="replay an arrival trace through the continuous-batching "
        "engine: 'poisson' = independent requests; 'sessions' = "
        "multi-turn conversations replayed TWICE (prefix sharing off, "
        "then on) with the same-run session-cache gate; 'overload' = "
        "one burst replayed TWICE (plain FIFO, then tiered admission "
        "with deadline shedding and preemption) with the same-run "
        "overload gate; 'failover' = one system-prompt burst replayed "
        "on a single engine, a healthy replica cluster, and (with "
        "--inject kill_replica) a cluster losing a replica mid-burst, "
        "with the same-run bit-exact failover gate",
    )
    ap.add_argument("--arch", default="llama3.2-1b",
                    help="comma-separated reduced arch ids (trace mode)")
    ap.add_argument("--rate", type=float, default=20.0, help="req/s (trace mode)")
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--decode-block", type=int, default=8,
                    help="decode tokens per host round-trip (trace mode)")
    ap.add_argument("--prompt-range", default="4,16",
                    help="min,max prompt tokens (trace mode)")
    ap.add_argument("--gen-range", default="4,16",
                    help="min,max generated tokens (trace mode)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size in tokens; 0 = flat slot pool")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="paged-pool size in pages; 0 = flat-equivalent")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill chunk size; 0 = monolithic")
    ap.add_argument("--long-frac", type=float, default=0.0,
                    help="fraction of requests drawing LONG prompts "
                    "(long-prompt mixed trace)")
    ap.add_argument("--long-prompt-range", default="48,64",
                    help="min,max long-prompt tokens when --long-frac > 0")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="replay a common-system-prompt trace TWICE on the "
                    "paged engine at equal KV bytes — prefix sharing off, "
                    "then on (+shared row) — and report the same-run "
                    "contract (strictly fewer peak pages, strictly more "
                    "admitted concurrency, no throughput loss, "
                    "bit-identical greedy tokens)")
    ap.add_argument("--sys-prompt-len", type=int, default=12,
                    help="common system-prompt tokens for --shared-prefix "
                    "(keep >= 2 pages so full-page matching engages)")
    ap.add_argument("--n-sessions", type=int, default=4,
                    help="conversations in the sessions trace")
    ap.add_argument("--turns-range", default="3,5",
                    help="min,max chat turns per session (inclusive)")
    ap.add_argument("--user-range", default="3,6",
                    help="min,max new user tokens per turn (inclusive)")
    ap.add_argument("--think-ms", type=float, default=10.0,
                    help="delay between a reply and its follow-up turn")
    ap.add_argument("--warm-cache-pages", type=int, default=0,
                    help="LRU budget on matchable refcount-0 pages "
                    "(sessions trace, shared row); 0 = unbounded")
    ap.add_argument("--compare-paged", action="store_true",
                    help="run each arch TWICE at equal KV bytes: the flat "
                    "slot pool, then a paged pool (+paged row) with twice "
                    "the slots backed by the same page budget — the "
                    "admitted-concurrency/throughput comparison the paged "
                    "pool exists for")
    ap.add_argument("--deadline-ms", type=float, default=300.0,
                    help="admission deadline for the overload trace's "
                    "tiered row (waiters shed past it)")
    ap.add_argument("--tiers", default="1.0,0.5",
                    help="comma-separated rank fractions for the overload "
                    "trace's tiered row (first must be 1.0)")
    ap.add_argument("--inject", choices=["nan", "kill_replica"], default=None,
                    help="overload trace ('nan'): add a fault-injection row "
                    "(one request's logits poisoned to NaN mid-decode) "
                    "gated on exact single-request quarantine; failover "
                    "trace ('kill_replica'): add a cluster row with "
                    "replica 0 killed mid-burst, gated on bit-exact "
                    "failover with zero silent losses")
    ap.add_argument("--replicas", type=int, default=2,
                    help="cluster size for the failover trace")
    ap.add_argument("--heartbeat-ms", type=float, default=150.0,
                    help="failover trace: replica heartbeat deadline floor")
    ap.add_argument("--max-failovers", type=int, default=3,
                    help="failover trace: per-request retry budget before "
                    "a structured replica_lost rejection")
    ap.add_argument("--kill-step", type=int, default=6,
                    help="failover trace: replica-0 local step at which "
                    "--inject kill_replica fires")
    ap.add_argument("--no-warmup", action="store_true",
                    help="include jit compile time in the trace row")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--alpha", type=float, default=0.0,
                    help="RSI compression alpha (0 = dense) for trace mode")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--csv", default=None, help="also write rows to this CSV file")
    ap.add_argument("--json", default=None,
                    help="write trace rows to this JSON file (BENCH_serving.json)")
    ap.add_argument("--check-baseline", default=None,
                    help="baseline BENCH_serving.json to compare against; "
                    "exits non-zero if tok/s or utilization regresses")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="allowed relative drop vs the baseline (CI runners "
                    "are noisy; this gates collapses, not drift)")
    args = ap.parse_args()
    use_compile_cache()
    if args.trace == "poisson":
        common = dict(
            rate=args.rate,
            n_requests=args.n_requests,
            temperature=args.temperature,
            top_k=args.top_k,
            seed=args.seed,
            alpha=args.alpha,
            decode_block=args.decode_block,
            prompt_range=tuple(int(x) for x in args.prompt_range.split(",")),
            gen_range=tuple(int(x) for x in args.gen_range.split(",")),
            long_frac=args.long_frac,
            long_prompt_range=tuple(int(x) for x in args.long_prompt_range.split(",")),
            warmup=not args.no_warmup,
        )
        arch_list = tuple(a.strip() for a in args.arch.split(",") if a.strip())
        # effective paged geometry, recorded verbatim in the --json config
        # block so a checked-in baseline documents the run that produced it
        eff = dict(page_size=args.page_size, kv_pages=args.kv_pages,
                   prefill_chunk=args.prefill_chunk)
        if args.compare_paged and args.shared_prefix:
            raise SystemExit(
                "--compare-paged and --shared-prefix are separate "
                "comparisons; run them as two invocations"
            )
        if args.shared_prefix:
            # identical paged geometry for both rows (EQUAL KV bytes, same
            # slots, same trace): the only difference is share_prefix.  The
            # default pool is sized to BIND — half the worst-case footprint
            # — so page-gated admission, not slot count, is what the banked
            # prefix pages relax.
            page = args.page_size or 4
            top = max(common["prompt_range"][1],
                      common["long_prompt_range"][1] if args.long_frac > 0 else 0)
            max_len = args.sys_prompt_len + top + common["gen_range"][1]
            max_pages = -(-max_len // page)
            eff = dict(page_size=page,
                       kv_pages=args.kv_pages or args.n_slots * max_pages // 2,
                       prefill_chunk=args.prefill_chunk,
                       sys_prompt_len=args.sys_prompt_len, share_prefix=True)
            base_kw = dict(
                n_slots=args.n_slots, max_len=max_len, page_size=page,
                kv_pages=eff["kv_pages"], prefill_chunk=args.prefill_chunk,
                sys_prompt_len=args.sys_prompt_len, **common,
            )
            # "+sys" keeps these rows distinct from the --compare-paged rows
            # in a merged baseline file; the pairing rule is X vs X+shared
            rows = run_trace(arch_list, row_suffix="+sys", **base_kw)
            rows += run_trace(
                arch_list, share_prefix=True, row_suffix="+sys+shared", **base_kw
            )
        elif args.compare_paged:
            # equal KV bytes: the paged pool holds exactly the flat pool's
            # token capacity (n_slots * max_len worth of pages) but backs
            # TWICE the decode slots — admission is page-gated, so the
            # paged engine can admit more concurrent requests whenever
            # real footprints are below the flat worst case.
            page = args.page_size or 16
            chunk = args.prefill_chunk or 2 * page
            top = max(common["prompt_range"][1],
                      common["long_prompt_range"][1] if args.long_frac > 0 else 0)
            max_len = top + common["gen_range"][1]
            max_pages = -(-max_len // page)
            eff = dict(page_size=page,
                       kv_pages=args.kv_pages or args.n_slots * max_pages,
                       prefill_chunk=chunk, paged_n_slots=2 * args.n_slots)
            rows = run_trace(arch_list, n_slots=args.n_slots, max_len=max_len, **common)
            rows += run_trace(
                arch_list,
                n_slots=eff["paged_n_slots"],
                max_len=max_len,
                page_size=eff["page_size"],
                kv_pages=eff["kv_pages"],
                prefill_chunk=eff["prefill_chunk"],
                row_suffix="+paged",
                **common,
            )
        else:
            rows = run_trace(
                arch_list,
                n_slots=args.n_slots,
                page_size=args.page_size,
                kv_pages=args.kv_pages,
                prefill_chunk=args.prefill_chunk,
                **common,
            )
    elif args.trace == "sessions":
        # one invocation = TWO rows over the identical multi-turn trace —
        # prefix sharing off, then on (+shared) — gated against each other
        page = args.page_size or 4
        chunk = args.prefill_chunk or 2 * page
        eff = dict(page_size=page, prefill_chunk=chunk,
                   sys_prompt_len=args.sys_prompt_len,
                   n_sessions=args.n_sessions, turns_range=args.turns_range,
                   user_range=args.user_range,
                   warm_cache_pages=args.warm_cache_pages)
        arch_list = tuple(a.strip() for a in args.arch.split(",") if a.strip())
        sess_kw = dict(
            n_sessions=args.n_sessions,
            turns_range=tuple(int(x) for x in args.turns_range.split(",")),
            user_range=tuple(int(x) for x in args.user_range.split(",")),
            gen_range=tuple(int(x) for x in args.gen_range.split(",")),
            sys_prompt_len=args.sys_prompt_len,
            rate=args.rate,
            think_time=args.think_ms / 1e3,
            n_slots=args.n_slots,
            seed=args.seed,
            alpha=args.alpha,
            decode_block=args.decode_block,
            page_size=page,
            kv_pages=args.kv_pages,
            prefill_chunk=chunk,
            temperature=args.temperature,
            top_k=args.top_k,
            warmup=not args.no_warmup,
        )
        rows = run_sessions_trace(arch_list, row_suffix="+turns", **sess_kw)
        rows += run_sessions_trace(
            arch_list, share_prefix=True,
            warm_cache_pages=args.warm_cache_pages,
            row_suffix="+turns+shared", **sess_kw,
        )
    elif args.trace == "overload":
        # one invocation = two (or three, with --inject) rows over the
        # identical burst — plain FIFO, tiered admission, optionally a
        # fault-injected FIFO re-run — gated against each other
        tiers = tuple(float(f) for f in args.tiers.split(",") if f)
        page = args.page_size or 4
        eff = dict(page_size=page, kv_pages=args.kv_pages,
                   deadline_ms=args.deadline_ms, tiers=args.tiers,
                   inject=args.inject or "")
        arch_list = tuple(a.strip() for a in args.arch.split(",") if a.strip())
        rows = run_overload_trace(
            arch_list,
            rate=args.rate,
            n_requests=args.n_requests,
            n_slots=args.n_slots,
            prompt_range=tuple(int(x) for x in args.prompt_range.split(",")),
            gen_range=tuple(int(x) for x in args.gen_range.split(",")),
            deadline_ms=args.deadline_ms,
            tiers=tiers,
            seed=args.seed,
            alpha=args.alpha or 0.5,
            decode_block=args.decode_block,
            page_size=page,
            kv_pages=args.kv_pages,
            temperature=args.temperature,
            top_k=args.top_k,
            warmup=not args.no_warmup,
            inject=args.inject or "",
        )
    elif args.trace == "failover":
        # one invocation = single-engine reference + healthy cluster +
        # (with --inject kill_replica) a kill row — gated against each
        # other in the same run
        page = args.page_size or 4
        eff = dict(page_size=page, sys_prompt_len=args.sys_prompt_len,
                   replicas=args.replicas, heartbeat_ms=args.heartbeat_ms,
                   max_failovers=args.max_failovers, kill_step=args.kill_step,
                   inject=args.inject or "")
        arch_list = tuple(a.strip() for a in args.arch.split(",") if a.strip())
        rows = run_failover_trace(
            arch_list,
            rate=args.rate,
            n_requests=args.n_requests,
            n_slots=args.n_slots,
            n_replicas=args.replicas,
            prompt_range=tuple(int(x) for x in args.prompt_range.split(",")),
            gen_range=tuple(int(x) for x in args.gen_range.split(",")),
            sys_prompt_len=args.sys_prompt_len,
            page_size=page,
            decode_block=args.decode_block,
            heartbeat_ms=args.heartbeat_ms,
            max_failovers=args.max_failovers,
            kill_step=args.kill_step,
            seed=args.seed,
            temperature=args.temperature,
            top_k=args.top_k,
            warmup=not args.no_warmup,
            inject=args.inject or "",
        )
    elif args.sweep_backends:
        rows = run_backend_sweep()
    else:
        rows = run()
    emit_csv(rows, csv_path=args.csv)
    if args.json:
        if args.trace is None:
            raise SystemExit("--json applies to --trace rows")
        write_json(
            rows,
            args.json,
            config=dict(
                rate=args.rate, n_requests=args.n_requests, n_slots=args.n_slots,
                decode_block=args.decode_block, seed=args.seed, alpha=args.alpha,
                prompt_range=args.prompt_range, gen_range=args.gen_range,
                long_frac=args.long_frac,
                long_prompt_range=args.long_prompt_range,
                compare_paged=args.compare_paged,
                **eff,
            ),
        )
    if args.check_baseline:
        if args.trace != "poisson":
            raise SystemExit("--check-baseline applies to --trace poisson rows")
        n_bad = check_baseline(rows, args.check_baseline, tolerance=args.tolerance)
        if args.compare_paged:
            # half the baseline tolerance: same-machine relative gates are
            # tighter than cross-machine absolute floors, but not noise-free
            n_bad += check_paged_rows(rows, tolerance=args.tolerance / 2)
        if n_bad:
            sys.exit(f"[perf-smoke] {n_bad} metric(s) regressed beyond tolerance")
    if args.trace == "poisson" and args.shared_prefix:
        # the shared-vs-unshared contract is gated UNCONDITIONALLY: both
        # rows ran back-to-back on this machine, so the comparison is
        # meaningful even where absolute cross-machine floors are not
        n_bad = check_shared_rows(rows, tolerance=args.tolerance / 2)
        if n_bad:
            sys.exit(f"[perf-smoke] {n_bad} shared-prefix gate(s) violated")
    if args.trace == "sessions":
        # likewise same-run: sharing off vs on over the identical
        # multi-turn conversations
        n_bad = check_sessions_rows(rows, tolerance=args.tolerance / 2)
        if n_bad:
            sys.exit(f"[perf-smoke] {n_bad} sessions gate(s) violated")
    if args.trace == "overload":
        # same-run: FIFO vs tiered admission over the identical burst,
        # plus exact-quarantine gates when --inject armed a fault
        n_bad = check_overload_rows(rows)
        if n_bad:
            sys.exit(f"[perf-smoke] {n_bad} overload gate(s) violated")
    if args.trace == "failover":
        # same-run: single engine vs healthy cluster vs kill row over the
        # identical burst — the bit-exact failover contract
        n_bad = check_failover_rows(rows, tolerance=args.tolerance)
        if n_bad:
            sys.exit(f"[perf-smoke] {n_bad} failover gate(s) violated")
