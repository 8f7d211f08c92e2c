"""Serving launcher: continuous-batching engine (default) or the legacy
static batched prefill+decode path, optionally from an RSI-compressed
checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
        --batch 4 --prompt-len 16 --gen 32 [--engine continuous|static] \
        [--n-slots 4] [--decode-block 8] [--temperature 0.7 --top-k 40] \
        [--page-size 64 [--kv-pages N] [--prefill-chunk 256] [--share-prefix]] \
        [--compress-alpha 0.3 --q 4] [--kernels auto|xla|pallas|reference]

``--engine continuous`` (default) routes requests through
``repro.serving.Engine``: a slotted KV-cache pool with FIFO admission,
padded micro-batch prefill, a device-resident FUSED decode loop
(``--decode-block`` tokens per host round-trip, sampling and stop
detection on device, KV pool donated through the step), and per-request
sampling params.  ``--engine static`` keeps the original fixed-shape
``greedy_generate`` path.

``--page-size`` switches the continuous engine to the PAGED KV pool:
fixed-size pages shared by all slots through per-slot block tables,
admission gated on each request's actual page need (``--kv-pages`` sizes
the pool; default matches flat capacity), and — with ``--prefill-chunk`` —
long prompts prefilled chunk-by-chunk interleaved with decode blocks so a
long prefill no longer stalls running requests.  ``--share-prefix`` adds
refcounted copy-on-write prompt-prefix sharing on top: repeated leading
full pages (system-prompt traffic) are mapped read-only instead of
re-allocated and re-prefilled.

``--tiers 1.0,0.5`` arms elastic-rank serving on a compressed checkpoint:
nested prefix slices of the SAME factors serve as cheaper fallback tiers,
and ``--degrade-queue-depth`` / ``--degrade-free-frac`` let admission move
new requests to a deeper tier under pressure instead of queueing them
(each degraded response carries the tier's spectral-bound certificate).
``--deadline-ms`` sheds waiters not admitted in time with a structured
rejection; ``--preempt`` lets queue-head requests preempt lower-priority
actives (their pages re-index as warm cache for bit-exact resume).

``--replicas N`` serves through a :class:`repro.serving.Cluster`: N
thread-backed engine replicas behind one shared admission queue with
least-loaded routing, per-replica heartbeats (``--heartbeat-ms`` floor,
deadline adapted from observed step times), and bit-exact failover — a
dead replica's in-flight requests resume on survivors with at most
``--max-failovers`` retries before a structured ``replica_lost``
rejection.  ``--event-log PATH`` appends one JSON line per serving event
(shed / degrade / preempt / quarantine / straggler / failover / replica
life-cycle), so post-mortems read a log instead of scraping stdout.

SIGINT/SIGTERM drain gracefully: the queue is shed with ``"shutdown"``
rejections, active slots decode to completion, and the summary still
prints — a second signal kills the process as usual.

Kernel backend selection goes through repro.runtime.dispatch: ``--kernels``
overrides the arch config's ``kernels`` field, and the dispatcher's hit
counters are printed after generation so you can see which path every linear
actually took.
"""

from __future__ import annotations

import argparse
import signal
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", choices=["continuous", "static"], default="continuous")
    ap.add_argument("--batch", type=int, default=4, help="number of requests")
    ap.add_argument("--n-slots", type=int, default=0,
                    help="cache slots in the pool (default: --batch)")
    ap.add_argument("--decode-block", type=int, default=8,
                    help="decode tokens per host round-trip (continuous engine)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV-cache page size in tokens; 0 = flat slot pool "
                    "(continuous engine)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="pages in the paged pool; 0 = flat-equivalent "
                    "capacity (n_slots * ceil(max_len / page_size))")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill prompts longer than this in page-backed "
                    "chunks interleaved with decode; 0 = monolithic "
                    "(requires --page-size)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="refcounted copy-on-write prompt-prefix sharing: "
                    "requests repeating an earlier prompt's leading full "
                    "pages map them read-only and prefill only the "
                    "unshared tail (requires --page-size; inert for "
                    "families without mid-prompt prefill)")
    ap.add_argument("--warm-cache-pages", type=int, default=0,
                    help="cap on refcount-0 pages kept matchable in the "
                    "prefix index (LRU eviction); 0 = unbounded "
                    "(requires --page-size)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 = softmax sampling (continuous engine)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="0 = full vocab (continuous engine)")
    ap.add_argument("--compress-alpha", type=float, default=0.0)
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--tiers", default="",
                    help="comma-separated rank fractions, first must be 1.0 "
                    "(e.g. '1.0,0.5,0.25'): nested elastic-rank tiers served "
                    "from prefix slices of the compressed factors "
                    "(continuous engine; requires --compress-alpha)")
    ap.add_argument("--tier-q", type=int, default=2,
                    help="power iterations for the per-tier certificate probe")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="shed requests not ADMITTED within this many ms "
                    "(structured rejection; 0 = no deadline)")
    ap.add_argument("--degrade-queue-depth", type=int, default=0,
                    help="queue depth at which admission degrades new "
                    "requests to a deeper tier; 0 = disabled")
    ap.add_argument("--degrade-free-frac", type=float, default=0.0,
                    help="free-page fraction below which admission degrades "
                    "new requests to a deeper tier; 0 = disabled")
    ap.add_argument("--preempt", action="store_true",
                    help="queue-head requests may preempt lower-priority "
                    "actives; preempted K/V re-indexes as warm cache for "
                    "bit-exact resume (requires --share-prefix)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind one shared admission queue "
                    "(thread-backed; heartbeat failure detection + bit-exact "
                    "failover; continuous engine only)")
    ap.add_argument("--heartbeat-ms", type=float, default=1000.0,
                    help="replica heartbeat deadline floor; the effective "
                    "per-replica deadline adapts up from observed step times")
    ap.add_argument("--max-failovers", type=int, default=2,
                    help="failovers per request before it is rejected with "
                    "reason='replica_lost'")
    ap.add_argument("--event-log", default="",
                    help="append one JSON line per serving event (shed, "
                    "degrade, preempt, quarantine, straggler, failover, "
                    "replica life-cycle) to this path")
    ap.add_argument("--close-sessions", action="store_true",
                    help="after the run, drop each prompt's cached prefix "
                    "branch (the session-close hook) and report freed pages "
                    "(requires --share-prefix)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--kernels",
        choices=["auto", "xla", "pallas", "reference"],
        default=None,
        help="kernel backend (default: the arch config's `kernels` field)",
    )
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.core import CompressionPolicy, compress_tree
    from repro.data.synthetic import SyntheticLM
    from repro.models.model import build_model
    from repro.runtime import dispatch
    from repro.runtime.compile_cache import use_compile_cache
    from repro.runtime.dispatch import DispatchConfig, use_dispatch

    use_compile_cache()
    cfg = get_arch(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    n0 = sum(x.size for x in jax.tree_util.tree_leaves(params))

    if args.compress_alpha > 0:
        policy = CompressionPolicy(alpha=args.compress_alpha, q=args.q, min_dim=16)
        params, _, rep = compress_tree(params, policy, jax.random.PRNGKey(1))
        print("[compress]", rep.summary())

    data = SyntheticLM(cfg, batch=args.batch, seq=args.prompt_len, kind="serve", seed=args.seed)
    batch = {k: jnp.asarray(v) for k, v in data.at_step(0).items()}
    max_len = args.prompt_len + args.gen

    dcfg = (
        DispatchConfig(backend=args.kernels)
        if args.kernels is not None
        else DispatchConfig.from_arch(cfg)
    )
    dev = jax.devices()[0]
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"Pallas interpret mode {dcfg.interpret_mode()}")
    dispatch.reset_counters()

    if args.engine == "static":
        from repro.train.serve_step import greedy_generate

        t0 = time.time()
        with use_dispatch(dcfg):
            out = greedy_generate(model, params, batch, steps=args.gen, max_len=max_len)
        out = np.asarray(out)
        dt = time.time() - t0
        print(f"[static] generated {out.shape} tokens in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s, params {n0/1e6:.1f}M, "
              f"kernels={dcfg.backend})")
        print("first sequences:", out[: min(2, args.batch), :12].tolist())
    else:
        from repro.serving import Cluster, Engine, EventLog, Request, SamplingParams
        from repro.serving.engine import AdmissionPolicy, percentile
        from repro.serving.scheduler import FailoverBudget

        tiers = tuple(float(f) for f in args.tiers.split(",") if f) or None
        admission = None
        if args.deadline_ms > 0 or args.degrade_queue_depth > 0 or args.degrade_free_frac > 0:
            admission = AdmissionPolicy(
                n_tiers=len(tiers) if tiers else 1,
                degrade_queue_depth=args.degrade_queue_depth or None,
                degrade_free_frac=args.degrade_free_frac or None,
            )
        n_slots = args.n_slots or args.batch
        event_log = EventLog(args.event_log) if args.event_log else None

        def make_engine(rid=0):
            return Engine(model, params, n_slots=n_slots, max_len=max_len,
                          dispatch=dcfg,
                          decode_block=args.decode_block,
                          page_size=args.page_size or None,
                          kv_pages=args.kv_pages or None,
                          prefill_chunk=args.prefill_chunk or None,
                          share_prefix=args.share_prefix,
                          warm_cache_pages=args.warm_cache_pages or None,
                          tiers=tiers, tier_q=args.tier_q,
                          admission=admission, preempt=args.preempt)

        cluster = None
        if args.replicas > 1:
            cluster = Cluster(
                make_engine, args.replicas,
                heartbeat_ms=args.heartbeat_ms,
                budget=FailoverBudget(max_failovers=args.max_failovers,
                                      base_ms=10.0),
                event_log=event_log,
            )
            eng = cluster.replicas[0].eng  # summary counters below aggregate
        else:
            eng = make_engine()
            if event_log is not None:
                sink = event_log.sink()
                eng.on_event = sink
                eng.scheduler.on_event = sink
        np_batch = {k: np.asarray(v) for k, v in batch.items()}
        reqs = []
        for b in range(args.batch):
            extras = {k: v[b] for k, v in np_batch.items() if k != "tokens"}
            # per-request seed: otherwise every request shares one PRNG
            # stream and sampled continuations are correlated across the batch
            sp = SamplingParams(
                temperature=args.temperature, top_k=args.top_k, seed=args.seed + b
            )
            reqs.append(Request(
                prompt=np_batch["tokens"][b], max_new_tokens=args.gen,
                sampling=sp, extras=extras,
                deadline_ms=args.deadline_ms or None,
                min_tier=(len(tiers) - 1) if tiers else 0,
            ))

        # graceful drain: first SIGINT/SIGTERM sheds the queue and lets
        # active slots decode to completion; default handling is restored
        # afterwards so a SECOND signal kills the process as usual
        draining = {"on": False}

        def _drain(signum, frame):
            draining["on"] = True
            print(f"\n[drain] caught {signal.Signals(signum).name}: "
                  "shedding the queue, finishing active slots")
            for s, h in prev_handlers.items():
                signal.signal(s, h)

        prev_handlers = {}
        for s in (signal.SIGINT, signal.SIGTERM):
            try:
                prev_handlers[s] = signal.signal(s, _drain)
            except ValueError:  # not the main thread (tests)
                break

        t0 = time.time()
        try:
            if cluster is not None:
                done = cluster.run(reqs, stop=lambda: draining["on"])
                cluster.close()
            else:
                done = eng.run(reqs, stop=lambda: draining["on"])
        finally:
            for s, h in prev_handlers.items():
                if signal.getsignal(s) == _drain:
                    signal.signal(s, h)
        dt = time.time() - t0
        engines = [r.eng for r in cluster.replicas] if cluster is not None else [eng]
        if cluster is not None:
            print(f"[cluster] replicas={args.replicas} "
                  f"failovers={cluster.failovers} "
                  f"prefix_match={cluster.failovers_prefix_match} "
                  f"replica_deaths={cluster.replica_deaths} "
                  f"heartbeat_misses={cluster.heartbeat_misses} "
                  f"rejoins={cluster.rejoins} "
                  f"replica_lost_rejections={cluster.exhausted}")
        ok = [r for r in done if r.status == "ok"]
        shed = [r for r in done if r.status == "shed"]
        errored = [r for r in done if r.status == "error"]
        n_tok = sum(len(r.tokens) for r in done)
        print(f"[continuous] {len(done)} requests, {n_tok} tokens in {dt:.2f}s "
              f"({n_tok / dt:.1f} tok/s, slots={n_slots}, params {n0/1e6:.1f}M, "
              f"kernels={dcfg.backend})")
        if shed or errored or admission is not None or tiers:
            by_tier = [0] * (len(tiers) if tiers else 1)
            for r in ok:
                by_tier[r.tier] += 1
            certs = " ".join(
                f"t{i}<= {c.prob_deviation_bound:.3g}"
                for i, c in enumerate(eng.tier_certificates)
                if c is not None
            )
            print(f"[overload] ok={len(ok)} shed={len(shed)} "
                  f"errored={len(errored)} "
                  f"degraded={eng.degraded_admissions} "
                  f"preemptions={eng.preemptions} "
                  f"quarantined={eng.quarantined} "
                  f"tier_counts={by_tier}" + (f" cert_bounds[{certs}]" if certs else ""))
            for r in shed:
                print(f"[shed] uid={r.rejected.uid} reason={r.rejected.reason} "
                      f"waited={r.rejected.waited_ms:.0f}ms "
                      f"queue_depth={r.rejected.queue_depth}")
        # a replay that completed ZERO requests has no percentiles —
        # report n/a instead of crashing on percentile([], ...)
        lats = sorted(r.latency for r in ok)
        lat_s = (
            f"p50={percentile(lats, 0.5)*1e3:.0f}ms "
            f"p95={percentile(lats, 0.95)*1e3:.0f}ms"
            if lats
            else "p50=n/a p95=n/a (0 completed)"
        )
        steps_t = sum(e.steps for e in engines)
        syncs_t = sum(e.host_syncs for e in engines)
        dec_t = sum(e.decoded_tokens for e in engines)
        print(f"latency {lat_s} "
              f"decode_steps={steps_t} host_syncs={syncs_t} "
              f"tok_per_sync={dec_t / max(syncs_t, 1):.1f} "
              f"util={sum(e.batch_utilization for e in engines) / len(engines):.3f}")
        for i, e in enumerate(engines):
            tag = f"[paged r{i}]" if cluster is not None else "[paged]"
            if not e.paged:
                continue
            print(f"{tag} page_size={e.page_size} pool={e.kv_pages} pages "
                  f"peak_pages={e.peak_pages_in_use} "
                  f"peak_active={e.peak_active} "
                  f"prefill_chunks={e.prefill_chunks} "
                  f"kv_bytes_cap={e.kv_bytes_capacity}")
            if args.share_prefix:
                print(f"[shared{' r%d' % i if cluster is not None else ''}] "
                      f"shared_pages={e.shared_page_hits} "
                      f"cow_forks={e.cow_forks} "
                      f"matched_admissions={e.shared_admissions} "
                      f"prefill_tok_skipped={e.skipped_prefill_tokens} "
                      f"cached_pages={e.prefix_cached_pages} "
                      f"evictions={e.prefix_evictions}")
        if args.close_sessions and args.share_prefix and cluster is None:
            freed = sum(eng.drop_session(r.prompt) for r in done)
            print(f"[sessions] closed {len(done)}, freed {freed} cached "
                  f"pages (cached now {eng.prefix_cached_pages})")
        if event_log is not None:
            event_log.close()
            print(f"[events] JSON lines appended to {args.event_log}")
        ok_done = ok if ok else done
        if ok_done and ok_done[0].tokens:
            out = np.asarray([ok_done[0].tokens], np.int32)
            print("first sequence:", ok_done[0].tokens[:12])
        else:
            out = np.zeros((0, 0), np.int32)

    print("[dispatch] per-site kernel paths:")
    print(dispatch.format_counters())
    return out


if __name__ == "__main__":
    main()
