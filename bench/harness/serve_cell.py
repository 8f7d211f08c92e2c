"""Set-up, window and check of a serving cell."""

from __future__ import annotations

import gc
import time

from bench.harness import check_serve, seeds
from bench.harness import traffic as gen
from bench.harness import weights
from bench.harness.serve_loop import Server


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def run(run, args, t_process, compiles, tracer):
    import jax

    from bench.cost.model import per_token
    from bench.harness.runner import CHECK_SAMPLE, DRAIN_S, _devices
    from repro.models.model import build_model

    config, mix, cell = run.config, run.mix, run.cell.cell
    cfg = weights.arch_config(config)
    model = build_model(cfg)
    comp = config["compression"]
    t = time.perf_counter()
    params = jax.block_until_ready(
        weights.make_params(model, comp, seeds.jax_key(args.seed, seeds.WEIGHTS)))
    t_weights = time.perf_counter() - t
    server = Server(model, params, config, mix)
    t = time.perf_counter()
    warm = server.warm_up(cfg.vocab, seeds.rng(args.seed, seeds.TOKENS + 100))
    print(f"[setup] weights made in {t_weights:.1f} s; warm-up {time.perf_counter() - t:.1f} s",
          flush=True)
    load = gen.open_loop(mix, float(cell["rate_rps"]), run.seconds, cfg.vocab, args.seed)
    leaves, _ = weights.served_shapes(model, comp)
    run.cost = per_token(leaves, config, route=(cfg.top_k, cfg.n_experts))
    run.n_slots = server.n_slots
    run.kv_pages = server.kv_pages
    run.decode_block = int(config["engine"]["decode_block"])
    c0 = server.counters()
    n0 = compiles.n
    run.setup_s = time.perf_counter() - t_process
    run.t0, run.records, run.steps = server.run_open(load, run.seconds, DRAIN_S, tracer)
    run.compiles_in_window = compiles.events[n0:]
    c1 = server.counters()
    run.counters = {k: c1[k] - c0[k] for k in c0}
    devices = _devices(run.cell.chips)
    run.memory_peak_bytes = memory_peak(devices)
    if tracer is not None:
        run.trace = tracer.reduce(run)
    run.attempted = len(run.records)
    run.failed = sum(1 for r in run.records if not r.ok)
    print(f"[serve] warm-up {warm} requests; {run.attempted} requests, {run.failed} failed; "
          f"pool {server.kv_pages} pages of {server.page}, at most "
          f"{server.engine.peak_pages_in_use} in use; largest prefill group "
          f"{max((s.grouped for s in run.steps), default=0)} (warmed {server.group_warm}); "
          f"counters {run.counters}", flush=True)
    # the program's state goes before the reference runs; the weights are
    # the benchmark's own and stay
    server.engine = None
    del server
    gc.collect()
    t = time.perf_counter()
    recs = check_serve.sample(run.records, args.seed, CHECK_SAMPLE)
    gap, n = check_serve.gaps(params, config, recs)
    run.check_s = time.perf_counter() - t
    print(f"[check] {len(recs)} requests, {n} served tokens re-run through the reference",
          flush=True)
    run.ok = run.failed == 0 and n > 0
    if getattr(args, "control", False):
        run.control, _ = check_serve.gaps(params, config, recs, control=True)
    limit = cell["correct"]["logit_gap"]
    return {"logit_gap": {"value": gap, "limit": limit}}
