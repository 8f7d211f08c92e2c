"""Find an open-loop cell's knee once, on the chip: the highest offered rate
at which the engine keeps up (time to first token stays flat and the queue
drains within the window).  The cell file then fixes its rate below it.

    python3 bench/sweep.py --workload <cell> --rates 1,1.5,2 --seconds 40 --seed 1

One process, one engine and one warm-up; one JSON line per rate, rates in
the order given, stopping after the first rate whose queue does not drain.
The benchmark's own runs never run it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def serve(config: dict, mix: dict, seed: int):
    """(server, vocab): the cell's engine, warmed up."""
    import jax

    from bench.harness import seeds, weights
    from bench.harness.serve_loop import Server
    from repro.models.model import build_model

    cfg = weights.arch_config(config)
    model = build_model(cfg)
    params = jax.block_until_ready(weights.make_params(
        model, config["compression"], seeds.jax_key(seed, seeds.WEIGHTS)))
    server = Server(model, params, config, mix)
    server.warm_up(cfg.vocab, seeds.rng(seed, seeds.TOKENS + 100))
    return server, cfg.vocab


def sweep(server, mix: dict, vocab: int, rates, seconds: float, seed: int):
    """One reading per rate; stops after the first rate that overloads."""
    from bench.harness import runner
    from bench.harness import traffic as gen

    for rate in rates:
        server.engine.reset_prefix_cache()
        items = gen.open_loop(mix, rate, seconds, vocab, seed)
        t0, recs, steps = server.run_open(items, seconds, runner.DRAIN_S)
        ok = [r for r in recs if r.ok]
        ttft = sorted(r.req.t_first - r.due for r in ok)
        half = len(ok) // 2
        first = sorted(r.req.t_first - r.due for r in ok[:half])
        last = sorted(r.req.t_first - r.due for r in ok[half:])
        close = t0 + seconds
        line = {
            "rate": rate, "requests": len(recs), "ok": len(ok),
            "ttft_p50_ms": 1e3 * runner.percentile(ttft, 0.5),
            "ttft_p95_ms": 1e3 * runner.percentile(ttft, 0.95),
            "ttft_p50_first_half_ms": 1e3 * runner.percentile(first, 0.5),
            "ttft_p50_second_half_ms": 1e3 * runner.percentile(last, 0.5),
            "tokens_per_s": sum(s.emitted for s in steps if s.t1 <= close) / seconds,
            "drain_s": max(r.req.t_done for r in ok) - close if ok else None,
            "largest_prefill_group": max((s.grouped for s in steps), default=0),
        }
        yield line
        if len(ok) < len(recs) or line["drain_s"] is None or line["drain_s"] > seconds / 2:
            return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import runner, spec

    runner.use_compile_cache()
    cell = spec.load_cell(args.workload)
    runner._devices(cell.chips)
    server, vocab = serve(cell.config, cell.traffic, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    for line in sweep(server, cell.traffic, vocab, rates, args.seconds, args.seed):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
