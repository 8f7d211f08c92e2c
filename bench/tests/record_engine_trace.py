"""Record the small chip trace with the engine's own spans that
``test_engine_trace.py`` reads.

    python3 bench/tests/record_engine_trace.py

Needs a TPU.  Minitron-4b at its published widths cut to 2 layers, RSI
factors from seed 0, 8 slots (as ``record_trace.py``).  Three engine steps
are traced: a short prompt's grouped prefill, a long prompt's two chunks,
and decode blocks.  Writes ``bench/tests/data/serve_engine.xplane.pb.gz``
and what the harness counted while tracing (``serve_engine.json``).
"""

import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np

    from bench.harness import runner, weights
    from bench.harness.serve_loop import Server
    from bench.harness.trace import Tracer
    from bench.tests.record_trace import DATA, _save
    from repro.models.model import build_model
    from repro.serving import Request

    runner._devices(1)
    DATA.mkdir(exist_ok=True)
    config = json.loads((ROOT / "bench/configs/minitron4b-rsi.json").read_text())
    config = dict(config, num_hidden_layers=2,
                  engine=dict(config["engine"], n_slots=8, kv_pool_bytes=None))
    mix = {"prompt": {"min": 100, "max": 300}, "output": {"max": 40}}
    cfg = weights.arch_config(config)
    model = build_model(cfg)
    params = weights.make_params(model, config["compression"], jax.random.PRNGKey(0))
    server = Server(model, params, config, mix)
    rng = np.random.default_rng(0)
    server.warm_up(cfg.vocab, rng)
    eng = server.engine
    with tempfile.TemporaryDirectory() as tmp:
        tr = Tracer(Path(tmp), 1.0)
        tr.start(server)
        short = eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 100), max_new_tokens=40))
        long = eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 300), max_new_tokens=40))
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
        tr.stop(server)
        counters = {k: tr.c1[k] - tr.c0[k] for k in tr.c1}
        _save(Path(tmp), "serve_engine",
              {"counters": counters, "pool_shapes": tr.pool_shapes, "n_slots": 8,
               "layers": 2, "engine_steps": 3, "short_uid": short.uid, "long_uid": long.uid})
    return 0


if __name__ == "__main__":
    sys.exit(main())
