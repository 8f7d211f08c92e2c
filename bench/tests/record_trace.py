"""Record the small chip trace the reducer's tests read.

    python3 bench/tests/record_trace.py

Needs a TPU.  Minitron-4b at its published widths cut to 2 layers, RSI
factors from seed 0, 8 slots, two engine steps traced (a chunk of a long
prompt and decode blocks).  Writes ``bench/tests/data/serve.xplane.pb.gz``
and what the harness counted while tracing (``serve.json``).
"""

import glob
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


def _save(tmp: Path, name: str, facts: dict) -> None:
    path = sorted(glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True))[-1]
    with open(path, "rb") as f, gzip.open(DATA / f"{name}.xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)
    (DATA / f"{name}.json").write_text(json.dumps(facts, indent=1) + "\n")


def main() -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np

    from bench.harness import runner, weights
    from bench.harness.serve_loop import Server
    from bench.harness.trace import Tracer
    from repro.models.model import build_model

    runner._devices(1)
    DATA.mkdir(exist_ok=True)
    config = json.loads((ROOT / "bench/configs/minitron4b-rsi.json").read_text())
    config = dict(config, num_hidden_layers=2,
                  engine=dict(config["engine"], n_slots=8, kv_pool_bytes=None))
    mix = {"prompt": {"min": 300, "max": 300}, "output": {"max": 40}}
    cfg = weights.arch_config(config)
    model = build_model(cfg)
    params = weights.make_params(model, config["compression"], jax.random.PRNGKey(0))
    server = Server(model, params, config, mix)
    rng = np.random.default_rng(0)
    server.warm_up(cfg.vocab, rng)
    from repro.serving import Request

    for n in (300, 280, 290):
        server.engine.submit(Request(prompt=rng.integers(0, cfg.vocab, n), max_new_tokens=40))
    server.engine.step()
    with tempfile.TemporaryDirectory() as tmp:
        tr = Tracer(Path(tmp), 1.0)
        tr.start(server)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                server.engine.step()
        tr.stop(server)
        counters = {k: tr.c1[k] - tr.c0[k] for k in tr.c1}
        _save(Path(tmp), "serve", {"counters": counters, "pool_shapes": tr.pool_shapes,
                                   "n_slots": 8, "layers": 2})
    return 0


if __name__ == "__main__":
    sys.exit(main())
