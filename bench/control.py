"""The control of a cell's correctness check, run on the chip at the cell's
own size: for each seed, one short run of the cell whose check is made
twice, on the program's output and on the plain reference the
configuration names (``bench/reference/<name>.py``, the lookup the check
itself makes) computed a precision down (float8 e4m3 operands).  The control
has to read above the cell's limit, and the program below it.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Prints one JSON line per seed: the program's reading, the control's, the
limit.  The benchmark's own runs never run it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def readings(workload: str, seed: int, seconds: float) -> dict:
    """One run of the cell with the control switched on; its readings."""
    from bench.harness import runner, serve_cell, spec

    cell = spec.load_cell(workload)
    import jax

    devices = runner._devices(cell.chips)
    run = types.SimpleNamespace(cell=cell, config=cell.config, mix=cell.traffic,
                                peaks=spec.peaks_for(devices[0].device_kind),
                                seconds=seconds, trace=None)
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                                 trace=0, control=True)
    checks = serve_cell.run(run, args, time.perf_counter(), runner.CompileCounter(), None)
    (name, c), = checks.items()
    jax.clear_caches()
    return {"workload": workload, "seed": seed, "number": name, "program": c["value"],
            "control": run.control, "limit": c["limit"],
            "control_fails": run.control > c["limit"], "program_passes": c["value"] <= c["limit"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import runner

    runner.use_compile_cache()
    for s in args.seeds.split(","):
        print(json.dumps(readings(args.workload, int(s), args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
