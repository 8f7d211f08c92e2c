"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/cells/<cell>.json``; its configuration, traffic mix and
metrics are found by the names it and ``BENCHMARK.json`` give.  It needs the
TPU chips the cell asks for and a ``device_kind`` listed in
``bench/peaks.json``; otherwise it exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import runner

    return runner.run(args, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
