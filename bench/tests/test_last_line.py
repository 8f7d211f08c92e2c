"""A whole run at a tiny size on the CPU prints the contract's last line,
and a run that finds no TPU prints none."""

import json
import math
import os
import shutil
import subprocess
import sys

from bench.harness import spec
from bench.tests.tiny import run_tiny


def test_last_line_matches_the_contract():
    line, text = run_tiny("open", seed=2**31 + 99)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and math.isfinite(m["value"]) and m["value"] > 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    assert json.loads(text.strip().splitlines()[-1]) == line


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minitron4b-rsi.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for ln in out.splitlines():
        try:
            if "metrics" in json.loads(ln):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(spec.ROOT)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and _no_result(p.stdout)
