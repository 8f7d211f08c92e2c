"""What one cell is made of, found by name: the cell file, its configuration,
its traffic mix and the metrics ``BENCHMARK.json`` gives it.

Every piece lives in a file of its own under ``bench/``, so a new cell, mix,
configuration, plain reference or per-layer metric is a new file and a new
entry in ``BENCHMARK.json``, never an edit of this code.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
REFERENCES = BENCH / "reference"


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    read: Callable  # read(run) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cell: dict  # bench/cells/<name>.json
    config: dict  # bench/configs/<config>.json
    traffic: dict  # bench/traffic/<mix>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(path: Path) -> ModuleType:
    """The Python file at ``path``, loaded once per process."""
    name = "bench_file" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # so that a dataclass in the file finds its module
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Callable:
    """``bench/metrics/<name>.py`` defines ``read(run)``."""
    return load_module(BENCH / "metrics" / f"{name}.py").read


def load_reference(name: str) -> ModuleType:
    """``bench/reference/<name>.py``, the plain reference a configuration
    names under ``"reference"``.  It defines ``hidden(params, tokens, config,
    *, fp8)`` and ``logits_rows(params, x, rows, config, *, fp8)`` as
    ``decoder.py`` does."""
    path = REFERENCES / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: reference {name!r} has no file {path}")
    return load_module(path)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str) -> Cell:
    bench = _json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = _json(BENCH / "cells" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(
                f"bench: {name}: {key} is {cell[key]!r} in its cell file, "
                f"{entry[key]!r} in BENCHMARK.json"
            )
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    # a per-layer metric without a workloads key goes to every cell that
    # reports the end-to-end metric it moves
    layer = [
        m for m in bench["per_layer"]
        if m["moves"] in moved and _applies(m, name)
    ]

    def metric(m):
        return Metric(m["name"], m["unit"], m["better"], m["source"], load_reader(m["name"]))

    return Cell(
        name=name,
        chips=int(entry["chips"]),
        cell=cell,
        config=_json(BENCH / "configs" / f"{cell['config']}.json"),
        traffic=_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        end_to_end=[metric(m) for m in e2e],
        per_layer=[metric(m) for m in layer],
    )


def peaks_for(kind: str) -> Dict[str, float]:
    """The peak row of ``device_kind``; a device missing from the table is an error."""
    table = _json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(
            f"bench: device kind {kind!r} is not in bench/peaks.json "
            f"(known: {sorted(table)}); refusing to report against a guessed peak"
        )
    return table[kind]
