"""What one cell is made of, found by name: the cell file, its configuration,
its traffic mix and the metrics ``BENCHMARK.json`` gives it.

Every piece lives in a file of its own under ``bench/``, so a new cell, mix,
configuration or per-layer metric is a new file and a new entry in
``BENCHMARK.json``, never an edit of this code.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


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


def load_reader(name: str) -> Callable:
    """``bench/metrics/<name>.py`` defines ``read(run)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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
