"""BENCHMARK.json against the contract's shape rules, and every cell, mix,
configuration and metric it names found by name under ``bench/``."""

import json
import re

from bench.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"] and BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_units_and_lines():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e and isinstance(e[k], str):
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")


def test_every_cell_loads_and_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    used_configs = set()
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        used_configs.add(w["config"])
        reported = {m.name for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m.read is not None
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or w in moved["workloads"], (m["name"], w)
    assert used_configs == {c["name"] for c in BENCH["configs"]}


def test_config_files_state_their_cut():
    for c in BENCH["configs"]:
        f = json.loads((spec.ROOT / c["file"]).read_text())
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        for k in ("assumed", "departures", "deployment"):
            assert f[k]
        for k in c["reduced"]:
            assert k in f["source_values"] and f["source_values"][k] != f[k]
            assert not k.endswith(("_dim", "_rank", "_size")), k
            assert k not in ("intermediate_size", "num_experts_per_tok", "num_attention_heads",
                             "num_key_value_heads", "head_dim", "hidden_size"), k
