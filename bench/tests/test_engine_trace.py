"""The readers of the engine's own record: ``engine_trace`` on a small chip
trace with engine spans (``record_engine_trace.py``) and on the older one
without (``record_trace.py``), and the four metrics it feeds on synthetic
runs."""

import gzip
import json
import math
import shutil
import types
from pathlib import Path

import pytest

from bench.harness import engine_trace, spec, trace

DATA = Path(__file__).resolve().parent / "data"
ENGINE_SPANS = {"engine.step", "engine.admit", "engine.prefill_group", "engine.page_scatter",
                "engine.sample", "engine.chunk", "engine.cow_fork", "engine.decode_block",
                "engine.decode_sync", "engine.finish", "engine.rematerialize"}


def _unpack(name, dst: Path) -> Path:
    src = DATA / f"{name}.xplane.pb.gz"
    if not src.exists():
        pytest.fail(f"missing recorded trace {src}")
    dst.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return dst


def _facts(name):
    return json.loads((DATA / f"{name}.json").read_text())


def _cell_run(tmp_path, monkeypatch, name):
    """A traced run of cell ``c`` whose profile is the recorded ``name``."""
    path = _unpack(name, tmp_path / ".bench_trace" / "c" / "plugins" / "profile" / "1" /
                   "host.xplane.pb")
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    facts = _facts(name)
    t = trace.reduce_file(str(path), facts["counters"], [], pool_shapes=facts["pool_shapes"])
    return types.SimpleNamespace(trace=t, cell=types.SimpleNamespace(name="c", chips=1))


def test_recorded_engine_spans(tmp_path):
    facts = _facts("serve_engine")
    path = _unpack("serve_engine", tmp_path / "t.xplane.pb")
    et = engine_trace.load(str(path))
    assert len(et.modules) == 1 and et.window_s > 0
    assert len(et.steps()) == facts["engine_steps"]
    assert {n for n, _, _ in et.spans} <= ENGINE_SPANS
    assert {"engine.admit", "engine.chunk", "engine.prefill_group", "engine.page_scatter",
            "engine.decode_block", "engine.decode_sync"} <= {n for n, _, _ in et.spans}

    t = trace.reduce_file(str(path), facts["counters"], [], pool_shapes=facts["pool_shapes"])
    # idle between programs is part of the idle between ops
    idle = et.idle_in_steps_s()
    assert 0 < idle <= t.window_s - t.busy_s
    # the midpoint rule puts every gap between programs somewhere
    chip = next(iter(et.modules))
    gaps = sum(b - a for a, b in et.gaps(chip)) / 1e9
    by_span = et.idle_by_span()
    assert math.isclose(sum(by_span.values()), gaps, rel_tol=1e-9)
    assert set(by_span) <= ENGINE_SPANS | {"outside engine.step"}
    # grouped prefill, its scatter and the first-token argmax run besides
    # the decode block and the chunk program
    assert et.module_s() - t.program_s["decode"] - t.program_s["chunk"] > 0


def test_grouped_prefill_is_not_taken_for_the_chunk_program(tmp_path):
    """The chunk program is told apart by the page pool in its ops; the
    grouped prefill and the first-token argmax carry no pool shape, so under
    any module name they never read as the chunk program."""
    facts = _facts("serve_engine")
    path = _unpack("serve_engine", tmp_path / "t.xplane.pb")
    ops, mods, _ = trace.load(str(path), 1)
    (chip,) = ops
    trace._assign_modules(ops[chip], mods[chip])
    texts = {}
    for o in ops[chip]:
        texts.setdefault(o.module, []).append(o.text)
    named = {m: t for m, t in texts.items()
             if m.startswith(("jit_grouped_prefill(", "jit_first_token("))}
    assert {m.split("(")[0] for m in named} == {"jit_grouped_prefill", "jit_first_token"}
    for m, t in named.items():
        as_lambda = "jit__lambda(" + m.split("(", 1)[1]
        assert trace.classify(as_lambda, t, facts["pool_shapes"]) is None, m
    chunk = [m for m, t in texts.items() if trace.classify(m, t, facts["pool_shapes"]) == "chunk"]
    assert chunk and all(m.startswith("jit__lambda(") for m in chunk)


def test_trace_without_engine_spans_reads_nothing(tmp_path, monkeypatch):
    path = _unpack("serve", tmp_path / "t.xplane.pb")
    et = engine_trace.load(str(path))
    assert et.modules and et.steps() == [] and et.spans == []
    run = _cell_run(tmp_path, monkeypatch, "serve")
    for name in ("engine_idle_pct.serve", "side_programs_ms"):
        assert spec.load_reader(name)(run) is None


def test_device_trace_readers_on_the_recorded_run(tmp_path, monkeypatch):
    run = _cell_run(tmp_path, monkeypatch, "serve_engine")
    calls = []
    load = engine_trace.load
    monkeypatch.setattr(engine_trace, "load", lambda *a: calls.append(a) or load(*a))
    idle = spec.load_reader("engine_idle_pct.serve")(run)
    side = spec.load_reader("side_programs_ms")(run)
    device_idle = spec.load_reader("device_idle_pct.serve")(run)
    assert len(calls) == 1  # one parse for both readers
    assert 0 < idle <= device_idle
    assert side > 0 and math.isfinite(side)
    assert spec.load_reader("engine_idle_pct.serve")(types.SimpleNamespace(trace=None)) is None


def test_innermost_span_and_idle_in_steps():
    et = engine_trace.EngineTrace(
        lo=0, hi=40, modules={"/device:TPU:0": [(0, 10), (20, 30)]},
        spans=[("engine.step", 5, 36), ("engine.decode_block", 8, 32),
               ("engine.decode_sync", 12, 18)])
    assert et.gaps("/device:TPU:0") == [(10, 20), (30, 40)]
    assert et.idle_in_steps_s() == pytest.approx(16e-9)  # 10 in the block, 6 after it
    assert et.idle_by_span() == pytest.approx({"engine.decode_sync": 10e-9,
                                               "engine.step": 10e-9})
    et.spans = [("engine.step", 5, 20)]
    assert et.idle_by_span() == pytest.approx({"engine.step": 10e-9,
                                               "outside engine.step": 10e-9})
    assert engine_trace._overlap([(0, 2), (4, 8)], [(1, 5), (7, 9)]) == 3


# --------------------------------------------------------------------------- #
# request timestamps
# --------------------------------------------------------------------------- #
def _rec(t_submit, t_admit=0.0, t_first=0.0):
    return types.SimpleNamespace(req=types.SimpleNamespace(
        t_submit=t_submit, t_admit=t_admit, t_first=t_first))


def _step(t0, t1, traced):
    return types.SimpleNamespace(t0=t0, t1=t1, traced=traced)


def test_wait_readers_leave_out_requests_not_admitted():
    recs = [_rec(1.0, 1.010, 2.0), _rec(1.5, 1.530, 4.5), _rec(2.0, 2.001, 2.5),
            _rec(3.0)]  # the last never got a slot
    run = types.SimpleNamespace(records=recs, steps=[])
    assert spec.load_reader("queue_wait_p95_ms")(run) == pytest.approx(30.0)
    assert spec.load_reader("prefill_wait_p95_ms")(run) == pytest.approx(2970.0)
    run.records = [_rec(3.0)]
    assert spec.load_reader("queue_wait_p95_ms")(run) is None
    assert spec.load_reader("prefill_wait_p95_ms")(run) is None


def test_wait_readers_on_a_program_without_admission_times():
    req = types.SimpleNamespace(t_submit=1.0, t_first=2.0)  # no t_admit
    run = types.SimpleNamespace(records=[types.SimpleNamespace(req=req)], steps=[])
    assert spec.load_reader("queue_wait_p95_ms")(run) is None
    assert spec.load_reader("prefill_wait_p95_ms")(run) is None


def test_wait_readers_leave_out_the_profiler_hold():
    # the harness held its loop from 10 to 25 s to start the profiler, and
    # from 30 to 44 s to stop it
    steps = [_step(9.0, 10.0, False), _step(25.0, 26.0, True), _step(29.0, 30.0, True),
             _step(44.0, 45.0, False)]
    assert engine_trace.held(types.SimpleNamespace(steps=steps)) == [(10.0, 25.0), (30.0, 44.0)]
    run = types.SimpleNamespace(records=[_rec(9.5, 25.5, 45.0)], steps=steps)
    assert spec.load_reader("queue_wait_p95_ms")(run) == pytest.approx(1000.0)
    assert spec.load_reader("prefill_wait_p95_ms")(run) == pytest.approx(5500.0)
    assert engine_trace.held(types.SimpleNamespace(steps=steps[:1])) == []
