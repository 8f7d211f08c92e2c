"""The trace reducer on a small trace recorded on a v5e chip
(``record_trace.py``): a serving stretch of minitron-4b cut to 2 layers."""

import gzip
import json
import shutil
import types
from pathlib import Path

import pytest

from bench.harness import trace
from bench.tests.tiny import PEAKS

DATA = Path(__file__).resolve().parent / "data"


def _reduce(name, tmp_path, **kw):
    src = DATA / f"{name}.xplane.pb.gz"
    if not src.exists():
        pytest.fail(f"missing recorded trace {src}")
    dst = tmp_path / f"{name}.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    facts = json.loads((DATA / f"{name}.json").read_text())
    return trace.reduce_file(str(dst), facts.get("counters", {}), [], **kw), facts


def test_serving_trace(tmp_path):
    facts = json.loads((DATA / "serve.json").read_text())
    t, _ = _reduce("serve", tmp_path, pool_shapes=facts["pool_shapes"])
    assert 0 < t.busy_s <= t.window_s
    assert t.program_s.get("decode", 0) > 0
    assert t.kernels["lowrank"] and t.kernels["paged_decode"]
    # a lowrank call's operands parse as x (M, K), A (K, r), B (r, N)
    x, a, b = trace._shapes(t.kernels["lowrank"][0].text)[1:4]
    assert x[-1] == a[-2] and a[-1] == b[-2]
    assert len(t.breakdown["device_ops"]) == 10 and len(t.breakdown["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in t.breakdown["device_ops"])
    assert sum(s for _, s in t.breakdown["device_ops"]) <= t.busy_s * 1.0001
    run = types.SimpleNamespace(trace=t, peaks=PEAKS, config={})
    share = trace.kernel_roofline(run, "lowrank")
    assert 0 < share <= 100


def test_serving_trace_finds_the_chunk_program(tmp_path):
    facts = json.loads((DATA / "serve.json").read_text())
    assert facts["counters"]["prefill_chunks"] > 0
    t, _ = _reduce("serve", tmp_path, pool_shapes=facts["pool_shapes"])
    assert t.program_s.get("chunk", 0) > 0
    t2, _ = _reduce("serve", tmp_path, pool_shapes=[])
    assert "chunk" not in t2.program_s


def test_union_and_gaps():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace._gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_self_times():
    ops = [trace.Op("%while.1 = x", 0, 10), trace.Op("%a = y", 1, 3), trace.Op("%b = z", 5, 2)]
    trace._self_times(ops)
    assert [o.self_ns for o in ops] == [5, 3, 2]


def test_feed_op_charged_once():
    call = ("%lowrank_matmul_pallas.{i} = bf16[8,4]{{1,0}} custom-call(bf16[8,4]{{1,0}} %x, "
            "bf16[4,2]{{1,0}} %dynamic-slice_bitcast_fusion.7, bf16[2,4]{{1,0}} %b), "
            'custom_call_target="tpu_custom_call"')
    feed = trace.Op("%dynamic-slice_bitcast_fusion.7 = bf16[4,2]{1,0} fusion()", 0, 5)
    k1 = trace.Op(call.format(i=1), 10, 2)
    k2 = trace.Op(call.format(i=2), 20, 2)
    trace._feeds([k1, k2], [feed, k1, k2])
    assert (k1.feed_ns, k2.feed_ns) == (5, 0)
