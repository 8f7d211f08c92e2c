"""The trace reducer on a small trace recorded on a v5e chip
(``record_trace.py``): a serving stretch of minitron-4b cut to 2 layers."""

import gzip
import json
import shutil
import types
from pathlib import Path

from unittest import mock

import pytest

from bench.harness import spec, trace
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


def _pinned_run(tmp_path):
    """The recorded trace with a fixed record of 16 traced engine steps, the
    minitron4b-rsi configuration cut to the trace's 2 layers."""
    from bench.cost.model import per_token
    from bench.harness import weights
    from bench.harness.serve_loop import Step
    from repro.models.model import build_model

    facts = json.loads((DATA / "serve.json").read_text())
    steps = [Step(t0=0.25 * i, t1=0.25 * i + 0.125 + 0.001 * i, emitted=3,
                  decode_ctx=300 + 37 * i, decode_tokens=3 if i % 4 else 0,
                  prefill_done=[256] if i % 4 == 0 else [], traced=True) for i in range(16)]
    config = dict(json.loads((spec.BENCH / "configs" / "minitron4b-rsi.json").read_text()),
                  num_hidden_layers=facts["layers"])
    src = DATA / "serve.xplane.pb.gz"
    dst = tmp_path / "serve.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    t = trace.reduce_file(str(dst), facts["counters"], steps, pool_shapes=facts["pool_shapes"])
    leaves, _ = weights.served_shapes(build_model(weights.arch_config(config)),
                                      config["compression"])
    return types.SimpleNamespace(trace=t, peaks=spec.peaks_for("TPU v5 lite"), config=config,
                                 steps=steps, cost=per_token(leaves, config))


def test_rooflines_and_mfu_read_what_they_read_before(tmp_path):
    """The kernels found by name and the model cost give, bit for bit, what
    the harness gave with the two kernels written into it (commit a503d8b)."""
    run = _pinned_run(tmp_path)
    assert run.cost == (191389696, 477728768, 24576)
    assert (len(run.trace.kernels["lowrank"]), len(run.trace.kernels["paged_decode"])) == (200, 32)
    assert spec.load_reader("roofline_pct.lowrank_matmul")(run) == 71.64661323573532
    assert spec.load_reader("roofline_pct.paged_decode_attention")(run) == 7.231547762644412
    assert spec.load_reader("mfu.serve")(run) == 0.05398025071927978


def test_a_kernel_is_found_by_its_cost_file(tmp_path):
    """A kernel's pattern and least time come from ``<kernel>.py`` in the
    kernel cost directory: a stand-in there is found, and no other."""
    with mock.patch.object(trace, "KERNEL_COSTS", DATA / "kernels"):
        run = _pinned_run(tmp_path)
        calls = run.trace.kernels["stub_decode"]
        assert set(run.trace.kernels) == {"stub_decode"} and len(calls) == 32
        dev_s = sum(o.dur_ns + o.feed_ns for o in calls) / 1e9
        assert trace.kernel_roofline(run, "stub_decode") == 100.0 * 32e-6 / dev_s
