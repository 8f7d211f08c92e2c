"""The engine's own trace: request timestamps and ``engine.*`` profiler spans.

Every request records when it was submitted, placed in a slot, got its
first token and finished (``t_submit <= t_admit <= t_first <= t_done``),
whichever prefill path served it.  Under ``jax.profiler.trace`` each engine
step writes host spans that read back from the profile, nested as the
engine module's docstring lists them; with the profiler on or off the
tokens are the same.
"""

import glob

import jax
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.models.model import build_model
from repro.serving import Engine, Request


@pytest.fixture(scope="module")
def lm():
    cfg = get_arch("llama3.2-1b", reduced=True)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)


def _drain(eng):
    while eng.has_work:
        eng.step()


def _ordered(r):
    assert r.status == "ok"
    assert 0 < r.t_submit <= r.t_admit <= r.t_first <= r.t_done


def _grouped(lm):
    cfg, model, params = lm
    rng = np.random.default_rng(0)
    eng = Engine(model, params, n_slots=2, max_len=32, page_size=4, prefill_chunk=8)
    reqs = [eng.submit(Request(prompt=_prompt(rng, cfg, n), max_new_tokens=4)) for n in (5, 7)]
    _drain(eng)
    assert eng.prefill_chunks == 0
    return reqs


def _chunked(lm):
    cfg, model, params = lm
    rng = np.random.default_rng(1)
    eng = Engine(model, params, n_slots=2, max_len=32, page_size=4, prefill_chunk=4)
    reqs = [eng.submit(Request(prompt=_prompt(rng, cfg, 13), max_new_tokens=4))]
    _drain(eng)
    assert eng.prefill_chunks == 4
    return reqs


def _shared_cow(lm):
    cfg, model, params = lm
    prompt = _prompt(np.random.default_rng(7), cfg, 8)  # two whole pages
    eng = Engine(model, params, n_slots=2, max_len=16, page_size=4,
                 share_prefix=True, decode_block=1)
    r0 = eng.submit(Request(prompt=prompt, max_new_tokens=6))
    eng.step()
    eng.step()
    r1 = eng.submit(Request(prompt=prompt, max_new_tokens=5))
    _drain(eng)
    assert eng.cow_forks == 1 and r1.prefill_skipped > 0
    return [r0, r1]


def _preempted(lm):
    cfg, model, params = lm
    rng = np.random.default_rng(12)
    # r0 holds all 5 pages, so admitting r1 needs r0 preempted
    eng = Engine(model, params, n_slots=2, max_len=32, page_size=4, kv_pages=5,
                 share_prefix=True, preempt=True, decode_block=2)
    r0 = eng.submit(Request(prompt=_prompt(rng, cfg, 8), max_new_tokens=10, priority=0))
    eng.step()
    eng.step()
    t_admit, t_first = r0.t_admit, r0.t_first
    r1 = eng.submit(Request(prompt=_prompt(rng, cfg, 8), max_new_tokens=6, priority=1))
    _drain(eng)
    assert eng.preemptions == 1 and len(r0.tokens) == 10
    # the resumed continuation was admitted again later; the client's
    # request keeps its first admission and first token
    assert (r0.t_admit, r0.t_first) == (t_admit, t_first)
    assert r0.t_admit < r1.t_admit
    return [r0, r1]


@pytest.mark.parametrize("path", [_grouped, _chunked, _shared_cow, _preempted],
                         ids=["grouped", "chunked", "shared_cow", "preempted"])
def test_request_timestamps_ordered(lm, path):
    for r in path(lm):
        _ordered(r)


def test_queued_request_is_admitted_when_a_slot_frees(lm):
    cfg, model, params = lm
    rng = np.random.default_rng(3)
    eng = Engine(model, params, n_slots=1, max_len=32, page_size=4, decode_block=2)
    r0 = eng.submit(Request(prompt=_prompt(rng, cfg, 5), max_new_tokens=3))
    r1 = eng.submit(Request(prompt=_prompt(rng, cfg, 5), max_new_tokens=3))
    eng.step()
    assert r0.t_admit > 0 and r1.t_admit == 0.0  # waiting for the one slot
    _drain(eng)
    for r in (r0, r1):
        _ordered(r)
    assert r1.t_admit >= r0.t_done


# --------------------------------------------------------------------------- #
# profiler spans
# --------------------------------------------------------------------------- #
def _serve_chunked_and_short(lm):
    """One prompt longer than the chunk and one short one; returns the
    engine and both requests."""
    cfg, model, params = lm
    rng = np.random.default_rng(5)
    eng = Engine(model, params, n_slots=2, max_len=32, page_size=4, prefill_chunk=4,
                 decode_block=2)
    long = eng.submit(Request(prompt=_prompt(rng, cfg, 10), max_new_tokens=5))
    short = eng.submit(Request(prompt=_prompt(rng, cfg, 3), max_new_tokens=5))
    _drain(eng)
    return eng, long, short


def _spans(trace_dir):
    """``engine.*`` host events of the profile: (line, name, start, end, stats)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((line.name, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _within(child, parent):
    return child[0] == parent[0] and parent[2] <= child[2] and child[3] <= parent[3]


def _children(spans, parent, name):
    return [s for s in spans if s[1] == name and _within(s, parent)]


def test_engine_spans_read_back_from_the_profile(lm, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        eng, long, short = _serve_chunked_and_short(lm)
    spans = _spans(tmp_path)
    steps = [s for s in spans if s[1] == "engine.step"]
    assert len(steps) == eng._step_idx
    assert sorted(s[4]["step"] for s in steps) == list(range(1, eng._step_idx + 1))
    # every other span sits inside one engine step
    for s in spans:
        if s[1] != "engine.step":
            assert sum(_within(s, st) for st in steps) == 1, s
    assert all(_children(spans, st, "engine.admit") for st in steps)

    chunks = [s for s in spans if s[1] == "engine.chunk"]
    assert len(chunks) == eng.prefill_chunks == 3
    assert {c[4]["uid"] for c in chunks} == {long.uid}
    assert sorted(c[4]["start"] for c in chunks) == [0, 4, 8]
    assert sorted(c[4]["n"] for c in chunks) == [2, 4, 4]

    groups = [s for s in spans if s[1] == "engine.prefill_group"]
    assert len(groups) == 1
    assert str(groups[0][4]["uids"]) == str(short.uid)
    assert len(_children(spans, groups[0], "engine.page_scatter")) == 1
    assert len(_children(spans, groups[0], "engine.sample")) == 1

    blocks = [s for s in spans if s[1] == "engine.decode_block"]
    assert len(blocks) == eng.host_syncs > 0
    assert all(len(_children(spans, b, "engine.decode_sync")) == 1 for b in blocks)

    finishes = [s for s in spans if s[1] == "engine.finish"]
    assert sorted(f[4]["uid"] for f in finishes) == sorted([long.uid, short.uid])


def test_tokens_unchanged_with_the_profiler_on(lm, tmp_path):
    _, long_off, short_off = _serve_chunked_and_short(lm)
    with jax.profiler.trace(str(tmp_path)):
        _, long_on, short_on = _serve_chunked_and_short(lm)
    assert long_on.tokens == long_off.tokens and len(long_on.tokens) == 5
    assert short_on.tokens == short_off.tokens and len(short_on.tokens) == 5


def test_engine_programs_have_stable_names(lm):
    """Profiles name a program execution ``jit_<function>``."""
    cfg, model, params = lm
    eng = Engine(model, params, n_slots=2, max_len=32, page_size=4, prefill_chunk=4)
    assert eng._prefill_jit.__name__ == "grouped_prefill"
    assert eng._argmax_jit.__name__ == "first_token"
    assert eng._fused_fn(True).__name__ == "fused"
