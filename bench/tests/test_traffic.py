"""The generator repeats per seed, and every seed gets the same work."""

import numpy as np

from bench.harness import traffic as gen
from bench.tests.tiny import MIXES


def _key(items):
    return [(it.prompt.tolist(), it.max_new, it.due, it.group) for it in items]


def test_open_loop_repeats_per_seed():
    a = gen.open_loop(MIXES["open"], 12.0, 5.0, 512, 2**31 + 7)
    b = gen.open_loop(MIXES["open"], 12.0, 5.0, 512, 2**31 + 7)
    assert _key(a) == _key(b)


def test_open_loop_same_work_other_order():
    a = gen.open_loop(MIXES["open"], 12.0, 5.0, 512, 1)
    b = gen.open_loop(MIXES["open"], 12.0, 5.0, 512, 2)
    assert len(a) == len(b) == 60
    assert sorted(it.max_new for it in a) == sorted(it.max_new for it in b)
    assert sorted(it.prompt.size for it in a) == sorted(it.prompt.size for it in b)
    # the same gaps, in another order: all but one of them are between dues
    ga, gb = sorted(np.diff([it.due for it in a])), sorted(np.diff([it.due for it in b]))
    assert sum(not np.isclose(x, y) for x, y in zip(ga, gb)) <= len(ga) // 2
    assert _key(a) != _key(b)
    assert all(0.0 <= it.due < 5.0 for it in a)
    assert [it.due for it in a] == sorted(it.due for it in a)


def test_open_loop_system_prompts_are_shared():
    items = gen.open_loop(MIXES["open"], 12.0, 5.0, 512, 3)
    sp = MIXES["open"]["system_prompts"]
    tenants = [it for it in items if it.group >= 0]
    assert len(tenants) == 30
    heads = {it.group: it.prompt[: sp["length"]].tolist() for it in tenants}
    for it in tenants:
        assert it.prompt[: sp["length"]].tolist() == heads[it.group]


def test_sizes_follow_the_distribution():
    s = gen.sizes({"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 16, "max": 512}, 1001)
    assert s[500] == 128 and s.min() >= 16 and s.max() <= 512
    u = gen.sizes({"dist": "uniform", "min": 64, "max": 512}, 449)
    assert u.min() == 64 and u.max() == 512


def test_min_prompt_counts_system_prompt_only_when_every_request_has_one():
    mix = MIXES["open"]
    assert gen.min_prompt(mix) == mix["prompt"]["min"]
    every = dict(mix, system_prompts=dict(mix["system_prompts"], share=1.0))
    assert gen.min_prompt(every) == mix["prompt"]["min"] + mix["system_prompts"]["length"]
    items = gen.open_loop(mix, 12.0, 5.0, 512, 4)
    assert min(it.prompt.size for it in items) < mix["system_prompts"]["length"]


def test_schedule_seed_fixes_sizes_and_arrivals_not_tokens():
    mix = dict(MIXES["open"], schedule_seed=0)
    a = gen.open_loop(mix, 12.0, 5.0, 512, 1)
    b = gen.open_loop(mix, 12.0, 5.0, 512, 2**31 + 2)
    assert [(it.prompt.size, it.max_new, it.due, it.group) for it in a] == \
        [(it.prompt.size, it.max_new, it.due, it.group) for it in b]
    assert _key(a) != _key(b)
