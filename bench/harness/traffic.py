"""The one generator of request traffic; a mix file under ``bench/traffic``
holds only its parameters.

Every seed gets the same multiset of sizes and gaps, drawn at evenly spaced
quantiles of the mix's distributions, and only their order and the token
ids change with the seed.  So two seeds do the same amount of work.  Where
the order itself moves a metric (two long prompts due close together queue
behind each other), a mix fixes it with ``schedule_seed``: every seed then
runs one schedule of sizes, system prompts and due times, and draws only
the token ids, so a difference between runs is noise, not a different load.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

from bench.harness import seeds


@dataclasses.dataclass
class Item:
    prompt: np.ndarray  # int32 token ids
    max_new: int
    due: float = 0.0  # seconds after the window opens (open loop)
    group: int = -1  # system prompt group, -1 for none


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` sizes at evenly spaced quantiles of ``dist``, ascending."""
    u = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = lo + u * (hi - lo + 1)
    else:
        raise ValueError(f"unknown size distribution {dist['dist']!r}")
    return np.clip(np.rint(v) if dist["dist"] == "lognormal" else np.floor(v), lo, hi).astype(np.int64)


def preamble_len(mix: dict) -> int:
    sp = mix.get("system_prompts")
    return int(sp["length"]) if sp else 0


def min_prompt(mix: dict) -> int:
    sp = mix.get("system_prompts")
    every = sp is not None and float(sp["share"]) >= 1.0
    return int(mix["prompt"]["min"]) + (preamble_len(mix) if every else 0)


def max_prompt(mix: dict) -> int:
    return int(mix["prompt"]["max"]) + preamble_len(mix)


def max_len(mix: dict) -> int:
    return max_prompt(mix) + int(mix["output"]["max"])


def _shapes(mix: dict, n: int):
    """(own prompt size, system prompt group, output size) of ``n`` requests:
    the same ``n`` triples for every seed.  Sizes sit at evenly spaced
    quantiles; a fixed shuffle, not the seed, pairs them, so a request's
    prompt and output lengths are independent."""
    fixed = np.random.default_rng(0)
    own = sizes(mix["prompt"], n)
    out = fixed.permutation(sizes(mix["output"], n))
    groups = np.full(n, -1)
    sp = mix.get("system_prompts")
    if sp:
        k = int(round(sp["share"] * n))
        groups[:k] = np.arange(k) % int(sp["groups"])
        groups = fixed.permutation(groups)
    return own, groups, out


def _requests(mix: dict, n: int, vocab: int, seed: int, order_rng) -> List[tuple]:
    """(tokens, group, max_new) of ``n`` requests, in the seed's order."""
    tok = seeds.rng(seed, seeds.TOKENS)
    own, groups, outs = _shapes(mix, n)
    perm = order_rng.permutation(n)
    sp = mix.get("system_prompts")
    if sp:
        system = [tok.integers(0, vocab, int(sp["length"])) for _ in range(int(sp["groups"]))]
    out = []
    for m, g, o in zip(own[perm], groups[perm], outs[perm]):
        body = tok.integers(0, vocab, int(m))
        if g >= 0:  # one of the shared system prompts
            p = np.concatenate([system[g], body])
        else:
            p = body
        out.append((p.astype(np.int32), int(g), int(o)))
    return out


def open_loop(mix: dict, rate: float, seconds: float, vocab: int, seed: int) -> List[Item]:
    """Requests due at a Poisson rate over ``[0, seconds)``, in due order."""
    n = max(1, int(round(rate * seconds)))
    if "schedule_seed" in mix:
        order = np.random.default_rng(int(mix["schedule_seed"]))
    else:
        order = seeds.rng(seed, seeds.TRAFFIC)
    gaps = order.permutation(-np.log1p(-_quantiles(n)))
    due = (np.cumsum(gaps) - gaps[0]) / rate
    return [
        Item(prompt=p, max_new=o, due=float(t), group=g)
        for (p, g, o), t in zip(_requests(mix, n, vocab, seed, order), due)
    ]
