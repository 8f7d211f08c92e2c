"""Streams of randomness from ``--seed``, which may exceed 32 bits."""

from __future__ import annotations

import numpy as np

# one stream per use, so adding a use never shifts another's draws
TRAFFIC, TOKENS, WEIGHTS, SAMPLE = range(4)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) % 2**63, stream]))


def jax_key(seed: int, stream: int):
    import jax

    seed = int(seed) % 2**63
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)
