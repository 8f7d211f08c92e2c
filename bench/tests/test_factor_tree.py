"""The factor tree made from the seed has the leaves, shapes and dtypes that
``compress_tree`` gives the same model at the configuration's policy."""

import jax
import pytest

from bench.harness import weights
from bench.tests.tiny import TINY_CONFIG, on_cpu


def test_factor_tree_matches_compress_tree():
    config = TINY_CONFIG
    from repro.core import CompressionPolicy, compress_tree
    from repro.models.model import build_model

    with on_cpu("open"):
        cfg = weights.arch_config(config)
    model = build_model(cfg)
    comp = config["compression"]
    made = weights.make_params(model, comp, jax.random.PRNGKey(0))
    dense = jax.jit(model.init)(jax.random.PRNGKey(1))
    policy = CompressionPolicy(alpha=comp["alpha"], q=comp["q"], min_dim=comp["min_dim"],
                               exclude=comp["exclude"], break_even_only=comp["break_even_only"])
    want, _, report = compress_tree(dense, policy, jax.random.PRNGKey(2))
    assert any(l.compressed for l in report.layers)
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)  # noqa: E731
    assert jax.tree_util.tree_structure(made) == jax.tree_util.tree_structure(want)
    assert shape(made) == shape(want)


def test_registry_mismatch_stops_the_run():
    bad = dict(TINY_CONFIG, hidden_size=64)
    with on_cpu("open"), pytest.raises(SystemExit):
        weights.arch_config(bad)
