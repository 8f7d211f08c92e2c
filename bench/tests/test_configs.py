"""A configuration goes through the harness as a file: its published keys
checked against the registry, its cuts applied, its own ``arch_fields``
honoured, and a family the harness does not know refused."""

import dataclasses
import json
from unittest import mock

import jax
import pytest

from bench.cost.model import per_token
from bench.harness import spec, weights
from repro.configs.registry import get_arch
from repro.models.model import build_model

DATA = spec.BENCH / "tests" / "data"


def _config(name):
    return json.loads((DATA / f"{name}.json").read_text())


def _reduced(arch_id):
    return get_arch(arch_id, reduced=True)


def test_minitron_file_builds_the_registry_config():
    config = json.loads((spec.BENCH / "configs" / "minitron4b-rsi.json").read_text())
    assert weights.arch_config(config) == dataclasses.replace(get_arch("minitron-4b"), n_layers=32)


# the file as it stands, cut in depth; and with a key it names in
# ``arch_fields`` cut too: a router over 4 experts
CUTS = {
    "depth": {},
    "arch_fields": {"n_routed_experts": 4, "arch_fields": {"n_routed_experts": "n_experts"},
                    "reduced": ["num_hidden_layers", "n_routed_experts"],
                    "source_values": {"num_hidden_layers": 3, "n_routed_experts": 8}},
}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_deepseek_goes_through_with_no_harness_edit(cut):
    """The registry's reduced deepseek-v2-236b (MLA, 1 dense and 2 MoE
    layers, 8 routed experts, 2 per token, 1 shared), cut in depth to 1 + 1;
    the file has no ``head_dim``."""
    config = dict(_config("deepseek-v2-tiny"), **CUTS[cut])
    E = config["n_routed_experts"]
    assert "head_dim" not in config
    with mock.patch.object(weights, "get_arch", _reduced):
        cfg = weights.arch_config(config)
    assert (cfg.n_layers, cfg.first_dense_layers, cfg.n_experts, cfg.top_k) == (2, 1, E, 2)
    assert (cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.dense_d_ff, cfg.moe_d_ff) == (16, 8, 128, 32)
    model = build_model(cfg)
    comp = config["compression"]
    leaves, _ = weights.served_shapes(model, comp)
    shapes = {name: (shape, rank) for name, shape, _d, rank in leaves}
    assert shapes["layers/moe/experts/w_up"] == ((1, E, 64, 32), 10)
    assert shapes["dense_layers/mlp/w_up"] == ((1, 64, 128), 20)
    params = weights.make_params(model, comp, jax.random.PRNGKey(0))
    assert params["layers"]["moe"]["experts"]["w_up"]["a"].shape == (1, E, 64, 10)
    assert params["dense_layers"]["attn"]["wkv_a"]["b"].shape == (1, 8, 24)

    trunk, head, attn = per_token(leaves, config, route=(cfg.top_k, cfg.n_experts))
    routed = [x for x in leaves if "/experts/" in x[0]]
    rest, _, _ = per_token([x for x in leaves if x not in routed], config)
    # three routed leaves of E experts, each 2*10*(64+32) a token; the
    # built router sends a token to 2 of its E, so 2 experts' work
    assert trunk - rest == 3 * 2 * 2 * 10 * (64 + 32)
    assert head == 2 * 20 * (64 + 256)
    # absorbed latent attention over 2 layers of 4 heads: 2 L H (2 kv_lora + rope)
    assert attn == 2 * 2 * 4 * (2 * 16 + 8)


def test_uncut_key_must_match_the_registry():
    config = dict(_config("deepseek-v2-tiny"), n_routed_experts=4)
    with mock.patch.object(weights, "get_arch", _reduced), \
            pytest.raises(SystemExit, match="n_routed_experts=4"):
        weights.arch_config(config)


def test_only_depth_and_arch_fields_keys_are_cut():
    """A key of the harness's own tables in ``reduced`` (a width, the
    router's width) stops the run: it is cut only where the file names
    it in ``arch_fields``."""
    config = _config("deepseek-v2-tiny")
    for key, value in (("n_routed_experts", 4), ("hidden_size", 32), ("kv_lora_rank", 8)):
        cut = dict(config, reduced=config["reduced"] + [key], **{key: value})
        with mock.patch.object(weights, "get_arch", _reduced), \
                pytest.raises(SystemExit, match=f"reduced lists {key}"):
            weights.arch_config(cut)


def test_unknown_family_stops_the_run():
    config = dict(_config("deepseek-v2-tiny"), arch="mamba2-130m")
    with mock.patch.object(weights, "get_arch", _reduced), \
            pytest.raises(SystemExit, match="'ssm'"):
        weights.arch_config(config)


def test_arch_fields_name_the_field_a_key_sets():
    config = dict(_config("deepseek-v2-tiny"), arch_fields={"capacity": "capacity_factor"},
                  capacity=1.25)
    with mock.patch.object(weights, "get_arch", _reduced):
        assert weights.arch_config(config).capacity_factor == 1.25
        cut = dict(config, capacity=2.0, reduced=config["reduced"] + ["capacity"])
        assert weights.arch_config(cut).capacity_factor == 2.0
        with pytest.raises(SystemExit, match="capacity=2.0"):
            weights.arch_config(dict(config, capacity=2.0))
        # a file's own mapping takes precedence over the family's table
        phi_style = dict(config, arch_fields={"intermediate_size": "moe_d_ff"},
                         intermediate_size=32)
        assert weights.arch_config(phi_style).moe_d_ff == 32
        bad = dict(config, arch_fields={"experts_held": "n_experts_held"}, experts_held=16)
        with pytest.raises(SystemExit, match="n_experts_held"):
            weights.arch_config(bad)
