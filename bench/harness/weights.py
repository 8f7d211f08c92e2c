"""The served model: its ``ArchConfig`` and its RSI-factored weights.

The factors are made directly on the device from the seed, in one jitted
call, at the leaves and ranks ``compress_tree`` gives at the configuration's
alpha.  The rule below is the benchmark's own copy of ``CompressionPolicy``'s
(rank ``ceil(alpha * min(c, d))``, the exclude pattern, the minimum size and
the break-even test), so a later change to the policy does not move what the
serving cells serve.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional

from repro.configs.registry import get_arch

# configuration-file key -> ArchConfig field, by family; each key the file
# holds is checked against the registry
_KEYS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "norm_eps": "norm_eps",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    # multi-head latent attention
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
}
_FAMILY_KEYS = {
    "dense": {"intermediate_size": "d_ff"},
    "moe": {
        "intermediate_size": "dense_d_ff",
        "n_routed_experts": "n_experts",
        "num_experts_per_tok": "top_k",
        "moe_intermediate_size": "moe_d_ff",
        "n_shared_experts": "n_shared_experts",
        "first_k_dense_replace": "first_dense_layers",
    },
}


def arch_config(config: dict):
    """The registry's ``ArchConfig`` as the file states it.

    The depth, ``num_hidden_layers``, is always the file's.  Every other key
    the file holds that the tables above name must be what the registry
    builds.  The file's own ``arch_fields`` (key -> ``ArchConfig`` field)
    add to the tables and take precedence; a key named there is applied as
    a cut where ``reduced`` lists it, and is checked otherwise.  No other
    key is cut: a table key in ``reduced`` stops the run, so that no width
    and no router changes unseen.  A key the file lacks is not demanded.
    An unknown family, or an ``arch_fields`` field ``ArchConfig`` lacks,
    stops the run.
    """
    base = get_arch(config["arch"])
    if base.family not in _FAMILY_KEYS:
        raise SystemExit(f"bench: {config['name']}: the registry's {config['arch']} is of "
                         f"family {base.family!r}; the benchmark knows {sorted(_FAMILY_KEYS)}")
    own = config.get("arch_fields", {})
    keys = {**_KEYS, **_FAMILY_KEYS[base.family], **own}
    fields = {f.name for f in dataclasses.fields(base)}
    cuts = {"n_layers": int(config["num_hidden_layers"])}
    for key, field in keys.items():
        if field not in fields:
            raise SystemExit(f"bench: {config['name']}: {key} sets {field!r}, "
                             f"which ArchConfig does not have")
        if key not in config:
            continue
        if key in config.get("reduced", ()):
            if key not in own:
                raise SystemExit(f"bench: {config['name']}: reduced lists {key}, which sets "
                                 f"{field}; only the depth and keys named in arch_fields are cut")
            cuts[field] = config[key]
        elif getattr(base, field) != config[key]:
            raise SystemExit(
                f"bench: {config['name']}: {key}={config[key]!r} but the "
                f"registry's {config['arch']} has {field}={getattr(base, field)!r}"
            )
    cfg = dataclasses.replace(base, **cuts)
    if cfg.dtype != config["torch_dtype"]:
        raise SystemExit(f"bench: {config['name']}: dtype {cfg.dtype} != {config['torch_dtype']}")
    return cfg


def factor_rank(name: str, c: int, d: int, comp: dict) -> Optional[int]:
    """The rank ``compress_tree`` would give this leaf, or None if it stays dense."""
    if re.search(comp["exclude"], name) or min(c, d) < comp["min_dim"]:
        return None
    k = max(1, math.ceil(comp["alpha"] * min(c, d)))
    if comp["break_even_only"] and k >= (c * d - 1) // (c + d):
        return None
    return k


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def served_shapes(model, comp: dict):
    """[(name, shape, dtype, rank or None)] for every leaf of the dense tree."""
    import jax

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for path, s in flat:
        name = leaf_name(path)
        rank = factor_rank(name, *s.shape[-2:], comp) if len(s.shape) >= 2 else None
        out.append((name, tuple(s.shape), s.dtype, rank))
    return out, treedef


def make_params(model, comp: dict, key):
    """The served tree, made on the device in one jitted call.

    Factored leaves become ``{"a": (..., c, r), "b": (..., r, d)}`` with
    N(0, 1/c) and N(0, 1/r) entries, so ``x @ A @ B`` keeps the scale of the
    dense initializer's ``x @ W``.  Dense matrices are N(0, 1/c) (the
    embedding N(0, 1/d)), norm scales are ones.
    """
    import jax
    import jax.numpy as jnp

    leaves, treedef = served_shapes(model, comp)

    def make(key):
        out = []
        for i, (name, shape, dtype, rank) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if len(shape) < 2:
                out.append(jnp.ones(shape, dtype))
                continue
            c, d = shape[-2:]
            lead = shape[:-2]
            if rank is not None:
                ka, kb = jax.random.split(k)
                a = jax.random.normal(ka, lead + (c, rank), dtype) * jnp.asarray(c**-0.5, dtype)
                b = jax.random.normal(kb, lead + (rank, d), dtype) * jnp.asarray(rank**-0.5, dtype)
                out.append({"a": a, "b": b})
            else:
                scale = (d if "embed" in name else c) ** -0.5
                out.append(jax.random.normal(k, shape, dtype) * jnp.asarray(scale, dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)
