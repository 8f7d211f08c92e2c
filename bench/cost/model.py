"""Model operations per token of the served (factored) decoder, from shapes.

The rules, each read from the served tree's leaves, the configuration
file and, for routed experts, the router of the model the program builds:

- A factored linear (c, r, d) costs ``2 r (c + d)`` per token, a dense one
  ``2 c d``.  A stacked leaf counts each of its stacked slices: every layer
  of a layer stack, and the dense leading layers (``dense_layers/``) whole.
- The LM head runs once per sampled token; the embedding is a lookup.
- Routed experts (a leaf under ``experts/``, stacked ``(..., E, c, d)``)
  count ``k / R`` of their ``E`` stacked experts, where k experts per token
  and a router over R are those of the model the program builds
  (``route``, from its ``ArchConfig``: ``top_k``, ``n_experts``).  A token
  visits k of the R experts, so a chip that holds E of them does ``k E /
  R`` experts' work per token on average; where it holds all, k.  Shared
  experts, routers and everything else count whole.
- Attention adds, per layer and for a token whose context holds n
  positions, ``4 n H hd`` (scores and weighted sum over each head); with
  ``kv_lora_rank`` in the file, latent attention (MLA) in its absorbed
  form, ``2 n H (2 kv_lora_rank + qk_rope_head_dim)`` (scores over the
  latent and the rotary part, weighted sum over the latent).

Capacity padding, masked positions and recomputation are not model work.
"""

import math


def linear_flops(shape, rank) -> int:
    c, d = shape[-2:]
    return 2 * rank * (c + d) if rank is not None else 2 * c * d


def per_token(leaves, config: dict, route=None):
    """(trunk flops per token, head flops per sampled token, attention flops
    per context position) for the served tree ``leaves`` ([(name, shape,
    dtype, rank)] as ``weights.served_shapes`` gives).  ``route`` is the
    built model's (experts per token, router width), needed where a leaf
    is a routed expert's."""
    trunk = head = 0
    for name, shape, _dtype, rank in leaves:
        if len(shape) < 2 or name.startswith("embed"):
            continue
        if name.startswith("lm_head"):
            head += linear_flops(shape, rank)
            continue
        flops = math.prod(shape[:-2]) * linear_flops(shape, rank)  # stacked slices
        if "experts" in name.split("/"):
            if route is None:
                raise ValueError(f"{name} is a routed expert's; per_token needs route")
            k, width = route
            flops = flops * k / width
        trunk += flops
    layers, heads = config["num_hidden_layers"], config["num_attention_heads"]
    if "kv_lora_rank" in config:
        attn = 2 * layers * heads * (2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])
    else:
        attn = 4 * layers * heads * config["head_dim"]
    return trunk, head, attn
