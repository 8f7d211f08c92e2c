"""Model operations per token of the served (factored) decoder, from shapes.

A factored linear (c, r, d) costs ``2 r (c + d)`` per token, a dense one
``2 c d``.  The LM head runs once per sampled token.  Attention
adds ``4 n H hd`` per layer for a token whose context holds n positions.
Capacity padding, masked positions and recomputation are not model work.
"""

import math


def linear_flops(shape, rank) -> int:
    c, d = shape[-2:]
    return 2 * rank * (c + d) if rank is not None else 2 * c * d


def per_token(leaves, config: dict):
    """(trunk flops per token, head flops per sampled token, attention flops
    per context position) for the served tree ``leaves`` ([(name, shape,
    dtype, rank)] as ``weights.served_shapes`` gives)."""
    trunk = head = 0
    for name, shape, _dtype, rank in leaves:
        if len(shape) < 2 or name.startswith("embed"):
            continue
        if name.startswith("lm_head"):
            head += linear_flops(shape, rank)
            continue
        trunk += math.prod(shape[:-2]) * linear_flops(shape, rank)  # stacked layers
    layers = config["num_hidden_layers"]
    attn = 4 * layers * config["num_attention_heads"] * config["head_dim"]
    return trunk, head, attn
