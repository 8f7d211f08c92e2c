"""A stand-in plain reference for the lookup test: position i's logits are
``-token_id``, so the gap of a served token is its own id, and every call
is recorded."""

import numpy as np

CALLS = []


def hidden(params, tokens, config, *, fp8=False):
    CALLS.append(("hidden", config["name"], fp8))
    return np.zeros((len(tokens), 1), np.float32)


def logits_rows(params, x, rows, config, *, fp8=False):
    CALLS.append(("logits_rows", config["name"], fp8))
    logits = -np.arange(config["vocab_size"], dtype=np.float32)
    if fp8:  # the float8 control puts token 1 first everywhere
        logits[1] = 1.0
    yield rows, np.tile(logits, (len(rows), 1))
