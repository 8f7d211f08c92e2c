"""Paged decode attention: the least time for the valid K/V its decode
tokens attend (``bench/cost/attention.py``), from the harness's per-step
token counts in the traced window; None where no token was decoded there."""

import re

from bench.cost.attention import paged_decode

PATTERN = re.compile(r"^%paged_decode_attention_pallas[.\s]")


def least(run, calls, peaks):
    c, steps = run.config, run.trace.steps
    ctx = sum(s.decode_ctx for s in steps)
    n = sum(s.decode_tokens for s in steps)
    if not n:
        return None
    flops, nbytes = paged_decode(ctx, n, heads=c["num_attention_heads"],
                                 kv_heads=c["num_key_value_heads"],
                                 head_dim=c["head_dim"], layers=c["num_hidden_layers"])
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
