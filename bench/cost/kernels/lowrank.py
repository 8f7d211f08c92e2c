"""Fused low-rank matmul calls, single and batched: each call's least time
from its operand shapes in the trace (``bench/cost/lowrank.py``)."""

import re

from bench.cost.lowrank import lowrank_call
from bench.cost.hlo import shapes

PATTERN = re.compile(r"^%lowrank_matmul(_batched)?_pallas[.\s]")


def least(run, calls, peaks):
    total = 0.0
    for o in calls:
        # the custom call's output shape, then its operands x, A, B
        x, _a, b = shapes(o.text)[1:4]
        L = x[0] if len(x) == 3 else 1
        M, K = x[-2:]
        r, N = b[-2:]
        flops, nbytes = lowrank_call(M, K, r, N, L=L)
        total += max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return total
