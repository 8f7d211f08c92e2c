"""Fused low-rank kernels (single and batched), as a share of their
roofline: the least time their calls need, the larger of operations over
the peak rate and bytes over the peak bandwidth (``bench/cost/lowrank.py``,
from each call's operand shapes in the trace), over their device time."""

from bench.harness.trace import kernel_roofline


def read(run):
    return kernel_roofline(run, "lowrank")
