"""Paged decode attention kernel as a share of its roofline: the least time
for the valid K/V its decode tokens attend (``bench/cost/attention.py``,
from the harness's per-step token counts in the traced window), over the
kernel's device time there."""

from bench.harness.trace import kernel_roofline


def read(run):
    return kernel_roofline(run, "paged_decode")
