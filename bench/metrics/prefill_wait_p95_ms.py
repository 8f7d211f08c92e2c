"""95th percentile, over the run's requests with a first token, of the time
from a slot to the first token (the engine's ``t_first - t_admit``): the
chunk queue, the decode blocks between its chunks, and its own prefill."""

from bench.harness.engine_trace import wait_p95_ms


def read(run):
    return wait_p95_ms(run, "t_admit", "t_first")
