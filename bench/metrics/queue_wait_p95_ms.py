"""95th percentile, over the run's admitted requests, of the time from
submission to a slot (the engine's ``t_admit - t_submit``): the wait in the
scheduler's queue for a slot and pages.  A request never admitted is left
out; a program that records no admission time reads nothing."""

from bench.harness.engine_trace import wait_p95_ms


def read(run):
    return wait_p95_ms(run, "t_submit", "t_admit")
