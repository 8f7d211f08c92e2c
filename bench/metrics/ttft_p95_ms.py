"""95th percentile, over every request of the window, of the time from when
the request was due to its first token.
A failed request counts as missing: +inf."""

import math

from bench.harness.runner import percentile


def read(run):
    if not hasattr(run, "records"):
        return None
    v = [(r.req.t_first - r.due) if r.ok else math.inf for r in run.records]
    return 1e3 * percentile(v, 0.95)
