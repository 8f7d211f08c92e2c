"""95th percentile over requests of (t_done - t_first) / (tokens - 1): the
mean gap between a request's output tokens.  Tokens arrive in blocks of
``decode_block``, so single gaps are not what a user sees.  A failed request
counts as +inf."""

import math

from bench.harness.runner import percentile


def read(run):
    if not hasattr(run, "records"):
        return None
    v = []
    for r in run.records:
        if not r.ok:
            v.append(math.inf)
        elif len(r.req.tokens) > 1:
            v.append((r.req.t_done - r.req.t_first) / (len(r.req.tokens) - 1))
    return 1e3 * percentile(v, 0.95)
