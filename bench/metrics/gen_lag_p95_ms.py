"""How late the open-loop generator sent requests: 95th percentile of send
time minus due time.  A starved generator would otherwise read as a fast
server."""

from bench.harness.runner import percentile


def read(run):
    if run.mix.get("loop") != "open" or not getattr(run, "records", None):
        return None
    return 1e3 * percentile([r.sent - r.due for r in run.records], 0.95)
