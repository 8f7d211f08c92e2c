"""1 - (union of device-op intervals) / traced window, in a serving cell."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
