"""Device time per decode step: the device time of the fused decode block's
executions (``jit_fused`` modules) in the traced window, over the decode
steps the engine counted there (blocks x decode_block)."""


def read(run):
    t = run.trace
    if t is None or not t.counters.get("steps"):
        return None
    s = t.program_s.get("decode", 0.0)
    return 1e3 * s / t.counters["steps"] if s > 0 else None
