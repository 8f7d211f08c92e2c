"""Device time per chunk-prefill program: the device time of the chunk
program's executions in the traced window (``jit__lambda`` modules that
write the page pool), over the chunks the engine counted there."""


def read(run):
    t = run.trace
    if t is None or not t.counters.get("prefill_chunks"):
        return None
    s = t.program_s.get("chunk", 0.0)
    return 1e3 * s / t.counters["prefill_chunks"] if s > 0 else None
