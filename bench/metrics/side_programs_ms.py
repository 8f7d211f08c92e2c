"""Device time per engine step of the programs that are neither the fused
decode block nor the chunk program (grouped prefill, its page scatter, the
first-token argmax, the copy-on-write fork, transfers run as programs):
the traced window's program time less ``program_s["decode"]`` and
``["chunk"]``, over the ``engine.step`` spans in the window."""

from bench.harness.engine_trace import for_run


def read(run):
    et = for_run(run)
    if et is None:
        return None
    p = run.trace.program_s
    side = et.module_s() - p.get("decode", 0.0) - p.get("chunk", 0.0)
    return 1e3 * side / len(et.steps())
