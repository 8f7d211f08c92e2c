"""Share of the traced window in which no program runs on the chip while
the host is inside an ``engine.step`` span: the device idle the engine's
host work causes.  Idle inside one program is the compiler's and is left
out, so this reads at most ``device_idle_pct.serve``."""

from bench.harness.engine_trace import for_run


def read(run):
    et = for_run(run)
    if et is None:
        return None
    return 100.0 * et.idle_in_steps_s() / et.window_s
