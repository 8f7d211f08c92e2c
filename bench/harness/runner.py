"""One run of one cell: set-up, the measured window, the check of what the
window produced, and the result line."""

from __future__ import annotations

import json
import math
import sys
import types
from pathlib import Path

from bench.harness import spec
from repro.runtime.compile_cache import use_compile_cache

DRAIN_S = 90.0  # how long past the close a due request may take to finish
CHECK_SAMPLE = 8  # requests whose served tokens the reference re-runs


def percentile(values, frac: float) -> float:
    """Nearest-rank percentile; a missing value (failed request) is +inf."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(frac * len(v)) - 1)]


def _devices(chips: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: JAX found no device: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"bench: cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts the programs this process compiles or loads from the
    persistent cache (JAX times both as one event), with their names: one
    that first runs inside the window was not warmed up."""

    def __init__(self):
        import jax

        self.events = []  # (function name, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def n(self) -> int:
        return len(self.events)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), float(duration)))


def _checks_line(checks: dict) -> None:
    for name, c in checks.items():
        print(f"[check] {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)


def run(args, t_process: float) -> int:
    cell = spec.load_cell(args.workload)
    import jax

    devices = _devices(cell.chips)
    peaks = spec.peaks_for(devices[0].device_kind)
    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    print(f"[bench] {cell.name} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache_dir}", file=sys.stderr, flush=True)

    loop = cell.traffic["loop"]
    run = types.SimpleNamespace(cell=cell, config=cell.config, mix=cell.traffic,
                                peaks=peaks, seconds=float(args.seconds), trace=None)
    tracer = None
    if args.trace:
        from bench.harness.trace import Tracer

        tracer = Tracer(Path(spec.ROOT) / ".bench_trace" / cell.name, float(args.seconds),
                        chips=cell.chips)
    if loop == "open":
        from bench.harness import serve_cell

        checks = serve_cell.run(run, args, t_process, compiles, tracer)
    else:
        raise SystemExit(f"bench: unknown loop kind {loop!r}")

    correct = bool(run.ok) and all(c["value"] <= c["limit"] for c in checks.values())
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = m.read(run)
        if v is None:
            continue
        if not math.isfinite(v):
            correct = False
            continue
        metrics[m.name] = {"value": v, "unit": m.unit}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    line = {"correct": correct, "attempted": run.attempted, "failed": run.failed,
            "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown
    print(f"[bench] compiles in window: {len(run.compiles_in_window)} "
          f"{run.compiles_in_window}; "
          f"set-up {run.setup_s:.3f} s; check {run.check_s:.3f} s", file=sys.stderr)
    _checks_line(checks)
    line["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0
