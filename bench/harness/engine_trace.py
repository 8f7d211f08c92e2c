"""What the engine records about itself in a serving run: its request
timestamps and, in a ``--trace 1`` run, its ``engine.*`` profiler spans.

The engine (``src/repro/serving/engine.py``) opens a host span around each
step (``engine.step``) and each phase of one; they share the clock of the
device's ``XLA Modules`` line, so an idle stretch between two programs can
be put down to what the host was doing in it.  This module reads the
newest profile of the cell once per run, walking only the ``XLA Modules``
line of each chip and the host spans, never the far longer ``XLA Ops``
line.  A profile of a program that opens no ``engine.*`` span reads as
nothing: the readers return ``None``.

Each request carries ``t_submit``, ``t_admit`` (placed in a slot) and
``t_first``: queue wait is ``t_admit - t_submit``, prefill wait ``t_first -
t_admit``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import sys
import time
from typing import Dict, List, Optional, Tuple

from bench.harness import spec
from bench.harness.runner import percentile
from bench.harness.trace import _gaps

Interval = Tuple[float, float]


@dataclasses.dataclass
class EngineTrace:
    lo: float  # the traced window (``bench.window``), ns
    hi: float
    modules: Dict[str, List[Interval]]  # per chip: program executions
    spans: List[Tuple[str, float, float]]  # engine.* host spans, by start
    read_s: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def steps(self) -> List[Interval]:
        """``engine.step`` spans wholly inside the window."""
        return [(s, e) for n, s, e in self.spans
                if n == "engine.step" and self.lo <= s and e <= self.hi]

    def module_s(self) -> float:
        """Device seconds of program executions wholly inside the window,
        averaged over chips (the rule ``trace.reduce_file`` uses)."""
        return sum(e - s for iv in self.modules.values() for s, e in iv
                   if self.lo <= s and e <= self.hi) / len(self.modules) / 1e9

    def gaps(self, chip: str) -> List[Interval]:
        """Stretches of the window in which no program runs on ``chip``."""
        iv = [(max(s, self.lo), min(e, self.hi)) for s, e in self.modules[chip]
              if e > self.lo and s < self.hi]
        return _gaps(iv, self.lo, self.hi)

    def idle_in_steps_s(self) -> float:
        """Seconds of the window in which no program runs while the host is
        inside an engine step, averaged over chips."""
        steps = self.steps()
        total = sum(_overlap(self.gaps(c), steps) for c in self.modules)
        return total / len(self.modules) / 1e9

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds by the innermost ``engine.*`` span open at each
        gap's midpoint, averaged over chips."""
        starts = [s for _, s, _ in self.spans]
        out: Dict[str, float] = {}
        for chip in self.modules:
            for a, b in self.gaps(chip):
                name = _innermost(self.spans, starts, (a + b) / 2)
                out[name] = out.get(name, 0.0) + (b - a) / len(self.modules) / 1e9
        return out


def _innermost(spans, starts, t) -> str:
    # spans of one thread nest, so the latest-starting span still open at
    # t is the innermost one
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, s, e = spans[i]
        if e >= t:
            return name
        i -= 1
    return "outside engine.step"


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def load(path: str, chips: int = 1) -> EngineTrace:
    """Read one profile's ``XLA Modules`` lines and host spans."""
    from jax.profiler import ProfileData

    t = time.perf_counter()
    modules: Dict[str, List[Interval]] = {}
    spans, window = [], []
    for plane in ProfileData.from_file(path).planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit():
            if int(name[len("/device:TPU:"):]) >= chips:
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[name] = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                                           for ev in line.events)
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine."):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
                    elif ev.name == "bench.window":
                        window.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    # a profile without the harness's window holds no engine step in it
    lo, hi = window[0] if window else (0.0, 0.0)
    spans.sort(key=lambda sp: (sp[1], -sp[2]))
    return EngineTrace(lo, hi, modules, spans, read_s=time.perf_counter() - t)


def report(et: EngineTrace, path: str) -> None:
    print(f"[engine_trace] {path}: read in {et.read_s:.1f} s; "
          f"{len(et.steps())} engine steps in {et.window_s:.3f} s", file=sys.stderr)
    for name, s in sorted(et.idle_by_span().items(), key=lambda kv: -kv[1]):
        print(f"[engine_trace] device idle in {name}: {s!r} s", file=sys.stderr)


def for_run(run) -> Optional[EngineTrace]:
    """The engine trace of a traced run, read once; ``None`` where the run
    was not traced, no profile was written, or the program opened no engine
    step in its window."""
    if getattr(run, "trace", None) is None:
        return None
    if not hasattr(run, "engine_trace"):
        files = sorted(glob.glob(str(spec.ROOT / ".bench_trace" / run.cell.name / "**" /
                                     "*.xplane.pb"), recursive=True))
        et = load(files[-1], run.cell.chips) if files else None
        if et is not None and et.modules:
            report(et, files[-1])
        run.engine_trace = et if et is not None and et.modules and et.steps() else None
    return run.engine_trace


def held(run) -> List[Interval]:
    """Stretches (perf_counter s) in which the harness held its loop to
    start or stop the profiler: from the engine step before the first
    traced one to it, and from the last traced one to the step after."""
    steps = getattr(run, "steps", None) or []
    traced = [i for i, s in enumerate(steps) if s.traced]
    if not traced:
        return []
    i, j = traced[0], traced[-1]
    out = []
    if i > 0:
        out.append((steps[i - 1].t1, steps[i].t0))
    if j + 1 < len(steps):
        out.append((steps[j].t1, steps[j + 1].t0))
    return out


def wait_p95_ms(run, start: str, end: str) -> Optional[float]:
    """Nearest-rank p95, over the run's requests that reached both
    timestamps, of ``end - start`` less any stretch in which the harness
    held the loop for the profiler; ``None`` where no request did (or the
    program keeps no such timestamp)."""
    holds = held(run)
    v = []
    for rec in getattr(run, "records", None) or []:
        a, b = getattr(rec.req, start, 0.0), getattr(rec.req, end, 0.0)
        if a > 0 and b >= a:
            v.append(b - a - sum(max(0.0, min(b, e) - max(a, s)) for s, e in holds))
    return 1e3 * percentile(v, 0.95) if v else None

