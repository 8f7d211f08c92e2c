"""The profiler trace of a ``--trace 1`` run, and its reduction to the
numbers the per-layer metrics read.

On a TPU the trace holds, per chip, an ``XLA Modules`` line (one event per
program execution, named ``jit_<function>(<fingerprint>)``) and an ``XLA
Ops`` line (one event per HLO instruction, named by its full HLO text,
operand shapes included; a loop's event encloses its body's).  Device busy
time is the union of the op intervals inside the traced window, which the
harness marks with a ``bench.window`` host span; its ``bench.step`` spans
around each engine step name the idle gaps.

A kernel is found by name: ``bench/cost/kernels/<kernel>.py`` gives the
``PATTERN`` its trace events match and ``least(run, calls, peaks)``, the
least time its calls in the window need (None where it cannot be known).
A new kernel's roofline is that file and a per-layer metric,
``bench/metrics/roofline_pct.<name>.py``, that reads
``kernel_roofline(run, "<kernel>")``.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import re
import shutil
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

from bench.cost.hlo import SHAPE
from bench.cost.hlo import shapes as _shapes  # noqa: F401  (the tests read it here)
from bench.harness import spec

KERNEL_COSTS = spec.BENCH / "cost" / "kernels"
DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16", "int32": "s32", "int8": "s8"}


@dataclasses.dataclass
class Op:
    text: str  # the HLO instruction
    start_ns: float
    dur_ns: float
    module: str = ""  # name of the program execution that encloses it
    self_ns: float = 0.0  # duration minus the ops it encloses
    feed_ns: float = 0.0  # ops that sliced or copied this op's operands for it


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float
    program_s: Dict[str, float]  # device seconds per program kind
    kernels: Dict[str, List[Op]]
    counters: Dict[str, int]
    steps: list
    breakdown: dict


def kernels() -> Dict[str, ModuleType]:
    """Every kernel with a cost file, by name, in the order of the names."""
    return {p.stem: spec.load_module(p) for p in sorted(KERNEL_COSTS.glob("*.py"))}


def shape_text(dtype, shape) -> str:
    return f"{DTYPE.get(str(dtype), str(dtype))}[{','.join(str(int(d)) for d in shape)}]"


def _union(intervals) -> float:
    total, end = 0.0, -math.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _gaps(intervals, lo, hi):
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _self_times(ops: List[Op]) -> None:
    """Set each op's self time: its duration less the ops it encloses."""
    stack: List[Op] = []
    for o in sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns)):
        while stack and o.start_ns >= stack[-1].start_ns + stack[-1].dur_ns:
            stack.pop()
        o.self_ns = o.dur_ns
        if stack:
            stack[-1].self_ns -= o.dur_ns
        stack.append(o)


def _label(o: Op) -> str:
    """A readable name for an op: instruction name without its number, its
    output shape, and the program it ran in."""
    name = re.sub(r"\.\d+$", "", _name(o.text))
    out = re.split(r" [a-z][a-z0-9_\-]*\(", o.text.split(" = ", 1)[-1], maxsplit=1)[0]
    shapes = SHAPE.findall(out)
    big = max(shapes, key=lambda s: math.prod(int(x) for x in s[1].split(",") if x), default=None)
    prog = re.sub(r"\(\d+\)$", "", o.module)
    return f"{prog}: {name} {big[0]}[{big[1]}]" if big else f"{prog}: {name}"


def _name(text: str) -> str:
    return text.split(" = ", 1)[0].lstrip("%")


OPERANDS = re.compile(r"custom-call\((.*?)\), custom_call_target")
FEEDS = re.compile(r"slice|copy")


def _feeds(kernel_ops: List[Op], ops: List[Op]) -> None:
    """Charge each kernel call with the ops that sliced or copied its operands
    just before it (a stacked layer's factor or page pool cut out for the
    call, or moved to fast memory): its least time counts those bytes, so
    its time has to count that work too.  Each such op is charged once, to
    the first call that reads it."""
    import bisect

    charged = set()
    by_name: Dict[str, List[tuple]] = {}
    for o in ops:
        n = _name(o.text)
        if FEEDS.search(n):
            by_name.setdefault(n, []).append((o.start_ns + o.dur_ns, o.dur_ns))
    for lst in by_name.values():
        lst.sort()
    for k in kernel_ops:
        m = OPERANDS.search(k.text)
        for operand in re.findall(r"%([\w.\-]+)", m.group(1) if m else ""):
            lst = by_name.get(operand)
            if not lst:
                continue
            i = bisect.bisect_right(lst, (k.start_ns, math.inf)) - 1
            if i >= 0 and (operand, i) not in charged:
                charged.add((operand, i))
                k.feed_ns += lst[i][1]


def load(path: str, chips: int):
    """(ops per chip, modules per chip, harness host spans) of one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    mods: Dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit():
            if int(name[len("/device:TPU:"):]) >= chips:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[name] = [Op(ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]
                elif line.name == "XLA Modules":
                    mods[name] = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                                  for ev in line.events]
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return ops, mods, spans


def _assign_modules(ops: List[Op], mods) -> None:
    mods = sorted(mods, key=lambda m: m[1])
    i = 0
    for o in sorted(ops, key=lambda o: o.start_ns):
        while i < len(mods) and mods[i][2] < o.start_ns:
            i += 1
        if i < len(mods) and mods[i][1] <= o.start_ns:
            o.module = mods[i][0]


def classify(module: str, texts: List[str], pool_shapes: List[str]) -> Optional[str]:
    """Which program a module execution is: the fused decode block or the
    chunk-prefill program (a ``jit__lambda`` that writes the page pool)."""
    if module.startswith("jit_fused("):
        return "decode"
    if module.startswith("jit__lambda(") and pool_shapes and any(
            p in t for t in texts for p in pool_shapes):
        return "chunk"
    return None


def reduce_file(path: str, counters: dict, steps: list, *, chips: int = 1,
                pool_shapes: List[str] = ()) -> Trace:
    ops, mods, spans = load(path, chips)
    if not ops or not any(ops.values()):
        raise SystemExit(f"bench: no TPU ops in the trace {path}")
    win = [(s, e) for n, s, e in spans if n == "bench.window"]
    lo, hi = win[0] if win else (min(o.start_ns for l in ops.values() for o in l),
                                 max(o.start_ns + o.dur_ns for l in ops.values() for o in l))
    busy, program, gaps_all = [], {}, []
    patterns = {k: mod.PATTERN for k, mod in kernels().items()}
    found: Dict[str, List[Op]] = {k: [] for k in patterns}
    tally: Dict[str, float] = {}
    for chip, lst in ops.items():
        lst = [o for o in lst if lo <= o.start_ns and o.start_ns + o.dur_ns <= hi]
        _self_times(lst)
        _assign_modules(lst, mods.get(chip, []))
        iv = [(o.start_ns, o.start_ns + o.dur_ns) for o in lst]
        busy.append(_union(iv))
        gaps_all.extend(_gaps(iv, lo, hi))
        by_module: Dict[tuple, List[Op]] = {}
        for o in lst:
            tally[_label(o)] = tally.get(_label(o), 0.0) + o.self_ns
            for k, pat in patterns.items():
                if pat.search(o.text):
                    found[k].append(o)
            by_module.setdefault(o.module, []).append(o)
        _feeds([o for k in found.values() for o in k], lst)
        kinds = {}
        for m in by_module:
            kinds[m] = classify(m, [o.text for o in by_module[m]], list(pool_shapes))
        for name, s, e in mods.get(chip, []):
            k = kinds.get(name)
            if k and lo <= s and e <= hi:
                program[k] = program.get(k, 0.0) + (e - s)
    n = len(ops)

    def span_at(t):
        for name, s, e in spans:
            if name != "bench.window" and s <= t <= e:
                return name
        return "harness loop (no engine call)"

    gaps_all.sort(key=lambda g: g[0] - g[1])
    breakdown = {
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(tally.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[span_at((a + b) / 2), (b - a) / 1e9] for a, b in gaps_all[:10]],
    }
    return Trace(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n / 1e9,
                 program_s={k: v / n / 1e9 for k, v in program.items()},
                 kernels=found, counters=counters,
                 steps=steps, breakdown=breakdown)


class Tracer:
    """Traces up to 10 s of the window from a quarter into it: long enough
    that chunks and decode blocks both run in it at a rate below the knee.
    A whole window of a 32-layer model's ops would be too large to read
    within a run's time limit."""

    def __init__(self, out_dir: Path, seconds: float, chips: int = 1):
        self.dir = Path(out_dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.start_s = 0.25 * seconds
        self.stop_s = self.start_s + min(10.0, 0.5 * seconds)
        self.chips = chips
        self.c0 = self.c1 = {}
        self.pool_shapes: List[str] = []
        self._window = None

    def start(self, server) -> None:
        import jax

        self.c0 = server.counters()
        self.pool_shapes = server.pool_shapes()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the harness's own spans are enough
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def stop(self, server) -> None:
        import jax

        self._window.__exit__(None, None, None)
        self.c1 = server.counters()
        jax.profiler.stop_trace()

    def reduce(self, run=None) -> Optional[Trace]:
        files = sorted(glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True))
        if not files:
            return None
        steps = [s for s in getattr(run, "steps", []) if s.traced]
        counters = {k: self.c1[k] - self.c0.get(k, 0) for k in self.c1}
        t = time.perf_counter()
        out = reduce_file(files[-1], counters, steps, chips=self.chips,
                          pool_shapes=self.pool_shapes)
        print(f"[trace] {files[-1]}: read in {time.perf_counter() - t:.1f} s", flush=True)
        for k, calls in out.kernels.items():
            print(f"[trace] {k}: {len(calls)} calls, own {sum(o.dur_ns for o in calls) / 1e9!r} s, "
                  f"operand feeds {sum(o.feed_ns for o in calls) / 1e9!r} s", flush=True)
        return out


def kernel_roofline(run, kind: str) -> Optional[float]:
    """Least time of the kernel's calls in the traced window over their
    device time, the ops that fed their operands included, in percent; None
    where no call of it ran there or its least time is not known."""
    t = run.trace
    if t is None or not t.kernels.get(kind):
        return None
    calls = t.kernels[kind]
    dev_s = sum(o.dur_ns + o.feed_ns for o in calls) / 1e9
    least = kernels()[kind].least(run, calls, run.peaks)
    if least is None:
        return None
    return 100.0 * least / dev_s
