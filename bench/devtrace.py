"""The profiler trace of a measured window, and its reduction to numbers.

``WindowTracer`` starts JAX's profiler when the window opens and stops it when
the window closes. It marks each round boundary with a host annotation
(``bench.boundary``); it adds nothing to the program.

``reduce_trace`` turns the trace into a ``TraceSummary``: the device's busy
time (the union of the intervals in which an operation ran on it), the
traced window between the first and the last boundary mark, each XLA
module's device time and run count, the operations that took most time (by
self time: an op such as a ``while`` loop holds its body's ops, which are
taken out of its own time; each named by its HLO name and fusion kind), and
the idle gaps, each named by the XLA module that ran last before it (the
program has no host spans of its own yet, so this is what tells the gap
after the round program from the gap after eval). It reads the
``.xplane.pb`` with nothing but ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BOUNDARY = "bench.boundary"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Interval = Tuple[float, float]  # (start_s, end_s) on the trace's clock


# ---------------------------------------------------------------------------
# Recording
# ---------------------------------------------------------------------------


class WindowTracer:
    def __init__(self, log_dir: Path):
        self.log_dir = Path(log_dir)

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # every Python call would slow the host loop
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self.mark_boundary()

    def mark_boundary(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(BOUNDARY):
            pass

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def summarise(self) -> "TraceSummary":
        files = glob.glob(str(self.log_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file under {self.log_dir}, found {files}")
        return reduce_trace(files[0])


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class TraceSummary:
    window: Interval
    boundaries: List[float]
    busy: List[Interval]  # union of device op intervals inside the window
    modules: Dict[str, Tuple[float, int]]  # name -> (op seconds inside the window, runs)
    ops: Dict[str, float]  # op name -> seconds inside the window
    idle_after: Dict[str, float]  # "after <module>" -> idle seconds
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return total(self.busy)

    @property
    def rounds(self) -> int:
        return len(self.boundaries) - 1

    def busiest_module(self) -> Optional[str]:
        if not self.modules:
            return None
        return max(self.modules, key=lambda k: self.modules[k][0])

    def breakdown(self) -> dict:
        top_ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        top_gaps = sorted(self.idle_after.items(), key=lambda kv: -kv[1])[:TOP]
        return {
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in top_gaps],
        }


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), kind=kLoop, ...`` -> ``%fusion.12 kLoop``."""
    name = hlo.split(" = ", 1)[0]
    kind = hlo.find(", kind=")
    if kind >= 0:
        name += " " + hlo[kind + 7:].split(",", 1)[0]
    return name


def self_times(events: List[Tuple[str, float, float]]) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self seconds) per event: its duration less the part
    that the ops nested directly inside it cover."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [e - s for _, s, e in events]
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, events[stack[-1]][2]) - s
        stack.append(i)
    return [(n, s, e, own[i]) for i, (n, s, e) in enumerate(events)]


def reduce_trace(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(pd) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` (see ``reduce_trace``)."""
    marks: List[float] = []
    op_events: List[Tuple[str, float, float]] = []
    module_events: List[Tuple[str, float, float]] = []
    n_devices = 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            if n_devices:  # one chip: the first TPU core's plane
                continue
            n_devices += 1
            for line in plane.lines:
                if line.name == OPS_LINE:
                    op_events.extend(_events(line))
                elif line.name == MODULES_LINE:
                    module_events.extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks.extend(s for name, s, _ in _events(line) if name == BOUNDARY)
    if n_devices == 0 or not op_events:
        raise RuntimeError("no device operations in the trace")
    marks.sort()
    if len(marks) < 2:
        raise RuntimeError(f"{len(marks)} round boundaries in the trace; need two or more")
    lo, hi = marks[0], marks[-1]

    busy = clip(union([(s, e) for _, s, e in op_events]), lo, hi)
    ops: Dict[str, float] = defaultdict(float)
    for name, s, e, own in self_times(op_events):
        if s >= lo and e <= hi:
            ops[op_name(name)] += own

    # each module run's op time: the union of the ops inside its interval
    op_iv = sorted((s, e) for _, s, e in op_events)
    starts = [s for s, _ in op_iv]
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for name, s, e in module_events:
        if s < lo or e > hi:
            continue
        i = bisect.bisect_left(starts, s)
        inside = []
        while i < len(op_iv) and op_iv[i][0] < e:
            inside.append((op_iv[i][0], min(op_iv[i][1], e)))
            i += 1
        modules[name][0] += total(union(inside))
        modules[name][1] += 1

    # each idle gap, by the module whose run began last before the gap did
    runs = sorted((s, name) for name, s, _ in module_events)
    run_starts = [s for s, _ in runs]
    idle: Dict[str, float] = defaultdict(float)
    for g in gaps(busy, lo, hi):
        i = bisect.bisect_right(run_starts, g[0]) - 1
        idle[f"after {runs[i][1]}" if i >= 0 else "before any module"] += g[1] - g[0]

    return TraceSummary(
        window=(lo, hi), boundaries=marks, busy=busy,
        modules={k: (v[0], int(v[1])) for k, v in modules.items()},
        ops=dict(ops), idle_after=dict(idle),
    )
