"""The program's own spans and scopes in a traced window, for ``metrics/``.

A program that carries them puts two things into the profile that
``devtrace`` does not read:

- host spans named ``obs.<name>``: its tracer's profiler lane, one per child
  of each sync-loop iteration (``plan``, ``data``, ``h2d``, ``launch``,
  ``sync``, ``eval``, ``control``, ``ckpt``, ``log``) inside ``obs.iter``;
- named device programs (``jit_fed_round(<id>)``), whose operations' scopes
  (``client/.../jvp(fwd)``, ``client/.../transpose(jvp(fwd))``,
  ``client/.../opt``, ``server``) come from ``repro.obs.programs.op_scopes()``
  after the window, as a device operation in the trace carries no op_name.

``reduce_profile`` reduces them: the device's idle time in the window split
by the host span it fell in (``data``: plan, data or h2d; ``loop``: any other
span; ``untraced``: none), which sums to the idle time exactly, and each
module's operations by HLO instruction with self time. Where the program has
neither (an older program), the readers find nothing and return None.

The harness's ``Context`` carries neither the trace's path nor the rows. A
reader takes ``ctx.rows``, ``ctx.trace_path`` and ``ctx.scopes`` where a
harness sets them, and otherwise takes the rows and the trace's directory from
the ``run_cell`` call that runs the readers (its ``session`` and ``tracer``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import devtrace
from devtrace import Interval

PREFIX = "obs."
ITER = "obs.iter"
DATA = ("obs.plan", "obs.data", "obs.h2d")
ROUND_PROGRAM = "jit_fed_round"
EVAL_PROGRAM = "jit_eval_ce"

_WRAPPED = re.compile(r"([\w.\-]+)\((.*)\)")


def module_base(name: str) -> str:
    """``jit_fed_round(123)`` -> ``jit_fed_round``."""
    return name.split("(", 1)[0]


def instruction(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def bucket(op_name: str) -> Optional[str]:
    """The round program's part an op_name belongs to: ``fwd``, ``bwd``
    (``client``'s transposed forward), ``opt``, ``server``, ``client`` (the
    client phase's other ops), or None (unscoped). Transformations wrap scope
    names (``vmap(transpose(jvp(fwd)))``); XLA joins merged names with ``;``,
    of which the first is read."""
    names, transforms = set(), set()
    for seg in op_name.split(";", 1)[0].split("/"):
        m = _WRAPPED.fullmatch(seg)
        while m:
            transforms.add(m.group(1))
            seg = m.group(2)
            m = _WRAPPED.fullmatch(seg)
        names.add(seg)
    if "server" in names:
        return "server"
    if "client" not in names:
        return None
    if "transpose" in transforms:
        return "bwd"
    if "opt" in names:
        return "opt"
    if "fwd" in names:
        return "fwd"
    return "client"


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclasses.dataclass
class SpanSummary:
    window: Interval
    rounds: int
    spans: Dict[str, List[Interval]]  # obs.<name> -> its intervals, clipped to the window
    idle: Dict[str, float]  # "data" / "loop" / "untraced" -> idle seconds in the window
    modules: Dict[str, Tuple[int, Dict[str, float]]]  # base name -> (runs, {instruction: self s})

    @property
    def idle_s(self) -> float:
        return sum(self.idle.values())

    def per_run_ms(self, module: str, scopes: Dict[str, str]) -> Optional[Dict[Optional[str], float]]:
        """Self time per run of ``module``'s ops, in ms, by :func:`bucket` of
        each op's scope; ops the table lacks fall under ``"unknown"``."""
        if module not in self.modules:
            return None
        runs, ops = self.modules[module]
        out: Dict[Optional[str], float] = defaultdict(float)
        for name, seconds in ops.items():
            key = bucket(scopes[name]) if name in scopes else "unknown"
            out[key] += 1e3 * seconds / runs
        return dict(out)


def reduce_profile(pd) -> Optional[SpanSummary]:
    """Reduce a ``jax.profiler.ProfileData``; None where it holds no ``obs.*``
    span. The window and device events are read as ``devtrace`` reads them."""
    marks: List[float] = []
    spans: Dict[str, List[Interval]] = defaultdict(list)
    op_events, module_events = [], []
    device_seen = False
    for plane in pd.planes:
        if plane.name.startswith(devtrace.DEVICE_PLANE_PREFIX):
            if device_seen:  # one chip: the first TPU core's plane
                continue
            device_seen = True
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    op_events.extend(devtrace._events(line))
                elif line.name == devtrace.MODULES_LINE:
                    module_events.extend(devtrace._events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in devtrace._events(line):
                    if name == devtrace.BOUNDARY:
                        marks.append(s)
                    elif name.startswith(PREFIX):
                        spans[name].append((s, e))
    if not spans or not op_events or len(marks) < 2:
        return None
    marks.sort()
    lo, hi = marks[0], marks[-1]
    spans = {k: devtrace.clip(devtrace.union(v), lo, hi) for k, v in spans.items()}

    busy = devtrace.clip(devtrace.union([(s, e) for _, s, e in op_events]), lo, hi)
    idle = devtrace.gaps(busy, lo, hi)
    traced = devtrace.union([iv for k, v in spans.items() if k != ITER for iv in v])
    data = devtrace.union([iv for k in DATA for iv in spans.get(k, [])])
    idle_total = devtrace.total(idle)
    idle_traced = overlap(idle, traced)
    idle_data = overlap(idle, data)

    # each op by the module run whose interval holds its start, runs inside the window
    runs = sorted((s, e, module_base(name)) for name, s, e in module_events if s >= lo and e <= hi)
    starts = [s for s, _, _ in runs]
    modules: Dict[str, List] = {}
    for _, _, name in runs:
        modules.setdefault(name, [0, defaultdict(float)])[0] += 1
    for hlo, s, e, own in devtrace.self_times(op_events):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1] and e <= hi:
            modules[runs[i][2]][1][instruction(hlo)] += own

    return SpanSummary(
        window=(lo, hi), rounds=len(marks) - 1, spans=spans,
        idle={"data": idle_data, "loop": idle_traced - idle_data,
              "untraced": idle_total - idle_traced},
        modules={k: (v[0], dict(v[1])) for k, v in modules.items()},
    )


# ---------------------------------------------------------------------------
# What the readers read
# ---------------------------------------------------------------------------


def _run_cell_locals() -> dict:
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "run_cell" and "session" in f.f_locals:
            return f.f_locals
        f = f.f_back
    return {}


def rows(ctx) -> Optional[List[dict]]:
    """The run's history rows, set-up rows first."""
    if getattr(ctx, "rows", None) is not None:
        return ctx.rows
    session = _run_cell_locals().get("session")
    return None if session is None else session.rows


def _trace_path(ctx) -> Optional[str]:
    if getattr(ctx, "trace_path", None) is not None:
        return ctx.trace_path
    tracer = _run_cell_locals().get("tracer")
    if tracer is None:
        return None
    files = glob.glob(str(tracer.log_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return files[0] if len(files) == 1 else None


def of(ctx) -> Optional[SpanSummary]:
    """The window's span summary, reduced once per run and kept on ``ctx``."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "span_summary"):
        path = _trace_path(ctx)
        summary = None
        if path is not None:
            from jax.profiler import ProfileData

            summary = reduce_profile(ProfileData.from_file(path))
        ctx.span_summary = summary
    return ctx.span_summary


def scopes(ctx) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: op_name}}`` of the program's named programs,
    from ``ctx.scopes`` or ``repro.obs.programs``; empty where neither is."""
    if getattr(ctx, "scopes", None) is None:
        try:
            from repro.obs import programs
        except ImportError:
            ctx.scopes = {}
        else:
            try:
                ctx.scopes = programs.op_scopes()
            except Exception as e:  # the metrics it feeds are left out, and why is noted
                ctx.trace.notes.append(f"no scope table: op_scopes() raised {e!r}")
                ctx.scopes = {}
    return ctx.scopes


def round_parts_ms(ctx) -> Optional[Dict[Optional[str], float]]:
    """The round program's self time per run, in ms, by :func:`bucket`; None
    outside a sync cell or where the trace or the scope table lacks it."""
    if ctx.cell.flags().get("--aggregation") != "sync":
        return None
    if not hasattr(ctx, "round_parts"):
        summary = of(ctx)
        table = scopes(ctx).get(ROUND_PROGRAM) if summary is not None else None
        parts = None if table is None else summary.per_run_ms(ROUND_PROGRAM, table)
        if parts is not None:
            ctx.trace.notes.append(
                "round program parts, ms a run: " + ", ".join(
                    f"{k}={v!r}" for k, v in sorted(parts.items(), key=lambda kv: str(kv[0]))))
        ctx.round_parts = parts
    return ctx.round_parts


def idle_ms(ctx, group: str) -> Optional[float]:
    """Device-idle ms per window round under ``group`` (see the module docstring)."""
    summary = of(ctx)
    if summary is None:
        return None
    if not getattr(ctx, "idle_noted", False):
        ctx.trace.notes.append(
            f"idle by host span, s in {summary.rounds} rounds: "
            + ", ".join(f"{k}={v!r}" for k, v in summary.idle.items())
            + f"; device idle {ctx.trace.window_s - ctx.trace.busy_s!r}")
        ctx.idle_noted = True
    return 1e3 * summary.idle[group] / summary.rounds
