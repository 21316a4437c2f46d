"""Runs one benchmark cell through the trainer's own entry point.

A cell (``workloads/<name>.json``) names a model configuration
(``configs/<config>.json``), a traffic mix (``traffic/<traffic>.json``) and a
correctness check (``checks/<check>.py``). The harness turns the configuration
and the traffic into the trainer's flags and calls
``repro.launch.train.run(parse_args(argv), cfg=...)`` once, in this process.

Round boundaries come from the trainer's own per-round log: the harness hands
``run()`` a ``--log`` path and stands in for the ``MetricLogger`` it builds, so
each history row reaches the harness as the loop logs it (a sync round and an
async update alike). The first ``setup_rounds`` rows are set-up: they compile
the round and eval programs and are the rounds the check follows. The window
opens when the last of them is logged and closes at the first row logged at or
after ``--seconds``; the harness then ends the run by raising out of the log
call. The window therefore holds whole rounds and all the loop does in them:
plan, host data, host-to-device copies, the jitted round, metric syncs and
eval.

End-to-end metrics are read by the modules in ``end_to_end/`` and per-layer
metrics by those in ``metrics/``, each found by file name; a reader that has
nothing to read in this cell returns None and its metric is left out.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def process_age_s() -> float:
    """Seconds since this process was started, from /proc."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------------------
# Cells: data files found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def setup_rounds(self) -> int:
        return int(self.workload["setup_rounds"])

    def flags(self) -> Dict[str, Any]:
        """The trainer's flags: the configuration's recipe, then the traffic's."""
        c = self.config
        recipe = {
            "--local-steps": c["local_steps"],
            "--inner-lr": c["inner_optimizer"]["lr_max"],
            "--outer": c["outer_optimizer"]["name"],
            "--outer-lr": c["outer_optimizer"]["lr"],
        }
        return {**recipe, **self.traffic["flags"]}

    def tokens_per_client_round(self) -> int:
        f = self.flags()
        return int(f["--local-steps"]) * int(f["--batch"]) * int(f["--seq-len"])


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = BENCH) -> Cell:
    workload = _load_json(root / "workloads" / f"{name}.json")
    config = _load_json(root / "configs" / f"{workload['config']}.json")
    traffic = _load_json(root / "traffic" / f"{workload['traffic']}.json")
    return Cell(name, workload, config, traffic)


def load_module(path: Path):
    """Import a reader or check file by path (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_check(cell: Cell, seed: int, root: Path = BENCH):
    return load_module(root / "checks" / f"{cell.workload['check']}.py").Check(cell, seed)


def load_peaks(device_kind: str, root: Path = BENCH) -> dict:
    peaks = _load_json(root / "peaks.json")
    if device_kind not in peaks:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json; add its "
            f"published peaks with their source"
        )
    return peaks[device_kind]


def program_config(cell: Cell):
    """The trainer's ModelConfig: the registered architecture with every model
    number of the configuration file laid over it."""
    from repro.configs import get_config

    c = cell.config
    return dataclasses.replace(
        get_config(c["arch"]), n_layers=c["n_layers"], d_model=c["d_model"],
        n_heads=c["n_heads"], n_kv_heads=c["n_heads"], d_ff=c["d_ff"],
        vocab_size=c["vocab_size"], max_seq_len=c["max_seq_len"], **c["model"],
    )


def trainer_argv(cell: Cell, seed: int, log_path: str) -> List[str]:
    argv = []
    for k, v in cell.flags().items():
        if v is True:
            argv.append(k)
        elif v is not False:
            argv += [k, str(v)]
    return argv + ["--seed", str(seed), "--log", log_path]


def admitted_tokens(row: dict, cell: Cell) -> int:
    """Training tokens of the client deltas a history row admitted into the
    global model: τ·B·S per admitted client (sync: the round's contributors;
    async: the update's buffer fill)."""
    if "contributors" in row:
        n = len([c for c in str(row["contributors"]).split(",") if c != ""])
    else:
        n = int(round(float(row["buffer_fill"])))
    return n * cell.tokens_per_client_round()


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------


class WindowClosed(Exception):
    """Raised out of the trainer's log call to end the run at a round boundary."""


class Session:
    """Receives the trainer's history rows and keeps the window's clock."""

    def __init__(self, cell: Cell, seconds: float, check, tracer=None):
        self.cell = cell
        self.seconds = float(seconds)
        self.check = check
        self.tracer = tracer
        self.rows: List[dict] = []
        self.times: List[float] = []
        self.window_start: Optional[float] = None
        self.window_end: Optional[float] = None

    def on_row(self, row: dict) -> None:
        t = time.perf_counter()
        i = len(self.rows)
        self.rows.append(dict(row))
        self.times.append(t)
        n = self.cell.setup_rounds
        if i < n:
            self.check.on_setup_row(i, row)
            if i == n - 1:
                if self.tracer is not None:
                    self.tracer.start()
                self.window_start = time.perf_counter()
            return
        if self.tracer is not None:
            self.tracer.mark_boundary()
        if t - self.window_start >= self.seconds:
            self.window_end = t
            if self.tracer is not None:
                self.tracer.stop()
            raise WindowClosed

    @property
    def window_rows(self) -> List[dict]:
        return self.rows[self.cell.setup_rounds:]

    def logger_factory(self) -> Callable:
        session = self

        class RoundLog:
            """Stands in for the trainer's MetricLogger; writes nothing."""

            def __init__(self, path, fieldnames=None):
                self.path = path

            def log(self, row):
                session.on_row(row)

        return RoundLog


@contextlib.contextmanager
def patched(module, name: str, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What the metric readers read."""

    cell: Cell
    peaks: dict
    setup_s: float
    window_s: float
    window_tokens: int
    memory_peak_bytes: int
    trace: Any = None  # devtrace.TraceSummary in a traced run


def read_metrics(kind_dir: str, ctx: Context, root: Path = BENCH) -> Dict[str, dict]:
    out = {}
    for path in sorted((root / kind_dir).glob("*.py")):
        mod = load_module(path)
        value = mod.read(ctx)
        if value is not None:
            out[path.stem] = {"value": value, "unit": mod.UNIT}
    return out


def memory_peak_bytes(device) -> int:
    """The device allocator's reserved peak. On the TPU v5e the round's
    temporaries show in ``peak_bytes_reserved`` and not in
    ``peak_bytes_in_use``; a device that does not report it is an error."""
    stats = device.memory_stats() or {}
    if "peak_bytes_reserved" not in stats:
        raise RuntimeError(f"{device.device_kind} reports no peak_bytes_reserved: {sorted(stats)}")
    return int(stats["peak_bytes_reserved"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             out_dir: Path, root: Path = BENCH, check=None) -> dict:
    """Runs the cell once and returns the result object (without printing).

    ``t0`` is the process's start on the ``time.perf_counter`` clock. A
    caller may pass the cell's ``check`` object to read it afterwards."""
    import jax

    from repro.launch import train
    from repro.launch.compile_env import enable_compile_cache

    # the cache sits in this checkout, and every program goes into it, however
    # fast it compiled, so that only a checkout's first run compiles. Nothing
    # is evicted: with eviction on (a size limit set in the environment), one
    # entry written without eviction's access-time file fails every later write
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    device = devices[0]
    peaks = load_peaks(device.device_kind, root)

    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    if check is None:
        check = load_check(cell, seed, root)
    tracer = None
    if trace:
        import devtrace

        tracer = devtrace.WindowTracer(out_dir / "trace")
    session = Session(cell, seconds, check, tracer)
    argv = trainer_argv(cell, seed, str(out_dir / "rows.csv"))

    with contextlib.ExitStack() as stack:
        stack.enter_context(patched(train, "MetricLogger", session.logger_factory()))
        check.install(stack, train)
        try:
            train.run(train.parse_args(argv), cfg=program_config(cell))
        except WindowClosed:
            pass
        else:
            raise RuntimeError(
                f"the job's {cell.flags()['--rounds']} rounds ended before the "
                f"window closed; raise --rounds in the traffic file"
            )

    peak = memory_peak_bytes(device)
    setup_rows = [round(t - t0, 3) for t in session.times[: cell.setup_rounds]]
    print(f"set-up rounds logged at {setup_rows} s after the process started; window "
          f"opened at {session.window_start - t0!r} s", file=sys.stderr)
    rows = session.window_rows
    compiled = [r["compiles"] for r in rows]
    if any(c != 0 for c in compiled):
        raise RuntimeError(f"the window compiled programs: compiles per round {compiled}")
    check.release_program()
    gc.collect()

    ctx = Context(
        cell=cell, peaks=peaks,
        setup_s=session.window_start - t0,
        window_s=session.window_end - session.window_start,
        window_tokens=sum(admitted_tokens(r, cell) for r in rows),
        memory_peak_bytes=peak,
    )
    device_info = {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }
    result: Dict[str, Any] = {}
    if trace:
        ctx.trace = tracer.summarise()
        metrics = read_metrics("metrics", ctx, root)
        device_info["busy_s"] = ctx.trace.busy_s
        device_info["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()
        for line in ctx.trace.notes:
            print(line, file=sys.stderr)
    else:
        metrics = read_metrics("end_to_end", ctx, root)

    t_check = time.perf_counter()
    correct, checks = check.verify()
    print(f"the reference took {time.perf_counter() - t_check!r} s", file=sys.stderr)
    failed = sum(1 for r in rows if not math.isfinite(float(r["train_loss"])))
    out = {
        "correct": bool(correct) and failed == 0,
        "attempted": len(rows),
        "failed": failed,
        "metrics": metrics,
        "device": device_info,
        **result,
        "checks": checks,
    }
    return out
