"""The reduction of the program's spans and scopes (``spans.py``) and the
readers built on it: on a small trace written by hand as an XSpace text proto,
on the same readers over a program without spans (they leave their metrics
out), and on a trace recorded on the chip (``bench/testdata``)."""
import gzip
import json
import types

import pytest

import devtrace
import harness
import spans
from conftest import BENCH
from test_devtrace import _trace

NEW = ("data.wait_ms", "loop.wait_ms", "idle.untraced_ms", "eval.device_ms",
       "client.fwd_ms", "client.bwd_ms", "client.opt_ms", "server.device_ms",
       "setup.programs_s")

LOOP = "%while.1 = (f32[2]) while(f32[2] %p), condition=%c, body=%b"
FA = "%fusion.a = f32[2] fusion(f32[2] %p), kind=kLoop, calls=%fa"
FB = "%fusion.b = f32[2] fusion(f32[2] %fusion.a), kind=kOutput, calls=%fb"
FO = "%fusion.o = f32[2] fusion(f32[2] %fusion.b), kind=kLoop, calls=%fo"
FS = "%fusion.s = f32[2] fusion(f32[2] %fusion.o), kind=kLoop, calls=%fs"
FE = "%fusion.e = f32[2] fusion(f32[2] %q), kind=kLoop, calls=%fe"
SCOPES = {"jit_fed_round": {
    "while.1": "jit(fed_round)/client/while",
    "fusion.a": "jit(fed_round)/client/while/body/closed_call/vmap(jvp(fwd))/dot_general",
    "fusion.b": "jit(fed_round)/client/while/body/closed_call/vmap(transpose(jvp(fwd)))/transpose",
    "fusion.o": "jit(fed_round)/client/while/body/closed_call/vmap(opt)/mul;while/body",
    "fusion.s": "jit(fed_round)/server/add",
}}


def _round(t0):
    """One round program run at t0 ns and an eval run: ops nested in a loop."""
    return ([("jit_fed_round(7)", t0 + 100, t0 + 600), ("jit_eval_ce(9)", t0 + 700, t0 + 750)],
            [(LOOP, t0 + 100, t0 + 550), (FA, t0 + 100, t0 + 300), (FB, t0 + 350, t0 + 500),
             (FO, t0 + 500, t0 + 550), (FS, t0 + 560, t0 + 600), (FE, t0 + 700, t0 + 750)])


def _profile(named=True):
    from jax.profiler import ProfileData

    (m0, o0), (m1, o1) = _round(0), _round(1000)
    modules, ops = m0 + m1, o0 + o1
    host = [("bench.boundary", 90, 90), ("bench.boundary", 1000, 1000),
            ("bench.boundary", 2000, 2000)]
    if named:
        host += [("obs.iter", 20, 1000), ("obs.launch", 95, 105), ("obs.sync", 105, 620),
                 ("obs.eval", 640, 760), ("obs.log", 800, 850), ("obs.plan", 900, 910),
                 ("obs.data", 910, 1000), ("obs.iter", 1000, 1990), ("obs.h2d", 1000, 1090),
                 ("obs.launch", 1090, 1105), ("obs.sync", 1105, 1650)]
    else:
        modules = [(n.replace("jit_fed_round(7)", "jit__lambda(7)")
                    .replace("jit_eval_ce(9)", "jit__lambda(9)"), s, e) for n, s, e in modules]
    device = ("/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops})
    return ProfileData.from_text_proto(_trace([device, ("/host:CPU", {"python3": host})]))


@pytest.mark.parametrize("op_name,part", [
    ("jit(fed_round)/while/body/closed_call/client/vmap(jvp(fwd))/dot_general", "fwd"),
    ("jit(fed_round)/client/while/body/vmap(transpose(jvp(fwd)))/transpose", "bwd"),
    ("jit(fed_round)/client/while/body/closed_call/vmap(opt)/sub", "opt"),
    ("jit(fed_round)/client/while/body/opt/select_n", "opt"),
    ("jit(fed_round)/client/while/body/dynamic_update_slice", "client"),
    ("jit(fed_round)/server/reduce_sum", "server"),
    ("jit(fed_round)/client/while/body/vmap(opt)/mul;while/body/closed_call", "opt"),
    ("jit(fed_round)/broadcast_in_dim", None),
])
def test_scope_buckets(op_name, part):
    assert spans.bucket(op_name) == part


def test_reduction_of_spans_and_scopes_by_hand():
    pd = _profile()
    s = spans.reduce_profile(pd)
    d = devtrace.reduce_profile(pd)
    ns = 1e-9
    assert s.rounds == d.rounds == 2
    # idle 830 ns in the window: 10 + 100 (round 1's sync 20 and eval 60, untraced 20)
    # + 10 + 350 (eval 10, log 50, plan 10, data 90, h2d 90, launch 10, untraced 90) + ...
    assert s.idle == pytest.approx({"data": 190 * ns, "loop": 225 * ns, "untraced": 415 * ns})
    # the attribution sums to the device's idle time exactly
    assert s.idle_s == pytest.approx(d.window_s - d.busy_s, rel=1e-12)
    assert s.modules["jit_fed_round"][0] == 2 and s.modules["jit_eval_ce"][0] == 2
    parts = s.per_run_ms("jit_fed_round", SCOPES["jit_fed_round"])
    ms = 1e-6
    assert parts == pytest.approx({"fwd": 200 * ms, "bwd": 150 * ms, "opt": 50 * ms,
                                   "server": 40 * ms, "client": 50 * ms})
    # the parts are the round program's device time per run, whole
    seconds, runs = d.modules["jit_fed_round(7)"]
    assert sum(parts.values()) == pytest.approx(1e3 * seconds / runs)
    # an op the table lacks is counted, not dropped
    assert s.per_run_ms("jit_fed_round", {})["unknown"] == pytest.approx(490 * ms)


def _ctx(cell, pd, scopes, rows):
    ctx = harness.Context(
        cell=cell, peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        setup_s=1.0, window_s=1.0, window_tokens=1, memory_peak_bytes=0,
        trace=devtrace.reduce_profile(pd))
    ctx.span_summary = spans.reduce_profile(pd)
    ctx.scopes = scopes
    ctx.rows = rows
    return ctx


def test_readers_of_spans_and_scopes(tiny_cell, tiny_root):
    rows = [{"compile_s": 2.5}, {"compile_s": 0.25}, {"compile_s": 0.0}, {"compile_s": 0.0}]
    got = harness.read_metrics("metrics", _ctx(tiny_cell, _profile(), SCOPES, rows), tiny_root)
    assert set(NEW) <= set(got)
    v = {k: got[k]["value"] for k in NEW}
    ms = 1e-6
    assert v == pytest.approx({
        "data.wait_ms": 95 * ms, "loop.wait_ms": 112.5 * ms, "idle.untraced_ms": 207.5 * ms,
        "eval.device_ms": 50 * ms, "client.fwd_ms": 200 * ms, "client.bwd_ms": 150 * ms,
        "client.opt_ms": 50 * ms, "server.device_ms": 40 * ms, "setup.programs_s": 2.75})
    assert got["setup.programs_s"]["unit"] == "s"
    # the existing readers read as before
    assert got["round.device_ms"]["value"] == pytest.approx(490 * ms)


def test_readers_leave_out_what_an_older_program_lacks(tiny_cell, tiny_root):
    """No ``obs.*`` span, unnamed modules, no scope table, rows without
    ``compile_s``: each new metric is left out, and nothing raises."""
    ctx = _ctx(tiny_cell, _profile(named=False), {}, [{"compiles": 3}] * 4)
    assert ctx.span_summary is None
    got = harness.read_metrics("metrics", ctx, tiny_root)
    assert not set(NEW) & set(got)
    assert {"device.idle_share", "round.device_ms"} <= set(got)


def test_rows_and_trace_are_found_in_the_harness_call(tiny_cell, tmp_path):
    """Where the Context lacks them, the rows and the trace's directory come
    from the ``run_cell`` call that runs the readers."""
    ctx = types.SimpleNamespace(cell=tiny_cell, trace=object())

    def run_cell():
        session = types.SimpleNamespace(rows=[{"compile_s": 1.0}])
        tracer = types.SimpleNamespace(log_dir=tmp_path)
        return spans.rows(ctx), spans._trace_path(ctx), session, tracer

    rows, path, _, _ = run_cell()
    assert rows == [{"compile_s": 1.0}] and path is None  # no profile written there
    assert spans.rows(ctx) is None


def test_spans_and_scopes_of_a_trace_recorded_on_the_chip():
    """A 0.1 s window of a tiny sync cell (2 layers of width 128, S = 128, two
    clients of 2 sequences, τ = 2) on one TPU v5e, with the program's profiler
    lane and named programs, and the scope table ``op_scopes()`` gave after it
    (``record_tiny_trace.py``)."""
    from jax.profiler import ProfileData

    data = BENCH / "testdata"
    raw = gzip.decompress((data / "tiny-sync-v5e-spans.xplane.pb.gz").read_bytes())
    scopes = json.loads(gzip.decompress((data / "tiny-sync-v5e-spans.scopes.json.gz").read_bytes()))
    pd = ProfileData.from_serialized_xspace(raw)
    d = devtrace.reduce_profile(pd)
    s = spans.reduce_profile(pd)
    assert {spans.module_base(k) for k in d.modules} == {"jit_fed_round", "jit_eval_ce"}
    assert all(runs == d.rounds for _, runs in d.modules.values())
    assert {k.split("(")[0] for k in d.idle_after} <= {
        "after jit_fed_round", "after jit_eval_ce", "before any module"}
    # every child of the loop's iterations that runs here is on the host plane
    assert {"obs.plan", "obs.data", "obs.h2d", "obs.launch", "obs.sync", "obs.eval",
            "obs.log"} <= set(s.spans)
    assert s.rounds == d.rounds
    assert s.idle_s == pytest.approx(d.window_s - d.busy_s, rel=1e-9)
    assert s.idle["untraced"] < 0.1 * s.idle_s
    # the scope table names every op of the round program, and its parts add
    # up to the program's device time per run
    parts = s.per_run_ms("jit_fed_round", scopes["jit_fed_round"])
    assert "unknown" not in parts
    assert all(parts[k] > 0 for k in ("fwd", "bwd", "opt", "server")), parts
    seconds, runs = next(v for k, v in d.modules.items() if k.startswith("jit_fed_round"))
    assert sum(parts.values()) == pytest.approx(1e3 * seconds / runs, rel=1e-6)
    assert sum(parts[k] for k in ("fwd", "bwd", "opt", "server")) > 0.9 * sum(parts.values())
