"""Federation telemetry layer (PR 7).

Four layers, cheapest first:

1. **Event schema** — versioned round-trip, torn-tail tolerance vs loud
   interior corruption, incarnation-keyed span pairing.
2. **Tracer** — deterministic span ids, counters/gauges/ring, and the no-op
   guarantee: the disabled tracer records nothing and costs (almost) nothing.
3. **Exports** — a golden Chrome-trace conversion on synthetic fixed-clock
   events, round rollups, the Prometheus endpoint, report-CLI invariants.
4. **Read-only invariant** — the tentpole acceptance: an async federation run
   with tracing ON is BITWISE the run with tracing OFF (plain, int8, and the
   top-k error-feedback lane), because the tracer only reads host floats the
   metrics path already computed.

Satellite coverage rides along: the MetricLogger schema-growth fix (a late
``val_ppl`` column must widen the CSV, not vanish).
"""
import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batches, make_params, quad_loss, sgd_inner
from repro.core import (
    AsyncAggConfig,
    AsyncFederationDriver,
    FederatedConfig,
    Int8Codec,
    OuterOptConfig,
    ParticipationConfig,
    STRAGGLER_PROFILES,
    SyncAggregator,
    TopKCodec,
)
from repro.metrics import MetricLogger
from repro.obs import (
    EVENT_SCHEMA_VERSION,
    Event,
    JsonlSink,
    MetricsServer,
    NULL_TRACER,
    Tracer,
    check_run,
    chrome_trace,
    decode_event,
    dispatch_table,
    encode_event,
    load_run,
    observe_staleness,
    read_events,
    render_metrics,
    round_rollups,
    span_pairs,
)


# ---------------------------------------------------------------------------
# Event schema + JSONL durability
# ---------------------------------------------------------------------------


def test_event_roundtrip_and_version_refusal():
    ev = Event(
        name="dispatch", ph="B", ts=1.5, mono=0.25, proc="server", pid=42,
        trace="seed3", span="d7", parent="u2", attrs={"index": 7, "client": 1},
    )
    back = decode_event(encode_event(ev))
    assert back == ev
    stale = encode_event(ev)
    stale["v"] = EVENT_SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema version"):
        decode_event(stale)
    with pytest.raises(ValueError, match="phase"):
        Event(name="x", ph="Z", ts=0, mono=0, proc="p", pid=1, trace="t")


def _mk(name, ph, ts, mono, proc="server", pid=1, span="", parent=None, attrs=None):
    return Event(name=name, ph=ph, ts=ts, mono=mono, proc=proc, pid=pid,
                 trace="t", span=span, parent=parent, attrs=attrs or {})


def test_jsonl_sink_appends_and_torn_tail_is_dropped(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    sink = JsonlSink(path)
    sink.emit(_mk("a", "i", 1.0, 1.0))
    sink.emit(_mk("b", "i", 2.0, 2.0))
    sink.close()
    # crash tears the final line mid-append: the torn event never committed
    with open(path, "a") as f:
        f.write('{"v":1,"name":"torn","ph":"i","ts":3.0')
    events = read_events(path)
    assert [e.name for e in events] == ["a", "b"]
    # a respawned incarnation appends to the same file
    sink2 = JsonlSink(path)
    sink2.emit(_mk("c", "i", 4.0, 4.0, pid=2))
    sink2.close()
    # ...but the torn fragment now sits INTERIOR to the log: that is real
    # corruption (the line-commit discipline cannot produce it) — loud error
    with pytest.raises(ValueError, match="corrupt event line"):
        read_events(path)


def test_read_events_raises_on_interior_corruption(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    good = json.dumps(encode_event(_mk("a", "i", 1.0, 1.0)))
    with open(path, "w") as f:
        f.write(good + "\n" + "NOT JSON\n" + good + "\n")
    with pytest.raises(ValueError, match=r"ev\.jsonl:2"):
        read_events(path)


def test_span_pairs_keyed_by_process_incarnation():
    events = [
        _mk("work", "B", 1.0, 1.0, proc="w0", pid=10, span="d0@w0", attrs={"i": 0}),
        # pid 10 died; respawned incarnation pid 11 reopens the SAME span id
        _mk("work", "B", 2.0, 1.0, proc="w0", pid=11, span="d0@w0"),
        _mk("end", "E", 3.0, 2.5, proc="w0", pid=11, span="d0@w0",
            attrs={"outcome": "pushed"}),
        _mk("end", "E", 4.0, 9.0, proc="w1", pid=20, span="never-opened"),
    ]
    closed, opened = span_pairs(events)
    assert len(closed) == 1  # pid 11's close never matches pid 10's open
    assert closed[0]["pid"] == 11
    assert closed[0]["dur"] == 1.5  # same-process mono delta
    assert closed[0]["attrs"] == {"outcome": "pushed"}
    assert [ev.pid for ev in opened] == [10]  # the dead incarnation stays open


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_tracer_spans_counters_gauges_and_ring(tmp_path):
    sink = JsonlSink(str(tmp_path / "t.jsonl"))
    tr = Tracer(sink, proc="server", trace_id="seed0", ring_size=3)
    sid = tr.begin("dispatch", span_id="d0", parent="u0", index=0)
    assert sid == "d0"
    with tr.span("train", span_id="d0/t", parent="d0"):
        pass
    tr.end("d0", outcome="admitted")
    tr.point("admit", parent="d0", accepted=True)
    tr.count("admits")
    tr.count("bytes", 128.0)
    tr.gauge("round", 2.0)
    snap = tr.snapshot()
    assert snap["counters"] == {"admits": 1.0, "bytes": 128.0}
    assert snap["gauges"] == {"round": 2.0}
    assert len(tr.ring) == 3  # bounded flight recorder, oldest evicted
    tr.close()
    events = read_events(str(tmp_path / "t.jsonl"))
    closed, opened = span_pairs(events)
    assert opened == []
    assert {c["span"]: c["name"] for c in closed} == {"d0": "dispatch",
                                                      "d0/t": "train"}
    d0 = next(c for c in closed if c["span"] == "d0")
    assert d0["parent"] == "u0"
    assert d0["attrs"]["outcome"] == "admitted"  # end-attrs land on the span
    assert events[-1].ph == "C"  # close() snapshots the counters
    assert events[-1].attrs["counters"]["admits"] == 1.0


def test_null_tracer_records_nothing_and_is_cheap():
    n = 100_000
    t0 = time.perf_counter()
    for i in range(n):
        NULL_TRACER.count("x")
        NULL_TRACER.point("y", index=i)
        NULL_TRACER.begin("s", span_id="a")
        NULL_TRACER.end("a")
        with NULL_TRACER.span("s", span_id="a"):
            pass
    dt = time.perf_counter() - t0
    assert NULL_TRACER.snapshot() == {"counters": {}, "gauges": {}}
    assert len(NULL_TRACER.ring) == 0
    # generous absolute guard: 500k disabled calls must stay trivially cheap
    # (no locks, no clocks, no allocation beyond the call itself; a span's
    # profiler lane, with no profile being taken, checks whether one is)
    assert dt < 2.0, f"{n} no-op tracer loops took {dt:.2f}s"


# ---------------------------------------------------------------------------
# Chrome export + rollups (golden, on fixed-clock synthetic events)
# ---------------------------------------------------------------------------


def _golden_events():
    return [
        _mk("round", "B", 10.0, 1.0, pid=100, span="u0",
            attrs={"round": 0, "track": 0}),
        _mk("dispatch", "B", 10.25, 1.25, pid=100, span="d0", parent="u0",
            attrs={"index": 0, "client": 2, "track": 3}),
        _mk("assignment", "B", 10.5, 5.0, proc="w0", pid=200, span="d0@w0",
            parent="d0"),
        _mk("end", "E", 10.75, 5.5, proc="w0", pid=200, span="d0@w0",
            parent="d0", attrs={"outcome": "pushed"}),
        _mk("admit", "i", 11.0, 1.75, pid=100, parent="d0",
            attrs={"accepted": True, "staleness": 1.0}),
        _mk("end", "E", 11.25, 2.0, pid=100, span="d0",
            attrs={"outcome": "admitted"}),
        # "u0" stays open: rendered with the remainder of the server timeline
    ]


def test_chrome_trace_golden():
    got = chrome_trace(_golden_events())
    assert got == {
        "traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "args": {"name": "server"}},
            {"ph": "M", "name": "process_name", "pid": 2, "tid": 0,
             "args": {"name": "w0"}},
            {"ph": "X", "name": "assignment", "pid": 2, "tid": 0,
             "ts": 10.5e6, "dur": 0.5e6, "cat": "fed",
             "args": {"outcome": "pushed", "span": "d0@w0"}},
            {"ph": "X", "name": "dispatch", "pid": 1, "tid": 3,
             "ts": 10.25e6, "dur": 0.75e6, "cat": "fed",
             "args": {"index": 0, "client": 2, "outcome": "admitted",
                      "span": "d0"}},
            {"ph": "X", "name": "round", "pid": 1, "tid": 0,
             "ts": 10.0e6, "dur": 1.0e6, "cat": "fed",
             "args": {"round": 0, "span": "u0", "unclosed": True,
                      "pid_real": 100}},
            {"ph": "i", "s": "p", "name": "admit", "pid": 1, "tid": 0,
             "ts": 11.0e6, "cat": "fed",
             "args": {"accepted": True, "staleness": 1.0}},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 0,
             "args": {"name": "main"}},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 3,
             "args": {"name": "slot c2"}},
            {"ph": "M", "name": "thread_name", "pid": 2, "tid": 0,
             "args": {"name": "main"}},
        ],
        "displayTimeUnit": "ms",
    }


def test_round_rollups_attribute_admits_to_their_flush():
    events = [
        _mk("admit", "i", 1.0, 1.0, attrs={"accepted": True, "staleness": 2.0}),
        _mk("admit", "i", 2.0, 2.0, attrs={"accepted": False, "staleness": 9.0}),
        _mk("flush", "i", 3.0, 3.0, attrs={"round": 0, "train_loss": 1.5}),
        _mk("admit", "i", 4.0, 4.0, attrs={"accepted": True, "staleness": 0.0}),
        _mk("flush", "i", 5.0, 5.0, attrs={"round": 1, "train_loss": 1.2}),
    ]
    rows = round_rollups(events)
    assert [r["round"] for r in rows] == [0, 1]
    assert rows[0]["n_admitted"] == 1 and rows[0]["n_rejected"] == 1
    assert rows[0]["staleness_admitted_max"] == 2.0  # rejected age not counted
    assert rows[1]["n_admitted"] == 1 and rows[1]["staleness_admitted_max"] == 0.0


# ---------------------------------------------------------------------------
# Metrics endpoint
# ---------------------------------------------------------------------------


def test_staleness_histogram_buckets_are_cumulative():
    tr = Tracer(proc="server")
    for s in (0.0, 1.0, 2.0, 5.0, 11.0):
        observe_staleness(tr, s)
    text = render_metrics(tr)
    assert 'fed_staleness_admitted_rounds_bucket{le="0"} 1' in text
    assert 'fed_staleness_admitted_rounds_bucket{le="1"} 2' in text
    assert 'fed_staleness_admitted_rounds_bucket{le="3"} 3' in text
    assert 'fed_staleness_admitted_rounds_bucket{le="7"} 4' in text
    assert 'fed_staleness_admitted_rounds_bucket{le="+Inf"} 5' in text
    assert "fed_staleness_admitted_rounds_sum 19" in text
    assert "fed_staleness_admitted_rounds_count 5" in text


def test_metrics_server_serves_prometheus_text():
    tr = Tracer(proc="server")
    tr.count("pushes", 3)
    tr.gauge("round", 7.0)
    srv = MetricsServer(tr, port=0, extra=lambda: {"workers_alive": 2})
    try:
        url = f"http://{srv.host}:{srv.port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert "# TYPE fed_pushes_total counter" in body
        assert "fed_pushes_total 3" in body
        assert "fed_round 7" in body
        assert "fed_workers_alive 2" in body  # live extras (worker liveness)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/nope", timeout=5
            )
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Report invariants
# ---------------------------------------------------------------------------


def test_check_run_flags_unclosed_spans_and_orphans():
    dispatch_open = _mk("dispatch", "B", 1.0, 1.0, span="d0",
                        attrs={"index": 0})
    assert check_run([dispatch_open])  # unclosed, no kill recorded → problem
    kill = _mk("fault", "i", 2.0, 2.0, attrs={"kind": "kill"})
    assert check_run([dispatch_open, kill]) == []  # crash is in the audit

    orphan = [
        _mk("assignment", "B", 1.0, 1.0, proc="w0", pid=9, span="d9@w0",
            parent="d9"),
        _mk("end", "E", 2.0, 2.0, proc="w0", pid=9, span="d9@w0", parent="d9"),
    ]
    problems = check_run(orphan)
    assert any("orphan" in p for p in problems)

    bad_outcome = [
        _mk("dispatch", "B", 1.0, 1.0, span="d0", attrs={"index": 0}),
        _mk("end", "E", 2.0, 2.0, span="d0", attrs={"outcome": "whatever"}),
    ]
    assert any("non-terminal" in p for p in check_run(bad_outcome))

    assert any("expected injected faults" in p
               for p in check_run([], expect_faults=True))


def test_dispatch_table_collects_leases_and_pushes():
    events = [
        _mk("dispatch", "B", 1.0, 1.0, span="d0",
            attrs={"index": 0, "client": 3, "version": 0}),
        _mk("lease_grant", "i", 1.1, 1.1, parent="d0",
            attrs={"index": 0, "worker": "w0", "regrant": False,
                   "expired": False}),
        _mk("lease_grant", "i", 1.5, 1.5, parent="d0",
            attrs={"index": 0, "worker": "w1", "regrant": True,
                   "expired": True}),
        _mk("push_recv", "i", 2.0, 2.0, parent="d0",
            attrs={"index": 0, "worker": "w1", "dup": False}),
        _mk("end", "E", 2.5, 2.5, span="d0",
            attrs={"outcome": "admitted", "staleness": 1.0}),
    ]
    (row,) = dispatch_table(events)
    assert row["outcome"] == "admitted"
    assert [l["worker"] for l in row["leases"]] == ["w0", "w1"]
    assert row["leases"][1]["expired"] is True
    assert [p["worker"] for p in row["pushes"]] == ["w1"]


# ---------------------------------------------------------------------------
# MetricLogger schema growth (satellite: the silent-field-drop fix)
# ---------------------------------------------------------------------------


def test_metric_logger_grows_schema_instead_of_dropping_fields(tmp_path):
    path = str(tmp_path / "log.csv")
    log = MetricLogger(path)
    log.log({"round": 0, "train_loss": 2.0})
    # the val_ppl column appears only later (eval rounds) — the old logger
    # silently discarded it forever; now the header widens atomically
    log.log({"round": 1, "train_loss": 1.5, "val_ppl": 33.0})
    log.log({"round": 2, "train_loss": 1.2, "val_ppl": 30.0})
    rows = log.read()
    assert [r["val_ppl"] for r in rows] == ["", "33.0", "30.0"]
    with open(path) as f:
        header = f.readline().strip().split(",")
    assert header == ["round", "train_loss", "val_ppl"]


def test_metric_logger_resume_unions_existing_header(tmp_path):
    path = str(tmp_path / "log.csv")
    MetricLogger(path).log({"round": 0, "train_loss": 2.0})
    # a resumed run constructs a fresh logger against the existing file and
    # logs a wider row: old rows pad, nothing is lost
    log2 = MetricLogger(path)
    log2.log({"round": 1, "train_loss": 1.5, "val_ppl": 28.0})
    rows = log2.read()
    assert [r["round"] for r in rows] == ["0.0", "1.0"]
    assert rows[0]["val_ppl"] == "" and rows[1]["val_ppl"] == "28.0"


# ---------------------------------------------------------------------------
# The read-only invariant: tracing changes NOTHING (bitwise)
# ---------------------------------------------------------------------------


def _cfgs(partial=False):
    tau = 3
    fed = FederatedConfig(
        clients_per_round=2, local_steps=tau, inner=sgd_inner(lr=0.05),
        outer=OuterOptConfig(name="fedadam", lr=0.3),
    )
    acfg = AsyncAggConfig(buffer_size=2, staleness_alpha=0.5, max_staleness=0)
    pcfg = ParticipationConfig(
        population=6, clients_per_round=2, dropout_rate=0.1,
        straggler=STRAGGLER_PROFILES["heavy"], weighting="uniform",
        partial_progress=partial, local_steps=tau if partial else 0,
    )
    mb = lambda cid: make_batches(tau, 1, seed=100 + cid)
    return fed, acfg, pcfg, mb


def _async_driver(codec, partial, tracer):
    fed, acfg, pcfg, mb = _cfgs(partial)
    return AsyncFederationDriver(
        quad_loss, fed, acfg, pcfg, mb, seed=3,
        params=make_params(), rng=jax.random.PRNGKey(0), codec=codec,
        tracer=tracer,
    )


@pytest.mark.parametrize(
    "codec,partial",
    [(None, False), (Int8Codec(), False), (TopKCodec(k_fraction=0.25), True)],
    ids=["plain", "int8", "topk-ef-partial"],
)
def test_tracing_leaves_async_run_bitwise_unchanged(codec, partial, tmp_path):
    ref = _async_driver(codec, partial, tracer=None)
    h_ref = ref.run_updates(5)

    tracer = Tracer(JsonlSink(str(tmp_path / "server.jsonl")), proc="server",
                    trace_id="seed3")
    drv = _async_driver(codec, partial, tracer=tracer)
    h = drv.run_updates(5)

    assert h == h_ref  # every host-side metric row, float for float
    t_ref, m_ref = ref.checkpoint()
    t, m = drv.checkpoint()
    assert m == m_ref
    for a, b in zip(jax.tree_util.tree_leaves(t_ref),
                    jax.tree_util.tree_leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    drv.finalize_trace()
    tracer.close()
    events = load_run(str(tmp_path))
    assert check_run(events) == []  # and the trace it left behind is coherent
    closed, _ = span_pairs(events)
    assert any(c["name"] == "dispatch" and c["attrs"].get("outcome") == "admitted"
               for c in closed)


def test_tracing_leaves_sync_round_bitwise_unchanged(tmp_path):
    tau, c = 2, 3
    fed = FederatedConfig(
        clients_per_round=c, local_steps=tau, inner=sgd_inner(),
        outer=OuterOptConfig(name="fedadam", lr=0.1),
    )
    pcfg = ParticipationConfig(population=4, clients_per_round=c)
    ref = SyncAggregator(
        quad_loss, fed, pcfg, seed=0, params=make_params(),
        rng=jax.random.PRNGKey(1),
    )
    tracer = Tracer(JsonlSink(str(tmp_path / "sync.jsonl")), proc="server")
    traced = SyncAggregator(
        quad_loss, fed, pcfg, seed=0, params=make_params(),
        rng=jax.random.PRNGKey(1), tracer=tracer,
    )
    for r in range(3):
        b = make_batches(tau, c, seed=70 + r)
        m_ref = {k: float(v) for k, v in ref.run_round(b, ref.plan(r)).items()}
        m_tr = {k: float(v) for k, v in traced.run_round(b, traced.plan(r)).items()}
        assert m_ref == m_tr
    for a, b in zip(jax.tree_util.tree_leaves(ref.state),
                    jax.tree_util.tree_leaves(traced.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tracer.close()
    closed, opened = span_pairs(read_events(str(tmp_path / "sync.jsonl")))
    assert opened == []
    assert [c["span"] for c in closed] == ["r0", "r1", "r2"]
    assert all("train_loss" in c["attrs"] for c in closed)


# ---------------------------------------------------------------------------
# The profiler lane, the sync loop's span tree, named programs and scopes
# ---------------------------------------------------------------------------


def _host_events(log_dir):
    """``{name: [(start_s, end_s)]}`` of the ``obs.*`` events on the host planes
    of the one profile under ``log_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(log_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("obs."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


@pytest.fixture(scope="module")
def profiled_sync_run(tmp_path_factory):
    """Two tiny sync rounds of the trainer under a ``jax.profiler`` trace, with
    the JSONL tracer on; then the scope table of the programs that ran."""
    from repro.launch.train import parse_args, run
    from repro.obs import programs

    tmp = tmp_path_factory.mktemp("profiled")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    argv = ["--arch", "photon-75m", "--reduced", "--rounds", "2", "--local-steps", "2",
            "--clients", "2", "--population", "4", "--batch", "2", "--seq-len", "32",
            "--eval-batches", "1", "--trace", str(tmp / "server.jsonl")]
    jax.profiler.start_trace(str(tmp / "profile"), profiler_options=opts)
    try:
        out = run(parse_args(argv))
    finally:
        jax.profiler.stop_trace()
    return {"history": out["history"], "host": _host_events(tmp / "profile"),
            "events": read_events(str(tmp / "server.jsonl")),
            "scopes": programs.op_scopes()}


def test_profiler_lane_nests_the_sync_loop_on_the_host_plane(profiled_sync_run):
    host = profiled_sync_run["host"]
    iters = sorted(host["obs.iter"])
    assert len(iters) == 2
    for child in ("obs.plan", "obs.data", "obs.h2d", "obs.launch", "obs.sync",
                  "obs.eval", "obs.log"):
        spans = host[child]
        assert len(spans) == 2, child
        for (s, e), (lo, hi) in zip(sorted(spans), iters):
            assert lo <= s <= e <= hi, child
    # no span opens around work a run skips: no controller, guard or checkpoint
    assert not {"obs.control", "obs.ckpt"} & set(host)


def test_iter_span_covers_the_iteration_and_row_seconds_is_its_duration(
        profiled_sync_run):
    events = profiled_sync_run["events"]
    assert span_pairs(events)[1] == []
    begin = {e.span: e for e in events if e.ph == "B"}
    end = {e.span: e.mono for e in events if e.ph == "E"}
    for row in profiled_sync_run["history"]:
        it = begin[f"i{row['round']}"]
        assert it.name == "iter" and it.attrs["round"] == row["round"]
        kids = sorted((b for b in begin.values() if b.parent == it.span),
                      key=lambda b: b.mono)
        assert [b.name for b in kids] == [
            "plan", "data", "h2d", "launch", "sync", "eval", "log"]
        assert all(it.mono <= b.mono <= end[b.span] <= end[it.span] for b in kids)
        # the round's own span sits inside launch
        launch, r = kids[3], begin[f"r{row['round']}"]
        assert launch.mono <= r.mono <= end[r.span] <= end[launch.span]
        # `seconds` is the iteration up to its last child, the log: eval included
        log, ev = kids[-1], kids[-2]
        assert row["seconds"] == pytest.approx(log.mono - it.mono, abs=1e-3)
        assert row["seconds"] >= end[ev.span] - it.mono


def test_rows_carry_compile_seconds(profiled_sync_run):
    rows = profiled_sync_run["history"]
    assert all(r["compile_s"] >= 0.0 for r in rows)
    assert rows[0]["compiles"] > 0 and rows[0]["compile_s"] > 0.0
    assert rows[1]["compiles"] == 0 and rows[1]["compile_s"] == 0.0


def _parts(table):
    """Count of the table's op_names in each part of the round program."""
    parts = {"fwd": 0, "bwd": 0, "opt": 0, "server": 0}
    for op in table.values():
        path = op.split(";")[0]
        if "/server/" in path:
            parts["server"] += 1
        elif "/client/" in path:
            if "transpose(" in path:
                parts["bwd"] += 1
            elif "(opt)" in path or "/opt/" in path:
                parts["opt"] += 1
            elif "fwd" in path:
                parts["fwd"] += 1
    return parts


def test_op_scopes_name_the_round_and_eval_programs(profiled_sync_run):
    scopes = profiled_sync_run["scopes"]
    assert {"jit_fed_round", "jit_eval_ce"} <= set(scopes)
    parts = _parts(scopes["jit_fed_round"])
    assert all(n > 0 for n in parts.values()), parts
    assert all(op.startswith("jit(eval_ce)/") for op in scopes["jit_eval_ce"].values()
               if op.startswith("jit("))


@pytest.mark.parametrize("tile", [None, 2], ids=["flat", "tiled"])
def test_op_scopes_split_the_quadratic_round(tile):
    from repro.obs import programs

    tau, c = 2, 2
    fed = FederatedConfig(
        clients_per_round=c, local_steps=tau, inner=sgd_inner(),
        outer=OuterOptConfig(name="fedadam", lr=0.1),
    )
    pcfg = ParticipationConfig(population=4, clients_per_round=c)
    agg = SyncAggregator(quad_loss, fed, pcfg, seed=0, params=make_params(),
                         rng=jax.random.PRNGKey(1), cohort_tile=tile)
    agg.run_round(make_batches(tau, c, seed=5), agg.plan(0))
    scopes = programs.op_scopes()
    if tile is None:
        parts = _parts(scopes["jit_fed_round"])
    else:
        parts = _parts(scopes["jit_fed_round_tile"])
        parts["server"] = _parts(scopes["jit_fed_round_server"])["server"]
    assert all(n > 0 for n in parts.values()), parts


def test_lane_modules_import_without_jax():
    import subprocess
    import sys

    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from repro.obs import programs, tracer\n"
        "with tracer.NULL_TRACER.span('x') as s: assert s == 'x'\n"
        "t = tracer.Tracer()\n"
        "with t.span('y', span_id='y0') as s: assert s == 'y0'\n"
        "assert 'jax' not in {m.split('.')[0] for m in sys.modules if sys.modules[m]}\n"
    )
    import os
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env)
