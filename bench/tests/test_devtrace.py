"""The trace reduction, checked by hand on intervals and on a small trace
written as an XSpace text proto with the planes and lines a TPU trace has, and
on a trace recorded on the chip (``bench/testdata``)."""
import gzip

import pytest

import devtrace
from conftest import BENCH


def test_union_clip_gaps_by_hand():
    busy = devtrace.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert devtrace.clip(busy, 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]
    assert devtrace.gaps(devtrace.clip(busy, -1.0, 5.0), -1.0, 5.0) == [
        (-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert devtrace.total(busy) == 3.0


def _trace(planes):
    """An XSpace text proto: planes of (name, {line: [(event, start_ns, end_ns)]})."""
    names, out = {}, []
    for pid, (plane, lines) in enumerate(planes, 1):
        body = []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = "".join(
                f"events {{ metadata_id: {names.setdefault(n, len(names) + 1)} "
                f"offset_ps: {s * 1000} duration_ps: {(e - s) * 1000} }} "
                for n, s, e in events)
            body.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 {evs}}}')
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
                       for n, i in names.items())
        out.append(f'planes {{ id: {pid} name: "{plane}" {" ".join(body)} {meta}}}')
    return "\n".join(out)


LOOP = "%while.1 = (f32[2]) while(f32[2] %p), condition=%c, body=%b"
FA = "%fusion.a = f32[2] fusion(f32[2] %p), kind=kLoop, calls=%fa"
FB = "%fusion.b = f32[2] fusion(f32[2] %fusion.a), kind=kOutput, calls=%fb"
FE = "%fusion.e = f32[2] fusion(f32[2] %q), kind=kLoop, calls=%fe"


def test_reduction_of_a_trace_by_hand():
    from jax.profiler import ProfileData

    device = ("/device:TPU:0", {
        "XLA Modules": [("jit_round", 100, 600), ("jit_eval", 700, 750),
                        ("jit_round", 1100, 1600), ("jit_eval", 1700, 1750)],
        "XLA Ops": [("early", 0, 50), (LOOP, 100, 600), (FA, 100, 300), (FB, 350, 600),
                    (FE, 700, 750), (FA, 1100, 1600), (FE, 1700, 1750)],
    })
    host = ("/host:CPU", {"python": [
        ("bench.boundary", 90, 90), ("PjitFunction(round)", 95, 99),
        ("bench.boundary", 1000, 1000), ("bench.boundary", 2000, 2000)]})
    s = devtrace.reduce_profile(ProfileData.from_text_proto(_trace([device, host])))
    ns = 1e-9
    assert s.rounds == 2
    assert s.window_s == pytest.approx(1910 * ns)
    assert s.busy_s == pytest.approx(1100 * ns)  # 500 + 50 + 500 + 50
    assert s.busiest_module() == "jit_round"
    assert s.modules["jit_round"][1] == 2
    assert s.modules["jit_round"][0] == pytest.approx(1000 * ns)
    assert s.modules["jit_eval"][0] == pytest.approx(100 * ns)
    # by self time: the loop's own 50 ns are what its body's ops leave of it
    assert s.ops == pytest.approx({"%while.1": 50 * ns, "%fusion.a kLoop": 700 * ns,
                                   "%fusion.b kOutput": 250 * ns, "%fusion.e kLoop": 100 * ns})
    # gaps 10 + 100 + 350 + 100 + 250: the first before any module ran, two
    # after a round program, two after an eval
    assert s.idle_after == pytest.approx(
        {"before any module": 10 * ns, "after jit_round": 200 * ns, "after jit_eval": 600 * ns})
    assert [k for k, _ in s.breakdown()["device_ops"]] == [
        "%fusion.a kLoop", "%fusion.b kOutput", "%fusion.e kLoop", "%while.1"]


def test_reduction_of_a_trace_recorded_on_the_chip():
    """A 0.1 s window of a tiny sync cell (2 layers of width 128, S = 128, two
    clients of 2 sequences, τ = 2) on one TPU v5e, with the harness's
    boundary marks."""
    from jax.profiler import ProfileData

    raw = gzip.decompress((BENCH / "testdata" / "tiny-sync-v5e.xplane.pb.gz").read_bytes())
    s = devtrace.reduce_profile(ProfileData.from_serialized_xspace(raw))
    assert s.rounds == 9
    assert s.window_s == pytest.approx(0.106988969)
    assert 0 < s.busy_s < s.window_s
    # two unnamed modules, the round program and eval, each once a round
    assert sorted(runs for _, runs in s.modules.values()) == [9, 9]
    assert s.busiest_module() == "jit__lambda(12753008656364859790)"
    # the ops nest properly, so their self times add up to the busy time
    assert sum(s.ops.values()) == pytest.approx(s.busy_s)
    assert sum(s.idle_after.values()) == pytest.approx(s.window_s - s.busy_s)
    ops = s.breakdown()["device_ops"]
    assert len(ops) == 10 and all(" = " not in name for name, _ in ops)
    assert ops[0][0] == "%fusion.566 kCustom"
