"""The LM head's reader (``metrics/client.head_ms.py``) on synthetic op names
over the hand-written trace of ``test_spans.py``: fusion.a (200 ns a run),
fusion.b (150 ns), and fusion.e, the eval program's op (50 ns)."""
import pytest

import harness
from test_spans import SCOPES, _ctx, _profile

BODY = "jit(fed_round)/client/while/body/closed_call"
FWD = BODY + "/vmap(jvp(fwd))/head"
# the custom backward rule's ops: its own scope under the transposed forward's
BWD = BODY + "/vmap(transpose(vmap(jvp(fwd))))/head/head"
MS = 1e-6


def _read(tiny_cell, tiny_root, **ops):
    scopes = {"jit_fed_round": dict(SCOPES["jit_fed_round"], **ops),
              "jit_eval_ce": {"fusion.e": "jit(eval_ce)/head/td,vd->tv/dot_general"}}
    return harness.read_metrics("metrics", _ctx(tiny_cell, _profile(), scopes, []), tiny_root)


@pytest.mark.parametrize("op_name,counted", [
    (FWD + "/td,vd->tv/dot_general", True),
    (FWD + "/reduce_sum;while/body/closed_call", True),
    (BWD + "/tv,td->vd/dot_general", True),
    (BODY + "/vmap(transpose(jvp(fwd)))/head/add_any", True),
    # outside the client phase, or outside the head: not the head
    ("jit(fed_round)/server/head/add", False),
    ("jit(fed_round)/head/reduce_sum", False),
    (BODY + "/vmap(jvp(fwd))/attn/dot_general", False),
])
def test_head_ops_of_op_names(tiny_cell, tiny_root, op_name, counted):
    got = _read(tiny_cell, tiny_root, **{"fusion.a": op_name})
    if counted:
        assert got["client.head_ms"] == {"value": pytest.approx(200 * MS), "unit": "ms"}
    else:
        assert "client.head_ms" not in got


def test_head_reader_with_the_model_parts(tiny_cell, tiny_root):
    """The head's forward and backward both count, the eval program's head op
    does not; the backward rule's ops stay in ``client.bwd_ms``."""
    got = _read(tiny_cell, tiny_root, **{"fusion.a": FWD + "/td,vd->tv/dot_general",
                                         "fusion.b": BWD + "/tv,vd->td/dot_general"})
    assert got["client.head_ms"]["value"] == pytest.approx(350 * MS)
    assert got["client.fwd_ms"]["value"] == pytest.approx(200 * MS)
    assert got["client.bwd_ms"]["value"] == pytest.approx(150 * MS)
    assert got["eval.device_ms"]["value"] == pytest.approx(50 * MS)


def test_head_reader_leaves_out_a_program_without_the_scope(tiny_cell, tiny_root):
    got = harness.read_metrics("metrics", _ctx(tiny_cell, _profile(), SCOPES, []), tiny_root)
    assert "client.fwd_ms" in got
    assert "client.head_ms" not in got
