"""The attention core's readers (``attn_parts.py``, ``client.attn_ms``,
``attn.kernel_share``) on synthetic op names and on the hand-written trace of
``test_spans.py``."""
import pytest

import attn_parts
import harness
from test_spans import SCOPES, _ctx, _profile

FWD = "jit(fed_round)/client/while/body/closed_call/vmap(jvp(fwd))/while/body/attn"
BWD = "jit(fed_round)/client/while/body/closed_call/vmap(transpose(jvp(fwd)))/while/body"


@pytest.mark.parametrize("op_name,want", [
    (FWD + "/dot_general", ("fwd", False)),
    (FWD + "/jit(flash_attention)/flash_fwd/pallas_call", ("fwd", True)),
    (BWD + "/attn/transpose", ("bwd", False)),
    (BWD + "/attn/jit(flash_attention)/flash_bwd/pallas_call", ("bwd", True)),
    # the segment itself wrapped by a transformation still counts
    (BWD + "/transpose(jvp(attn))/flash_bwd/pallas_call", ("bwd", True)),
    (BWD + "/attn/exp;while/body/closed_call", ("bwd", False)),
    # outside the client phase, or outside the core: not attention
    ("jit(eval_ce)/attn/jit(flash_attention)/flash_fwd/pallas_call", None),
    ("jit(fed_round)/server/attn/add", None),
    (BWD + "/dot_general", None),
    ("jit(fed_round)/client/while/body/pallas_call", None),
])
def test_attention_parts_of_op_names(op_name, want):
    assert attn_parts.part(op_name) == want


def test_attention_readers(tiny_cell, tiny_root):
    """fusion.a (200 ns a run) is the forward's jnp op, fusion.b (150 ns) the
    backward's kernel; the rest of the round is not attention."""
    scopes = {"jit_fed_round": dict(
        SCOPES["jit_fed_round"],
        **{"fusion.a": FWD + "/dot_general",
           "fusion.b": BWD + "/attn/jit(flash_attention)/flash_bwd/pallas_call"})}
    got = harness.read_metrics("metrics", _ctx(tiny_cell, _profile(), scopes, []), tiny_root)
    ms = 1e-6
    assert got["client.attn_ms"] == {"value": pytest.approx(350 * ms), "unit": "ms"}
    assert got["attn.kernel_share"] == {"value": pytest.approx(100 * 150 / 350), "unit": "%"}
    # the model's parts read as before: the core is part of the forward and backward
    assert got["client.fwd_ms"]["value"] == pytest.approx(200 * ms)
    assert got["client.bwd_ms"]["value"] == pytest.approx(150 * ms)


def test_attention_readers_leave_out_a_program_without_the_scope(tiny_cell, tiny_root):
    got = harness.read_metrics("metrics", _ctx(tiny_cell, _profile(), SCOPES, []), tiny_root)
    assert "client.fwd_ms" in got
    assert not {"client.attn_ms", "attn.kernel_share"} & set(got)
