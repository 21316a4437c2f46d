"""The reader of ``moe.live_row_share``: the window's rows' live slots over the
rows the routed part was sized for, and nothing where the rows lack the
routed-rows counter (a program that sizes the routed part by every slot)."""
import pytest


def _read(cell, root, rows):
    import harness
    from test_spans import SCOPES, _ctx, _profile

    return harness.read_metrics("metrics", _ctx(cell, _profile(), SCOPES, rows), root)


def test_live_row_share_reads_the_window_rows(tiny_cell, tiny_root):
    # the first 3 rows are the set-up rounds, left out of the window
    rows = [{"moe_local_slots": 1.0, "moe_routed_rows": 3072.0}] * 3 + [
        {"moe_local_slots": 1200.0, "moe_routed_rows": 3072.0},
        {"moe_local_slots": 1872.0, "moe_routed_rows": 3072.0}]
    got = _read(tiny_cell, tiny_root, rows)
    assert got["moe.live_row_share"] == {"value": pytest.approx(50.0), "unit": "%"}


def test_live_row_share_is_left_out_without_the_counter(tiny_cell, tiny_root):
    rows = [{"moe_local_slots": 1237.0}] * 5  # a program whose rows lack moe_routed_rows
    assert "moe.live_row_share" not in _read(tiny_cell, tiny_root, rows)
    assert "moe.live_row_share" not in _read(tiny_cell, tiny_root, [])
