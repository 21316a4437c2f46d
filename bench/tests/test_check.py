"""The correctness check fails what it must: the control, and a run whose timed
path is broken underneath (the harness's look for a chip skipped)."""
import jax
import jax.numpy as jnp
import pytest

from conftest import run_tiny

SIDES = [("fp8", None), ("f32", "half_batch"), ("f32", "no_exchange")]


@pytest.mark.parametrize("precision,fault", SIDES)
def test_control_and_planted_faults_fail_a_limit(tiny_cell, tiny_root, tmp_path, precision, fault):
    import harness

    check = harness.load_check(tiny_cell, 3_000_000_017, tiny_root)
    run_tiny(tiny_cell, tiny_root, tmp_path / "out", check=check)
    gaps = check.compare(check.reference_readings(precision, fault), check.reference)
    limits = tiny_cell.workload["limits"]
    assert any(gaps[k] > limits[k] for k in limits), gaps


def _broken_round(real, fault):
    from repro.core.federated import apply_aggregate

    def state_unchanged(loss_fn, fed, state, batches, **kw):
        new_state, metrics = real(loss_fn, fed, state, batches, **kw)
        return dict(new_state, params=state["params"]), metrics

    def half_batch(loss_fn, fed, state, batches, **kw):
        def half(p, b):
            return loss_fn(p, {**b, "tokens": b["tokens"][: b["tokens"].shape[0] // 2]})

        return real(half, fed, state, batches, **kw)

    def no_exchange(loss_fn, fed, state, batches, **kw):
        def one_client(fed, s, deltas, client_weights=None, codec=None):
            first = jax.tree_util.tree_map(lambda d: jnp.broadcast_to(d[:1], d.shape), deltas)
            return apply_aggregate(fed, s, first, client_weights=client_weights, codec=codec)

        return real(loss_fn, fed, state, batches, **dict(kw, apply_fn=one_client))

    return {"state_unchanged": state_unchanged, "half_batch": half_batch,
            "no_exchange": no_exchange}[fault]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "no_exchange"])
def test_a_broken_timed_path_reads_not_correct(tiny_cell, tiny_root, tmp_path, monkeypatch, fault):
    from repro.core import aggregator

    monkeypatch.setattr(aggregator, "federated_round",
                        _broken_round(aggregator.federated_round, fault))
    result = run_tiny(tiny_cell, tiny_root, tmp_path / "out")
    assert result["correct"] is False, result["checks"]


def test_a_sound_window_counts_whole_rounds(tiny_cell, tiny_root, tmp_path):
    result = run_tiny(tiny_cell, tiny_root, tmp_path / "out", seconds=1.0)
    assert result["attempted"] >= 1 and result["failed"] == 0
    m = result["metrics"]
    assert set(m) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
    assert m["tokens_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert list(result)[-1] == "checks"
