"""Population-scale federation (ISSUE 9): streamed cohorts, the sparse
error-feedback store, and two-tier aggregation.

Keystone identities:
  - the tiled round with ``cohort_tile == C`` is BITWISE the flat round
    (state, metrics, residual store — codec and partial-progress lanes
    included): one tile runs on the round's own rng lane and the partial-sum
    divide mirrors ``apply_aggregate`` op for op;
  - the sparse store is observably the dense ``(P, ...)`` store: a sync run
    through :class:`SyncAggregator` matches the pure dense
    ``federated_round_with_uplink`` reference bitwise on params and on every
    ever-selected client's residual row, while never materializing a row for
    a never-selected client;
  - a legacy dense-layout checkpoint (the PR-8 schema: ``(P, ...)`` residual
    lane, no ``uplink_ids`` in the manifest) still restores and replays.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import make_batches, make_params, quad_loss, sgd_inner

from repro.checkpoint import CheckpointManager
from repro.core import (
    STRAGGLER_PROFILES,
    FederatedConfig,
    OuterOptConfig,
    ParticipationConfig,
    RobustAggConfig,
    SparseResidualStore,
    SyncAggregator,
    TopKCodec,
    federated_round_with_uplink,
    hierarchical_mean,
    init_federated_state,
    init_uplink_residuals,
)


def _fed(c, tau, **kw):
    return FederatedConfig(
        clients_per_round=c, local_steps=tau, inner=sgd_inner(),
        outer=OuterOptConfig(name="fedavg", lr=1.0), **kw,
    )


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# two-tier aggregation: tiled round vs flat round
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", [None, TopKCodec(k_fraction=0.5)])
@pytest.mark.parametrize("partial", [False, True])
def test_tiled_round_single_tile_bitwise_flat(codec, partial):
    """``cohort_tile == C`` is ONE tile: the streamed round must be BITWISE
    the flat round — rng, DP, codec residuals and partial-progress τ-mask
    included (tile 0 runs on the round's own rng lane, and the tile's
    Σ wΔ + single divide mirrors the flat weighted mean op for op)."""
    tau, c = 3, 4
    fed = _fed(c, tau, dp_clip=0.5, dp_noise=0.01)
    pcfg = ParticipationConfig(
        population=8, clients_per_round=c, dropout_rate=0.3,
        straggler=STRAGGLER_PROFILES["heavy"], weighting="examples",
    )
    params = make_params()
    flat = SyncAggregator(
        quad_loss, fed, pcfg, codec=codec, seed=7, params=params,
        rng=jax.random.PRNGKey(9), partial_progress=partial, donate=False,
    )
    tiled = SyncAggregator(
        quad_loss, fed, pcfg, codec=codec, seed=7, params=params,
        rng=jax.random.PRNGKey(9), partial_progress=partial, donate=False,
        cohort_tile=c,
    )
    for r in range(3):
        b = make_batches(tau, c, seed=40 + r)
        m_f = flat.run_round(b, flat.plan(r))
        m_t = tiled.run_round(b, tiled.plan(r))
        _assert_trees_equal(flat.state, tiled.state)
        assert set(m_f) == set(m_t)
        for k in m_f:
            np.testing.assert_array_equal(
                np.asarray(m_f[k]), np.asarray(m_t[k]), err_msg=k
            )
    if codec is not None:
        assert flat.residual_store.ids() == tiled.residual_store.ids()
        _assert_trees_equal(
            flat.residual_store.stacked(), tiled.residual_store.stacked()
        )


@pytest.mark.parametrize("tile", [1, 2, 3])
def test_tiled_round_uneven_tiles_match_flat(tile):
    """C = 5 with tile widths that do NOT divide it: the last tile pads with
    zero-weight slots. Pads contribute exact zeros to Σ wΔ and never touch
    the residual store, so the only difference from the flat round is
    floating-point summation order — allclose, and the resulting stores hold
    identical rows for identical ids."""
    tau, c = 2, 5
    fed = _fed(c, tau)
    pcfg = ParticipationConfig(population=12, clients_per_round=c)
    codec = TopKCodec(k_fraction=0.5)
    params = make_params()
    flat = SyncAggregator(
        quad_loss, fed, pcfg, codec=codec, seed=3, params=params,
        rng=jax.random.PRNGKey(5), donate=False,
    )
    tiled = SyncAggregator(
        quad_loss, fed, pcfg, codec=codec, seed=3, params=params,
        rng=jax.random.PRNGKey(5), donate=False, cohort_tile=tile,
    )
    for r in range(2):
        b = make_batches(tau, c, seed=50 + r)
        flat.run_round(b, flat.plan(r))
        tiled.run_round(b, tiled.plan(r))
        np.testing.assert_allclose(
            np.asarray(flat.state["params"]["w"]),
            np.asarray(tiled.state["params"]["w"]),
            rtol=1e-5, atol=1e-6,
        )
    assert flat.residual_store.ids() == tiled.residual_store.ids()
    for cid in flat.residual_store.ids():
        np.testing.assert_allclose(
            np.asarray(flat.residual_store.row(cid)["w"]),
            np.asarray(tiled.residual_store.row(cid)["w"]),
            rtol=1e-5, atol=1e-6,
        )


@pytest.mark.parametrize(
    "options,match",
    [
        ({"cohort_tile": 2, "fused_server": True}, "fused-server"),
        ({"cohort_tile": 2, "keep_inner_state": True}, "inner state"),
        ({"fused_server": True, "robust": RobustAggConfig(rule="median")},
         "robust rule"),
        ({"cohort_tile": 2, "robust": RobustAggConfig(screen=True)}, "screen"),
        ({"cohort_tile": 2, "robust": RobustAggConfig(rule="normclip")},
         "absolute --clip-norm"),
    ],
    ids=["fused-tile", "keep-opt-tile", "fused-robust", "screen-tile",
         "adaptive-normclip-tile"],
)
def test_cohort_tile_rejects_fused_server_and_keep_opt(options, match):
    """The round builder's five compatibility rules, each refused when the
    aggregator is built (before any state is allocated)."""
    from dataclasses import replace

    options = dict(options)
    fed = replace(_fed(2, 2), keep_inner_state=options.pop("keep_inner_state", False))
    pcfg = ParticipationConfig(population=4, clients_per_round=2)
    with pytest.raises(ValueError, match=match):
        SyncAggregator(quad_loss, fed, pcfg, params=make_params(), **options)


# ---------------------------------------------------------------------------
# hierarchical_mean: uneven islands (satellite)
# ---------------------------------------------------------------------------


def test_hierarchical_mean_uneven_unweighted_raises_value_error():
    deltas = {"w": jnp.ones((5, 3))}
    with pytest.raises(ValueError, match="does not divide"):
        hierarchical_mean(deltas, 2)


def test_hierarchical_mean_uneven_weighted_pads_exactly():
    """The documented zero-weight-padding path: uneven islands under the
    weighted form equal the flat weighted mean (pads add exact zeros and the
    divide uses the real weight mass only)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, 3)), jnp.float32)
    w = jnp.asarray([0.5, 1.0, 0.0, 2.0, 0.25], jnp.float32)
    flat = (x * w[:, None]).sum(0) / w.sum()
    for n_groups in (2, 3, 4):
        out = hierarchical_mean({"w": x}, n_groups, weights=w)["w"]
        np.testing.assert_allclose(np.asarray(out), np.asarray(flat), rtol=1e-6)


# ---------------------------------------------------------------------------
# sparse residual store semantics at scale
# ---------------------------------------------------------------------------


def test_sync_sparse_store_matches_dense_reference_bitwise():
    """The production aggregator (sparse store, host gather/scatter) against
    the pure dense ``(P, ...)`` reference round with identical plans, weights
    and batches: params bitwise every round, every ever-selected client's
    residual row bitwise, and never-selected clients own NO row (their dense
    rows stay exactly zero)."""
    tau, c, population = 2, 3, 50
    fed = _fed(c, tau)
    pcfg = ParticipationConfig(population=population, clients_per_round=c)
    codec = TopKCodec(k_fraction=0.5)
    params = make_params()

    agg = SyncAggregator(
        quad_loss, fed, pcfg, codec=codec, seed=0, params=params,
        rng=jax.random.PRNGKey(1), donate=False,
    )
    dense_state = init_federated_state(fed, params, jax.random.PRNGKey(1))
    dense_state["uplink_residuals"] = init_uplink_residuals(
        codec, params, population
    )
    dense_fn = jax.jit(
        lambda s, b, w, sel: federated_round_with_uplink(
            quad_loss, fed, codec, s, b, client_weights=w, selected=sel
        )
    )

    selected = set()
    for r in range(4):
        plan = agg.plan(r)
        selected.update(int(i) for i in plan.selected)
        b = make_batches(tau, c, seed=60 + r)
        w = jnp.asarray(agg.round_weights(plan))
        agg.run_round(b, plan)
        dense_state, _ = dense_fn(dense_state, b, w, jnp.asarray(plan.selected))
        _assert_trees_equal(agg.state["params"], dense_state["params"])

    store = agg.residual_store
    dense_rows = np.asarray(dense_state["uplink_residuals"]["w"])
    # a client's row follows it across cohorts: after 4 rounds of re-selection
    # the sparse rows still match the dense store position-for-position
    for cid in sorted(selected):
        assert cid in store
        np.testing.assert_array_equal(
            np.asarray(store.row(cid)["w"]), dense_rows[cid]
        )
    # never-selected clients own no row — in either representation
    assert len(store) == len(selected) < population
    for cid in range(population):
        if cid not in selected:
            assert cid not in store
            np.testing.assert_array_equal(dense_rows[cid], 0.0)


def test_sparse_store_gather_scatter_and_dense_roundtrip():
    params = make_params()
    store = SparseResidualStore(params)
    assert len(store) == 0 and store.nbytes == 0
    # gather of never-materialized ids is the dense zero-row gather
    g = store.gather([3, 7])
    np.testing.assert_array_equal(np.asarray(g["w"]), 0.0)
    assert len(store) == 0  # gathering materializes nothing
    rows = {"w": jnp.stack([jnp.full((4, 4), 1.0), jnp.full((4, 4), 2.0)])}
    store.scatter([3, 7], rows, mask=np.array([True, False]))
    assert 3 in store and 7 not in store  # masked slots never write
    store.scatter([7], {"w": rows["w"][1:]})
    assert store.ids() == [3, 7]
    dense = store.to_dense(10)
    np.testing.assert_array_equal(np.asarray(dense["w"][3]), 1.0)
    np.testing.assert_array_equal(np.asarray(dense["w"][7]), 2.0)
    assert float(jnp.abs(dense["w"]).sum()) == float(
        jnp.abs(rows["w"]).sum()
    )  # every other row exactly zero
    # dense -> sparse drops the all-zero rows
    back = SparseResidualStore.from_dense(params, dense)
    assert back.ids() == [3, 7]
    _assert_trees_equal(back.stacked(), store.stacked())


def test_sync_restore_from_legacy_dense_checkpoint_replays_bitwise(tmp_path):
    """A PR-8 style checkpoint — dense ``(P, ...)`` residual lane, no
    ``uplink_ids`` in the manifest — restores into the sparse store and the
    continued run is BITWISE the uninterrupted one."""
    tau, c, population = 2, 2, 6
    fed = _fed(c, tau)
    pcfg = ParticipationConfig(population=population, clients_per_round=c)
    codec = TopKCodec(k_fraction=0.5)
    params = make_params()

    def _mk():
        return SyncAggregator(
            quad_loss, fed, pcfg, codec=codec, seed=11, params=params,
            rng=jax.random.PRNGKey(2), donate=False,
        )

    # uninterrupted: 4 rounds
    full = _mk()
    for r in range(4):
        full.run_round(make_batches(tau, c, seed=70 + r), full.plan(r))

    # interrupted at round 2, checkpointed in the LEGACY dense layout
    part = _mk()
    for r in range(2):
        part.run_round(make_batches(tau, c, seed=70 + r), part.plan(r))
    tree, manifest = part.checkpoint()
    tree["uplink_residuals"] = part.residual_store.to_dense(population)
    manifest = {k: v for k, v in manifest.items() if k != "uplink_ids"}
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save_server(1, tree, extra={"aggregator": manifest})

    # restore through the dense-template lane (uplink_ids=None -> (P, ...))
    like = SyncAggregator.checkpoint_template(fed, pcfg, params, codec=codec)
    restored, man = ckpt.load_server(1, like)
    agg2 = _mk()
    agg2.restore(restored, man["extra"]["aggregator"])
    assert agg2.residual_store.ids() == part.residual_store.ids()

    for r in range(2, 4):
        agg2.run_round(make_batches(tau, c, seed=70 + r), agg2.plan(r))
    _assert_trees_equal(full.state, agg2.state)
    assert full.residual_store.ids() == agg2.residual_store.ids()
    _assert_trees_equal(
        full.residual_store.stacked(), agg2.residual_store.stacked()
    )


def test_restore_rejects_unroutable_residual_layout():
    fed = _fed(2, 2)
    pcfg = ParticipationConfig(population=6, clients_per_round=2)
    agg = SyncAggregator(
        quad_loss, fed, pcfg, codec=TopKCodec(k_fraction=0.5),
        params=make_params(),
    )
    state = {k: v for k, v in agg.state.items()}
    # 3 rows is neither the population (6) nor manifest-described — ambiguous
    state["uplink_residuals"] = {"w": jnp.zeros((3, 4, 4))}
    with pytest.raises(ValueError, match="uplink_ids|population"):
        agg.restore(state, None)


# ---------------------------------------------------------------------------
# train.py wiring: --cohort-tile smoke + dense-checkpoint --resume
# ---------------------------------------------------------------------------


def test_train_cohort_tile_matches_flat_run():
    """The CLI wiring end to end: a tiled driver run produces the same history
    keys and a sane trajectory; with tile == K it is the flat run's math."""
    from repro.launch.train import parse_args, run

    common = [
        "--arch", "photon-75m", "--reduced", "--rounds", "2",
        "--local-steps", "2", "--clients", "2", "--population", "5",
        "--batch", "2", "--seq-len", "32", "--eval-batches", "1",
        "--uplink", "topk", "--topk-fraction", "0.25",
    ]
    flat = run(parse_args(common))
    tiled = run(parse_args(common + ["--cohort-tile", "2"]))
    assert [h["round"] for h in tiled["history"]] == [0, 1]
    assert tiled["history"][0]["selected"] == flat["history"][0]["selected"]
    # same math modulo XLA scheduling: loss trajectories agree tightly
    for hf, ht in zip(flat["history"], tiled["history"]):
        np.testing.assert_allclose(
            hf["train_loss"], ht["train_loss"], rtol=1e-4
        )
    agg = tiled["aggregator"]
    assert agg.cohort_tile == 2 and len(agg.residual_store) > 0


def test_train_cohort_tile_rejected_under_async():
    from repro.launch.train import parse_args, run

    args = parse_args([
        "--arch", "photon-75m", "--reduced", "--aggregation", "async",
        "--cohort-tile", "2", "--rounds", "1",
    ])
    with pytest.raises(SystemExit, match="sync only"):
        run(args)


@pytest.mark.slow  # two driver runs + a resume (~30s CPU)
def test_train_resume_from_dense_checkpoint(tmp_path):
    """--resume from a PR-8 dense checkpoint: rewrite a current checkpoint
    into the legacy schema (dense residual lane, no uplink_ids) and resume —
    the driver must route it through ``from_dense`` and continue."""
    import json
    import os

    from repro.launch.train import parse_args, run

    common = [
        "--arch", "photon-75m", "--reduced", "--local-steps", "2",
        "--clients", "2", "--population", "4", "--batch", "2",
        "--seq-len", "32", "--eval-batches", "1",
        "--uplink", "topk", "--topk-fraction", "0.25",
    ]
    r_full = run(parse_args(common + ["--rounds", "3"]))
    ck = str(tmp_path / "ck")
    run(parse_args(common + ["--rounds", "2", "--ckpt-dir", ck]))

    # rewrite round 1 into the PR-8 layout
    mgr = CheckpointManager(ck)
    latest = mgr.latest_round()
    man = mgr.load_manifest(latest)
    agg_man = man["extra"]["aggregator"]
    ids = agg_man.pop("uplink_ids")
    d = os.path.join(ck, f"round_{latest:06d}")
    blob = dict(np.load(os.path.join(d, "server.npz")))
    population = 4
    for key in list(blob):
        if "uplink_residuals" in key:
            sparse = blob[key]
            dense = np.zeros((population,) + sparse.shape[1:], sparse.dtype)
            dense[np.asarray(ids)] = sparse
            blob[key] = dense
    np.savez(os.path.join(d, "server.npz"), **blob)
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(man, f)

    r_resumed = run(parse_args(
        common + ["--rounds", "3", "--ckpt-dir", ck, "--resume"]
    ))
    assert [h["round"] for h in r_resumed["history"]] == [2]
    assert (
        r_resumed["history"][0]["selected"] == r_full["history"][2]["selected"]
    )
    lf = r_full["history"][-1]["train_loss"]
    lr = r_resumed["history"][-1]["train_loss"]
    assert abs(lf - lr) / lf < 0.10, (lf, lr)
    # the resumed aggregator holds a sparse store again (flat memory): the
    # dense lane's nonzero rows came back, plus whatever round 2 selected
    resumed_ids = set(r_resumed["aggregator"].residual_store.ids())
    assert set(ids) <= resumed_ids
    assert resumed_ids <= set(ids) | {
        int(s) for s in r_resumed["history"][0]["selected"].split(",")
    }
