"""The unified Aggregator seam (core/aggregator.py): straggler partial
progress and resumable async dispatch.

Keystone identities:
  - partial progress with every client at full speed is BITWISE the PR-3
    round (rng + DP + uplink-residual lanes included) — the τ-mask and the
    τ_i/τ weight scale are exact no-ops at τ_i = τ;
  - a client credited τ_i < τ steps produces exactly the delta of a τ_i-step
    round on the same data (the mask really freezes the spent lanes);
  - a killed-and-resumed async run is BITWISE the uninterrupted run — buffer
    lanes, dispatch cursor, in-flight snapshots/version tags, uplink residuals
    and the simulated clock all round-trip through the canonical checkpoint
    schema (state pytree + JSON manifest).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from conftest import make_batches, make_params, quad_loss, sgd_inner

from repro.checkpoint import CheckpointManager
from repro.core import (
    STRAGGLER_PROFILES,
    AsyncAggConfig,
    AsyncBufferAggregator,
    AsyncFederationDriver,
    AsyncTimeline,
    FederatedConfig,
    OuterOptConfig,
    ParticipationConfig,
    StragglerProfile,
    SyncAggregator,
    TopKCodec,
    federated_round,
    init_federated_state,
    partial_progress_weights,
    plan_round,
    run_clients,
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
# plan_round partial progress: τ_i derivation + admission rule
# ---------------------------------------------------------------------------


def test_plan_round_partial_progress_derives_tau_and_admits_stragglers():
    tau = 8
    cfg = ParticipationConfig(
        population=16, clients_per_round=16,
        straggler=STRAGGLER_PROFILES["heavy"], weighting="examples",
        partial_progress=True, local_steps=tau,
    )
    cut = ParticipationConfig(
        population=16, clients_per_round=16,
        straggler=STRAGGLER_PROFILES["heavy"], weighting="examples",
    )
    deadline = STRAGGLER_PROFILES["heavy"].deadline
    saw_partial = False
    for r in range(10):
        plan = plan_round(cfg, 11, r)
        ref = plan_round(cut, 11, r)
        assert plan.local_steps is not None
        # τ_i = min(τ, ⌊τ·speed·deadline⌋) wherever admitted
        expect = np.minimum(tau, np.floor(tau * plan.speeds * deadline))
        np.testing.assert_array_equal(
            plan.local_steps[plan.mask], expect[plan.mask]
        )
        assert (plan.local_steps[~plan.mask] == 0).all()
        assert (plan.local_steps[plan.mask] >= 1).all()
        # the admission rule got STRICTLY more permissive than the deadline cut:
        # every deadline-cut contributor still contributes, and slow-but-not-
        # hopeless clients join with τ_i < τ
        assert (plan.mask | ~ref.mask).all()
        rescued = plan.mask & ~ref.mask
        if rescued.any():
            saw_partial = True
            assert (plan.local_steps[rescued] < tau).all()
        # raw plan weights stay UNSCALED n_k·mask — the τ_i/τ scale is the
        # aggregator's weight policy, not the sampler's
        assert (plan.weights[plan.mask] > 0).all()
    assert saw_partial, "heavy profile produced no partial clients in 10 rounds"


def test_rescued_client_keeps_its_realized_budget():
    """dropout 1.0 forces the empty-round rescue every round: the resurrected
    client must be credited its REAL τ_i (floored at 1), not a hardcoded single
    step — at full speed that is the full τ, so the bitwise full-speed identity
    survives the rescue firing."""
    tau = 8
    for profile in (StragglerProfile("eq", 0.0, 1.5), STRAGGLER_PROFILES["heavy"]):
        cfg = ParticipationConfig(
            population=8, clients_per_round=4, dropout_rate=1.0,
            straggler=profile, partial_progress=True, local_steps=tau,
        )
        for r in range(5):
            plan = plan_round(cfg, 5, r)
            assert plan.effective_k == 1
            idx = int(np.flatnonzero(plan.mask)[0])
            expect = min(tau, int(np.floor(tau * plan.speeds[idx] * profile.deadline)))
            assert plan.local_steps[idx] == max(1, expect)


def test_partial_progress_requires_tau():
    with pytest.raises(ValueError):
        ParticipationConfig(
            population=4, clients_per_round=2, partial_progress=True
        )


def test_partial_progress_weight_policy():
    w = np.asarray([2.0, 0.0, 4.0, 1.0], np.float32)
    ls = np.asarray([4, 0, 2, 1], np.int64)
    out = partial_progress_weights(w, ls, 4)
    np.testing.assert_allclose(out, [2.0, 0.0, 2.0, 0.25], rtol=1e-7)
    # τ_i = τ everywhere: bitwise the unscaled weights (×1.0 is exact)
    np.testing.assert_array_equal(
        partial_progress_weights(w, np.full(4, 4, np.int64), 4), w
    )
    # no τ-vector: pass-through
    np.testing.assert_array_equal(partial_progress_weights(w, None, 4), w)


@given(
    n=st.integers(2, 12),
    tau=st.integers(1, 32),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=25, deadline=None)
def test_partial_weights_are_convex_normalization(n, tau, seed):
    """Normalized partial-progress weights form a convex combination:
    Σw = 1, w_i ∝ n_k,i·τ_i/τ, and zero exactly where masked."""
    rng = np.random.default_rng(seed)
    n_k = rng.lognormal(0.0, 1.0, n).astype(np.float32)
    mask = rng.random(n) < 0.7
    if not mask.any():
        mask[int(rng.integers(n))] = True
    ls = np.where(mask, rng.integers(1, tau + 1, n), 0)
    raw = (n_k * mask).astype(np.float32)
    w = partial_progress_weights(raw, ls, tau)
    assert (w[~mask] == 0).all()
    assert (w[mask] > 0).all()
    p = np.asarray(w, np.float64) / np.sum(w, dtype=np.float64)
    np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-9)
    ref = n_k * mask * (ls / tau)
    np.testing.assert_allclose(p, ref / ref.sum(), rtol=1e-4, atol=1e-7)


# ---------------------------------------------------------------------------
# The τ-mask inside the scan
# ---------------------------------------------------------------------------


def test_full_tau_mask_is_bitwise_no_mask():
    """τ_i = τ for every client must reproduce the PR-3 round BITWISE — rng,
    DP clip/noise and top-k error-feedback residual lanes included."""
    tau, c = 4, 4
    params = make_params()
    batches = make_batches(tau, c)
    w = jnp.asarray([1.0, 2.0, 0.5, 3.0], jnp.float32)
    full = jnp.full((c,), tau, jnp.int32)
    for codec in (None, TopKCodec(k_fraction=0.25)):
        fed = _fed(c, tau, dp_clip=0.1, dp_noise=0.01)
        s0 = init_federated_state(fed, params, jax.random.PRNGKey(3))
        res = (
            jax.tree_util.tree_map(lambda p: jnp.zeros((c,) + p.shape), params)
            if codec is not None else None
        )
        base, m_base = jax.jit(
            lambda s, b: federated_round(
                quad_loss, fed, s, b, client_weights=w, codec=codec, residuals=res
            )
        )(s0, batches)
        masked, m_masked = jax.jit(
            lambda s, b, t: federated_round(
                quad_loss, fed, s, b, client_weights=w, codec=codec,
                residuals=res, tau_steps=t,
            )
        )(s0, batches, full)
        _assert_trees_equal(base, masked)
        for k in m_base:
            np.testing.assert_array_equal(
                np.asarray(m_base[k]), np.asarray(m_masked[k]), err_msg=k
            )


def test_all_partial_cohort_metrics_forward_fill_dead_steps():
    """When every contributor realizes τ_i < τ, the scan's tail steps have no
    active client — the round metrics must carry the LAST LIVE step's signal,
    not report train_loss = 0 (regression: zero-diluted loss trajectories)."""
    tau, c = 4, 3
    fed = _fed(c, tau)
    params = make_params()
    batches = make_batches(tau, c)
    w = jnp.ones((c,), jnp.float32)
    taus = jnp.asarray([2, 2, 1], jnp.int32)  # nobody reaches τ
    s0 = init_federated_state(fed, params)
    _, m = federated_round(
        quad_loss, fed, s0, batches, client_weights=w, tau_steps=taus
    )
    assert float(m["train_loss"]) > 0.1  # the τ_i=2 clients' step-1 loss
    assert float(m["train_loss_mean"]) > 0.1
    # the filled last step equals a truncated run's genuine last step
    ref, m_ref = federated_round(
        quad_loss, _fed(c, 2),
        init_federated_state(_fed(c, 2), params),
        {k: v[:2] for k, v in batches.items()},
        client_weights=w, tau_steps=jnp.asarray([2, 2, 1], jnp.int32),
    )
    np.testing.assert_array_equal(
        np.asarray(m["train_loss"]), np.asarray(m_ref["train_loss"])
    )


def test_async_partial_flush_rows_never_report_zero_loss():
    drv, *_ = _driver(partial=True)
    hist = drv.run_updates(6)
    assert all(r["train_loss_mean"] > 0.01 for r in hist), [
        r["train_loss_mean"] for r in hist
    ]


def test_partial_client_delta_equals_truncated_round():
    """A client masked to τ_i steps must emit exactly the delta of a τ_i-step
    round on the same leading batches — the held lanes really are frozen."""
    tau, tau_i, c = 5, 2, 3
    fed = _fed(c, tau)
    params = make_params()
    batches = make_batches(tau, c)
    taus = jnp.asarray([tau_i, tau, tau], jnp.int32)
    s0 = init_federated_state(fed, params)  # round 0: LR schedules align

    deltas, _ = run_clients(quad_loss, fed, s0, batches, tau_steps=taus)

    fed_short = _fed(c, tau_i)
    short_b = {k: v[:tau_i] for k, v in batches.items()}
    deltas_short, _ = run_clients(
        quad_loss, fed_short, init_federated_state(fed_short, params), short_b
    )
    # a τ-step scan and a τ_i-step scan are two different XLA programs, which
    # may fuse the same arithmetic differently: agree to a few float32 ulps
    short = np.asarray(deltas_short["w"][0])
    ulp_tol = 4 * np.finfo(np.float32).eps * np.abs(short).max()
    np.testing.assert_allclose(
        np.asarray(deltas["w"][0]), short, rtol=0, atol=ulp_tol
    )
    # the full-τ clients are untouched by their neighbors' masks
    full_deltas, _ = run_clients(quad_loss, fed, s0, batches)
    np.testing.assert_array_equal(
        np.asarray(deltas["w"][1]), np.asarray(full_deltas["w"][1])
    )


# ---------------------------------------------------------------------------
# SyncAggregator: seam == direct kernel; partial rescues stragglers
# ---------------------------------------------------------------------------


def test_sync_aggregator_full_speed_partial_bitwise_equals_plain():
    """Under a deadline nobody misses (speeds ≡ 1), the partial-progress
    aggregator must be BITWISE the plain one, dropout masks and all."""
    tau, c = 3, 4
    fed = _fed(c, tau, dp_clip=0.5, dp_noise=0.01)
    pcfg = ParticipationConfig(
        population=8, clients_per_round=c, dropout_rate=0.3,
        straggler=StragglerProfile("eq", 0.0, 1.5), weighting="examples",
    )
    params = make_params()
    plain = SyncAggregator(
        quad_loss, fed, pcfg, seed=7, params=params,
        rng=jax.random.PRNGKey(9),
    )
    partial = SyncAggregator(
        quad_loss, fed, pcfg, seed=7, params=params,
        rng=jax.random.PRNGKey(9), partial_progress=True,
    )
    for r in range(3):
        b = make_batches(tau, c, seed=30 + r)
        pl_a, pl_b = plain.plan(r), partial.plan(r)
        assert pl_b.local_steps is not None
        assert (pl_b.local_steps[pl_b.mask] == tau).all()
        np.testing.assert_array_equal(pl_a.mask, pl_b.mask)
        m_a = plain.run_round(b, pl_a)
        m_b = partial.run_round(b, pl_b)
        _assert_trees_equal(plain.state, partial.state)
        for k in m_a:
            np.testing.assert_array_equal(
                np.asarray(m_a[k]), np.asarray(m_b[k]), err_msg=k
            )


def test_sync_aggregator_partial_rescues_straggler_work():
    """Heavy profile: the partial aggregator admits more clients per round at
    fractional weights, and its checkpoint round-trips through the manager."""
    tau, c = 4, 8
    fed = _fed(c, tau)
    pcfg = ParticipationConfig(
        population=8, clients_per_round=c,
        straggler=STRAGGLER_PROFILES["heavy"], weighting="examples",
    )
    params = make_params()
    cut = SyncAggregator(quad_loss, fed, pcfg, seed=5, params=params)
    part = SyncAggregator(
        quad_loss, fed, pcfg, seed=5, params=params, partial_progress=True
    )
    admitted_cut = admitted_part = 0
    for r in range(6):
        admitted_cut += cut.plan(r).effective_k
        plan = part.plan(r)
        admitted_part += plan.effective_k
        w = part.round_weights(plan)
        frac = plan.local_steps[plan.mask] / tau
        np.testing.assert_allclose(
            w[plan.mask], plan.weights[plan.mask] * frac, rtol=1e-6
        )
    assert admitted_part > admitted_cut  # stragglers rescued, not cut


def test_sync_aggregator_checkpoint_schema_roundtrip(tmp_path):
    tau, c = 2, 2
    fed = _fed(c, tau)
    pcfg = ParticipationConfig(population=4, clients_per_round=c)
    agg = SyncAggregator(
        quad_loss, fed, pcfg, codec=TopKCodec(k_fraction=0.5), seed=0,
        params=make_params(), partial_progress=True,
    )
    plan = agg.plan(0)
    agg.run_round(make_batches(tau, c), plan)
    tree, manifest = agg.checkpoint()
    assert manifest["kind"] == "sync" and manifest["round"] == 1
    # the residual lane is sparse: one row per ever-selected client, with the
    # id set recorded in the manifest (never a dense (P, ...) expansion)
    assert manifest["uplink_ids"] == agg.residual_store.ids()
    assert jax.tree_util.tree_leaves(tree["uplink_residuals"])[0].shape[0] == len(
        manifest["uplink_ids"]
    )
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save_server(0, tree, extra={"aggregator": manifest})
    like = SyncAggregator.checkpoint_template(
        fed, agg.pcfg, make_params(), codec=TopKCodec(k_fraction=0.5),
        uplink_ids=manifest["uplink_ids"],
    )
    restored, man = ckpt.load_server(0, like)
    _assert_trees_equal(tree, restored)
    assert man["extra"]["aggregator"] == manifest

    # restore() routes the sparse lane back into an equivalent store
    agg2 = SyncAggregator(
        quad_loss, fed, agg.pcfg, codec=TopKCodec(k_fraction=0.5), seed=0,
        params=make_params(), partial_progress=True,
    )
    agg2.restore(restored, man["extra"]["aggregator"])
    assert agg2.residual_store.ids() == agg.residual_store.ids()
    _assert_trees_equal(agg2.residual_store.stacked(), agg.residual_store.stacked())


# ---------------------------------------------------------------------------
# AsyncTimeline under partial progress
# ---------------------------------------------------------------------------


def test_async_timeline_partial_progress_budgets_dispatches():
    tau = 8
    pcfg = ParticipationConfig(
        population=16, clients_per_round=8, dropout_rate=0.1,
        straggler=STRAGGLER_PROFILES["heavy"], weighting="examples",
        partial_progress=True, local_steps=tau,
    )
    deadline = STRAGGLER_PROFILES["heavy"].deadline
    tl = AsyncTimeline(pcfg, 7)
    events = [tl.dispatch(n) for n in range(60)]
    completing = [e for e in events if e.completes]
    assert len(completing) > 20
    for e in completing:
        assert 1 <= e.local_steps <= tau
        # the deadline is a budget: no completion takes longer than it
        assert e.duration <= deadline + 1e-9
        assert e.weight > 0  # unscaled n_k — policy scaling happens at admit
    assert any(e.local_steps < tau for e in completing)  # genuinely partial
    # purity: dispatch n is a function of (cfg, seed, n) alone
    tl2 = AsyncTimeline(pcfg, 7)
    for n in (0, 17, 59):
        assert tl2.dispatch(n) == events[n]


# ---------------------------------------------------------------------------
# Resumable async dispatch (the acceptance criterion)
# ---------------------------------------------------------------------------


def _driver(codec=None, partial=False, state=None, dispatch=None, pop=8, k=4):
    tau = 3
    fed = FederatedConfig(
        clients_per_round=k, local_steps=tau, inner=sgd_inner(lr=0.05),
        outer=OuterOptConfig(name="fedavg", lr=1.0),
    )
    acfg = AsyncAggConfig(buffer_size=2, staleness_alpha=0.5)
    pcfg = ParticipationConfig(
        population=pop, clients_per_round=k, dropout_rate=0.1,
        straggler=STRAGGLER_PROFILES["heavy"], weighting="examples",
        partial_progress=partial, local_steps=tau if partial else 0,
    )
    drv = AsyncFederationDriver(
        quad_loss, fed, acfg, pcfg,
        lambda cid: make_batches(tau, 1, seed=100 + cid),
        seed=3, params=make_params(), rng=jax.random.PRNGKey(1),
        codec=codec, state=state, dispatch=dispatch,
    )
    return drv, fed, acfg, pcfg


def _strip_update(rows):
    return [{k: v for k, v in r.items() if k != "update"} for r in rows]


@pytest.mark.parametrize(
    "codec,partial",
    [(None, False), (None, True), (TopKCodec(k_fraction=0.25), False)],
    ids=["plain", "partial", "topk"],
)
def test_async_kill_and_resume_is_bitwise_uninterrupted(tmp_path, codec, partial):
    """THE resume criterion: checkpoint mid-run through the canonical schema
    (CheckpointManager npz + JSON manifest), rebuild a fresh driver from it,
    and the continuation must be bitwise the uninterrupted run — server state,
    buffer lanes, dispatch cursor, residual store, sim clock and every metric
    row included."""
    drv_a, fed, acfg, pcfg = _driver(codec, partial)
    hist_a = drv_a.run_updates(6)

    drv_b, *_ = _driver(codec, partial)
    drv_b.run_updates(3)
    tree, manifest = drv_b.checkpoint()
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save_server(2, tree, extra={"aggregator": manifest})

    like = AsyncBufferAggregator.checkpoint_template(
        fed, acfg, pcfg, make_params(), codec,
        uplink_ids=manifest.get("uplink_ids"),
    )
    restored, man = ckpt.load_server(2, like)
    assert man["extra"]["aggregator"] == manifest  # JSON floats exact

    drv_c, *_ = _driver(
        codec, partial, state=restored, dispatch=man["extra"]["aggregator"]
    )
    assert drv_c.n_dispatched == drv_b.n_dispatched
    assert drv_c.sim_time == drv_b.sim_time
    assert drv_c._busy == drv_b._busy
    hist_c = drv_c.run_updates(3)

    # continuation rows match the uninterrupted run's rows exactly
    assert _strip_update(hist_a[3:]) == _strip_update(hist_c)
    # final state machines are bitwise identical — manifest and pytree
    tree_a, man_a = drv_a.checkpoint()
    tree_c, man_c = drv_c.checkpoint()
    assert man_a == man_c
    _assert_trees_equal(tree_a, tree_c)
    assert drv_a.work_completed == drv_c.work_completed
    assert drv_a.work_wasted == drv_c.work_wasted
    assert drv_a.uplink_bytes_total == drv_c.uplink_bytes_total


def test_async_resume_refuses_wrong_manifest():
    drv, fed, acfg, pcfg = _driver()
    tree, manifest = drv.checkpoint()
    with pytest.raises(ValueError):  # schema drift
        _driver(state=tree, dispatch=dict(manifest, schema=999))
    with pytest.raises(ValueError):  # kind mismatch
        _driver(state=tree, dispatch=dict(manifest, kind="sync"))
    with pytest.raises(ValueError):  # slot table truncated
        _driver(
            state=tree,
            dispatch=dict(manifest, slots=manifest["slots"][:-1]),
        )
    with pytest.raises(ValueError):  # manifest without the snapshot lanes
        bad = {k: v for k, v in tree.items() if k != "inflight_params"}
        _driver(state=bad, dispatch=manifest)


def test_async_checkpoint_keeps_legacy_subset():
    """checkpoint() extends checkpoint_state() — the PR-3 buffer round-trip
    schema stays recoverable: every legacy lane matches, with the legacy DENSE
    residual lane being exactly the dense expansion of the canonical sparse
    lane (manifest ids + stacked rows)."""
    drv, *_ = _driver(TopKCodec(k_fraction=0.25))
    for _ in range(5):
        drv.step()
    legacy = drv.checkpoint_state()
    tree, manifest = drv.checkpoint()
    for key, val in legacy.items():
        if key == "uplink_residuals":
            continue  # layouts differ by design — compared below
        _assert_trees_equal(val, tree[key])
    assert set(tree) - set(legacy) == {"inflight_params", "uplink_rng"}
    # sparse lane + manifest ids expand to exactly the legacy dense store
    from repro.core.federated import SparseResidualStore

    sparse = SparseResidualStore.from_stacked(
        make_params(), manifest["uplink_ids"], tree["uplink_residuals"]
    )
    _assert_trees_equal(
        sparse.to_dense(drv.pcfg.population), legacy["uplink_residuals"]
    )
    assert len(manifest["slots"]) == 4
    assert manifest["cursor"] == drv.n_dispatched


def test_async_driver_partial_progress_trains_and_scales_weights():
    """Partial-progress async e2e: partial completions admit at fractional
    weight (τ_i/τ · n_k, pre-discount), the loop trains, the clock advances."""
    drv, fed, acfg, pcfg = _driver(partial=True)
    saw_partial = False
    for _ in range(60):
        ev = drv._heap[0][2]
        if ev.completes and 0 < ev.local_steps < fed.local_steps:
            saw_partial = True
            expect = ev.weight * ev.local_steps / fed.local_steps
            assert drv.event_weight(ev) == pytest.approx(expect)
            assert drv.event_weight(ev) < ev.weight
        drv.step()
    assert saw_partial, "heavy profile produced no partial dispatches"
    assert drv.sim_time > 0 and drv.work_completed > 0
