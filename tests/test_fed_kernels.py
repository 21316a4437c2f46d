"""Fedcore kernel suite (kernels/fedcore): fused/ref parity in interpret mode.

The flat-buffer Pallas path must reproduce the per-leaf jnp reference chain it
replaces: the fused server apply (weighted mean + DP noise + outer update in
one (C, N) pass) against ``apply_aggregate`` within float32 tolerance, the
fused codec kernels against ``topk_compress`` / ``cast_compress`` /
``int8_compress`` bitwise where the selection semantics coincide, and the
flat-buffer pack/unpack as an exact pytree round-trip (hypothesis property).
The non-fused default path must remain BITWISE the PR-4 round — donation and
the ``apply_fn`` seam may not perturb it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import make_batches, make_params, quad_loss, sgd_inner

from repro.core import (
    Bf16Codec,
    FederatedConfig,
    Int8Codec,
    OuterOptConfig,
    ParticipationConfig,
    SyncAggregator,
    TopKCodec,
    apply_aggregate,
    federated_round,
    federated_round_with_uplink,
    get_codec,
    init_federated_state,
)
from repro.core.async_agg import AsyncAggConfig, flush_buffer, init_async_state
from repro.core.compression import cast_compress, int8_compress, topk_compress
from repro.kernels.fedcore import (
    FusedBf16Codec,
    FusedInt8Codec,
    FusedTopKCodec,
    fused_apply_aggregate,
    pack_client_leaves,
    pack_flat,
    pack_leaves,
    unpack_flat,
    unpack_leaves,
)

BLOCK = 128  # tiny block so multi-block grids execute even on toy shapes


def _params(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "a": jax.random.normal(ks[0], (7,)),
        "b": {"c": jax.random.normal(ks[1], (16, 8)), "d": jax.random.normal(ks[2], (33,))},
    }


def _deltas(params, c, seed=3):
    return jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(seed), (c,) + p.shape), params
    )


def _assert_trees(a, b, **tol):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32), **tol
        ),
        a,
        b,
    )


def _assert_trees_equal(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x, np.float32), np.asarray(y, np.float32)
        ),
        a,
        b,
    )


# ---------------------------------------------------------------------------
# Flat-buffer pack/unpack: exact pytree round-trip
# ---------------------------------------------------------------------------


def test_pack_unpack_roundtrip_bitwise_property():
    """Hypothesis property: for arbitrary leaf shape lists and pad multiples,
    pack → unpack is a BITWISE pytree round-trip and padding is zero."""
    from hypothesis import given, settings, strategies as st

    shapes_st = st.lists(
        st.lists(st.integers(1, 7), min_size=0, max_size=3), min_size=1, max_size=6
    )

    @settings(max_examples=40, deadline=None)
    @given(shapes=shapes_st, pad=st.sampled_from([1, 8, 128]), seed=st.integers(0, 2**16))
    def prop(shapes, pad, seed):
        rng = np.random.default_rng(seed)
        tree = {
            f"p{i}": jnp.asarray(rng.standard_normal(s), jnp.float32)
            for i, s in enumerate(map(tuple, shapes))
        }
        flat, treedef, spec = pack_flat(tree, pad)
        assert flat.shape == (spec.n_pad,) and spec.n_pad % pad == 0
        assert spec.n == sum(int(np.prod(s)) if s else 1 for s in map(tuple, shapes))
        np.testing.assert_array_equal(np.asarray(flat[spec.n :]), 0.0)
        back = unpack_flat(flat, treedef, spec)
        _assert_trees_equal(tree, back)

    prop()


def test_pack_client_leaves_matches_per_client_pack():
    """(C, ...) packing must agree with packing each client row separately —
    the (C, N) server buffer and the per-upload wire layout are the same bytes."""
    c = 3
    params = _params()
    deltas = _deltas(params, c)
    leaves = jax.tree_util.tree_leaves(deltas)
    flat2d, spec = pack_client_leaves(leaves, c, pad_multiple=BLOCK)
    assert flat2d.shape == (c, spec.n_pad)
    for k in range(c):
        row, row_spec = pack_leaves(
            [l[k] for l in jax.tree_util.tree_leaves(deltas)], BLOCK
        )
        assert row_spec.n == spec.n
        np.testing.assert_array_equal(np.asarray(flat2d[k]), np.asarray(row))
    back = unpack_leaves(flat2d[1], spec)
    for got, want in zip(back, [l[1] for l in leaves]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Fused server apply vs apply_aggregate (interpret-mode Pallas)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("outer", ["fedavg", "fedmom", "fedadam"])
@pytest.mark.parametrize("elastic", [False, True])
def test_fused_server_apply_matches_ref(outer, elastic):
    """The single fused (C, N) pass must reproduce the per-leaf weighted-mean →
    outer-update chain within float32 tolerance, with identical state schema,
    metric keys and a bitwise rng/round lane."""
    c = 4
    fed = FederatedConfig(
        clients_per_round=c, local_steps=2, outer=OuterOptConfig(name=outer, lr=0.7)
    )
    params = _params()
    deltas = _deltas(params, c)
    w = jnp.asarray([1.0, 2.0, 0.0, 0.5]) if elastic else None
    state = init_federated_state(fed, params, jax.random.PRNGKey(5))
    # two ref rounds so momentum/adam lanes are non-trivial when compared
    state, _ = apply_aggregate(fed, state, deltas, client_weights=w)
    ref_state, ref_metrics = apply_aggregate(fed, state, deltas, client_weights=w)
    fus_state, fus_metrics = fused_apply_aggregate(
        fed, state, deltas, client_weights=w,
        use_pallas=True, interpret=True, block=BLOCK,
    )
    _assert_trees(ref_state, fus_state, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(ref_state["rng"]), np.asarray(fus_state["rng"]))
    assert int(fus_state["round"]) == int(ref_state["round"])
    assert set(fus_metrics) == set(ref_metrics)
    for k in ref_metrics:
        np.testing.assert_allclose(
            float(ref_metrics[k]), float(fus_metrics[k]), rtol=2e-4, atol=1e-5, err_msg=k
        )


def test_fused_flat_jnp_path_matches_kernel():
    """The non-TPU fallback (flat jnp chain) and the interpret-mode kernel run
    the same per-block formulas — they must agree to float32 tolerance."""
    c = 4
    fed = FederatedConfig(
        clients_per_round=c, local_steps=2, outer=OuterOptConfig(name="fedadam", lr=0.1)
    )
    params = _params()
    deltas = _deltas(params, c)
    w = jnp.asarray([1.0, 3.0, 0.5, 2.0])
    state = init_federated_state(fed, params, jax.random.PRNGKey(5))
    a, ma = fused_apply_aggregate(
        fed, state, deltas, client_weights=w, use_pallas=True, interpret=True, block=BLOCK
    )
    b, mb = fused_apply_aggregate(
        fed, state, deltas, client_weights=w, use_pallas=False, block=BLOCK
    )
    _assert_trees(a, b, rtol=1e-6, atol=1e-7)
    for k in ma:
        np.testing.assert_allclose(float(ma[k]), float(mb[k]), rtol=1e-5, atol=1e-7)


def test_fused_dp_noise_advances_rng_bitwise_and_perturbs_params():
    """The fused path must consume the rng lane exactly like the ref (split →
    fold per dtype group) so downstream draws stay aligned; the noise itself is
    a different (flat-buffer) realization, so only distributional properties
    are asserted."""
    c = 4
    fed = FederatedConfig(
        clients_per_round=c, local_steps=2,
        outer=OuterOptConfig(name="fedavg", lr=1.0), dp_noise=0.05,
    )
    params = _params()
    deltas = _deltas(params, c)
    w = jnp.ones((c,))
    state = init_federated_state(fed, params, jax.random.PRNGKey(5))
    ref_state, _ = apply_aggregate(fed, state, deltas, client_weights=w)
    noisy, _ = fused_apply_aggregate(
        fed, state, deltas, client_weights=w, use_pallas=True, interpret=True, block=BLOCK
    )
    import dataclasses

    clean, _ = fused_apply_aggregate(
        dataclasses.replace(fed, dp_noise=0.0),
        state, deltas, client_weights=w, use_pallas=True, interpret=True, block=BLOCK,
    )
    np.testing.assert_array_equal(np.asarray(ref_state["rng"]), np.asarray(noisy["rng"]))
    diff = np.concatenate(
        [
            (np.asarray(a) - np.asarray(b)).ravel()
            for a, b in zip(
                jax.tree_util.tree_leaves(noisy["params"]),
                jax.tree_util.tree_leaves(clean["params"]),
            )
        ]
    )
    assert np.all(np.isfinite(diff)) and np.abs(diff).max() > 0
    # lr=1, fedavg: params shift BY the noise; scale is dp_noise·max(w)/Σw
    assert diff.std() == pytest.approx(0.05 / c, rel=0.35)


def test_fused_round_composes_with_run_clients():
    """federated_round(apply_fn=fused) vs the plain round: client phase shared
    verbatim, server phase within tolerance, metrics schema identical."""
    tau, c = 3, 4
    fed = FederatedConfig(
        clients_per_round=c, local_steps=tau, inner=sgd_inner(),
        outer=OuterOptConfig(name="fedmom", lr=0.7),
    )
    import functools

    fused = functools.partial(
        fused_apply_aggregate, use_pallas=True, interpret=True, block=BLOCK
    )
    w = jnp.asarray([1.0, 2.0, 0.5, 3.0])
    s_ref = init_federated_state(fed, make_params(), jax.random.PRNGKey(3))
    s_fus = init_federated_state(fed, make_params(), jax.random.PRNGKey(3))
    for r in range(2):
        b = make_batches(tau, c, seed=40 + r)
        s_ref, m_ref = federated_round(quad_loss, fed, s_ref, b, client_weights=w)
        s_fus, m_fus = federated_round(
            quad_loss, fed, s_fus, b, client_weights=w, apply_fn=fused
        )
        _assert_trees(s_ref, s_fus, rtol=2e-5, atol=1e-6)
        assert set(m_ref) == set(m_fus)


def test_fused_flush_buffer_matches_ref_flush():
    """--fused-server under async: flush_buffer(apply_fn=fused) on a partially
    filled buffer must match the ref flush within tolerance."""
    import functools

    c = 3
    fed = FederatedConfig(
        clients_per_round=c, local_steps=2, outer=OuterOptConfig(name="fedadam", lr=0.1)
    )
    acfg = AsyncAggConfig(buffer_size=c, staleness_alpha=0.5)
    params = _params()
    state = init_async_state(fed, acfg, params, jax.random.PRNGKey(0))
    deltas = _deltas(params, c)
    state["buffer"] = deltas
    state["buf_weights"] = jnp.asarray([1.0, 0.5, 0.0])
    state["buf_staleness"] = jnp.asarray([0.0, 1.0, 0.0])
    state["buf_count"] = jnp.asarray(2, jnp.int32)
    ref_s, ref_m = flush_buffer(fed, acfg, state)
    fus_s, fus_m = flush_buffer(
        fed, acfg, state,
        apply_fn=functools.partial(
            fused_apply_aggregate, use_pallas=True, interpret=True, block=BLOCK
        ),
    )
    _assert_trees(ref_s, fus_s, rtol=2e-5, atol=1e-6)
    for k in ref_m:
        np.testing.assert_allclose(float(ref_m[k]), float(fus_m[k]), rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Fused codec kernels vs the compression refs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [True, False])
def test_fused_topk_single_tensor_bitwise_vs_ref(use_pallas):
    """On a single-leaf tree the flat global-k threshold coincides with the
    per-leaf ref's, so fused payload AND residual must be bitwise
    ``topk_compress``."""
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (64,))}
    codec = FusedTopKCodec(
        k_fraction=0.25, use_pallas=use_pallas, interpret=True, block=BLOCK
    )
    payload, resid = codec.encode(tree, codec.init_residual(tree))
    ref_p, ref_r = topk_compress(tree, 0.25, codec.init_residual(tree))
    _assert_trees_equal(payload, ref_p)
    _assert_trees_equal(resid, ref_r)


def test_fused_topk_global_budget_and_mass_conservation():
    """Multi-leaf: exactly max(1, ⌊N·k⌋) entries of the WHOLE flat buffer
    survive (a global budget, unlike the per-leaf ref), and kept + residual
    reconstruct the input exactly."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    tree = {
        "a": jax.random.normal(ks[0], (40,)),
        "b": jax.random.normal(ks[1], (9, 7)),
        "c": jax.random.normal(ks[2], (5,)),
    }
    n = 40 + 63 + 5
    codec = FusedTopKCodec(k_fraction=0.1, use_pallas=True, interpret=True, block=BLOCK)
    payload, resid = codec.encode(tree, codec.init_residual(tree))
    kept = sum(int((np.asarray(x) != 0).sum()) for x in jax.tree_util.tree_leaves(payload))
    assert kept == max(1, int(n * 0.1))
    jax.tree_util.tree_map(
        lambda p, e, t: np.testing.assert_allclose(
            np.asarray(p + e), np.asarray(t), rtol=1e-6, atol=1e-7
        ),
        payload, resid, tree,
    )
    # wire accounting prices the same global budget (flat-length-sized indices)
    assert codec.nbytes(tree) == kept * (4 + 2)
    assert codec.payload_nbytes(payload) == codec.nbytes(tree)


@pytest.mark.parametrize("use_pallas", [True, False])
def test_fused_bf16_sr_bitwise_vs_ref(use_pallas):
    """Same rng → the fused flat SR pass produces the ref's payload BITWISE
    (the rounding noise is drawn identically per leaf, only the passes fuse);
    rng=None degrades to the same deterministic round-to-nearest."""
    tree = _params(seed=2)
    codec = FusedBf16Codec(use_pallas=use_pallas, interpret=True, block=BLOCK)
    sr, _ = codec.encode(tree, rng=jax.random.PRNGKey(7))
    _assert_trees_equal(sr, cast_compress(tree, jnp.bfloat16, rng=jax.random.PRNGKey(7)))
    det, _ = codec.encode(tree)
    _assert_trees_equal(det, cast_compress(tree, jnp.bfloat16))
    # round-trip: every SR output brackets its input within one bf16 ulp
    rt = codec.decode(sr)
    for k in ("a",):
        x = np.asarray(tree[k], np.float32)
        err = np.abs(np.asarray(rt[k], np.float32) - x)
        assert err.max() <= np.abs(x).max() * 2 ** -7


@pytest.mark.parametrize("use_pallas", [True, False])
def test_fused_int8_bitwise_vs_ref_and_roundtrip(use_pallas):
    tree = _params(seed=4)
    codec = FusedInt8Codec(use_pallas=use_pallas, interpret=True, block=BLOCK)
    payload, _ = codec.encode(tree)
    ref = int8_compress(tree)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        payload, ref,
    )
    out = codec.decode(payload)
    for k, leaf in (("a", tree["a"]),):
        scale = float(jnp.max(jnp.abs(leaf))) / 127.0
        assert float(jnp.max(jnp.abs(out[k] - leaf))) <= scale * 0.5 + 1e-6


def test_fused_topk_codec_inside_federated_round_bitwise():
    """The fused codec threaded through run_clients' vmap (the production call
    site) must reproduce the ref-codec round bitwise on single-leaf params."""
    tau, c, pop = 2, 2, 4
    fed = FederatedConfig(
        clients_per_round=c, local_steps=tau, inner=sgd_inner(),
        outer=OuterOptConfig(name="fedavg", lr=1.0),
    )
    ref_c = TopKCodec(k_fraction=0.3)
    fus_c = FusedTopKCodec(k_fraction=0.3, use_pallas=True, interpret=True, block=BLOCK)
    sel = jnp.asarray([2, 0])
    w = jnp.ones((c,))
    outs = []
    for codec in (ref_c, fus_c):
        state = init_federated_state(fed, make_params(), jax.random.PRNGKey(0))
        state["uplink_residuals"] = jax.tree_util.tree_map(
            lambda p: jnp.zeros((pop,) + p.shape, jnp.float32), make_params()
        )
        new_state, _ = federated_round_with_uplink(
            quad_loss, fed, codec, state, make_batches(tau, c),
            client_weights=w, selected=sel,
        )
        outs.append(new_state)
    _assert_trees_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# The default path stays bitwise (apply_fn seam + donation are invisible)
# ---------------------------------------------------------------------------


def test_sync_aggregator_default_round_bitwise_equals_direct_kernel():
    """SyncAggregator (donating jit, apply_fn=None) must produce bitwise the
    direct federated_round_with_uplink composition — the PR-4 identity."""
    tau, c, pop = 2, 3, 4
    fed = FederatedConfig(
        clients_per_round=c, local_steps=tau, inner=sgd_inner(),
        outer=OuterOptConfig(name="fedmom", lr=0.7), dp_clip=0.1, dp_noise=0.01,
    )
    pcfg = ParticipationConfig(population=pop, clients_per_round=c)
    agg = SyncAggregator(
        quad_loss, fed, pcfg, seed=0, params=make_params(),
        rng=jax.random.PRNGKey(1),
    )
    state = init_federated_state(fed, make_params(), jax.random.PRNGKey(1))
    # jit the direct composition exactly as the aggregator does (minus the
    # donation) so XLA fuses both sides identically — eager would drift 1 ulp
    direct = jax.jit(
        lambda s, b, w, sel: federated_round_with_uplink(
            quad_loss, fed, None, s, b, client_weights=w, selected=sel
        )
    )
    for r in range(2):
        plan = agg.plan(r)
        b = make_batches(tau, c, seed=60 + r)
        agg.run_round(b, plan)
        state, _ = direct(
            state, b, jnp.asarray(agg.round_weights(plan)), jnp.asarray(plan.selected)
        )
    _assert_trees_equal(agg.state, state)


def test_fused_sync_aggregator_end_to_end_close_to_ref():
    """--fused-server through the whole seam: the fused aggregator tracks the
    ref aggregator within float32 tolerance over multiple rounds."""
    tau, c, pop = 2, 3, 4
    fed = FederatedConfig(
        clients_per_round=c, local_steps=tau, inner=sgd_inner(),
        outer=OuterOptConfig(name="fedadam", lr=0.1),
    )
    pcfg = ParticipationConfig(population=pop, clients_per_round=c)
    ref = SyncAggregator(
        quad_loss, fed, pcfg, seed=0, params=make_params(), rng=jax.random.PRNGKey(1)
    )
    fus = SyncAggregator(
        quad_loss, fed, pcfg, seed=0, params=make_params(),
        rng=jax.random.PRNGKey(1), fused_server=True,
    )
    for r in range(3):
        plan = ref.plan(r)
        b = make_batches(tau, c, seed=70 + r)
        m_ref = ref.run_round(b, plan)
        m_fus = fus.run_round(b, plan)
        assert set(m_ref) == set(m_fus)
    _assert_trees(ref.state, fus.state, rtol=5e-5, atol=1e-6)


def test_get_codec_fused_factory():
    assert isinstance(get_codec("topk", 0.1, fused=True), FusedTopKCodec)
    assert isinstance(get_codec("bf16", fused=True), FusedBf16Codec)
    assert isinstance(get_codec("int8", fused=True), FusedInt8Codec)
    # the identity codec has no fused variant: it anchors the bitwise tests
    assert type(get_codec("float32", fused=True)).__name__ == "IdentityCodec"
    assert isinstance(get_codec("topk", 0.1), TopKCodec)
    assert not isinstance(get_codec("topk", 0.1), FusedTopKCodec)
    assert isinstance(get_codec("bf16"), Bf16Codec)
    assert isinstance(get_codec("int8"), Int8Codec)
