"""The mesh lowering runs the trainer's round.

``launch/steps.build_train_step`` lowers the programs of
``core/round_program`` that :class:`SyncAggregator` runs. Here both run one
round of a reduced photon config on the same concrete arrays — the mesh side
on a one-device ``("data", "model")`` mesh, with its batches pre-split into
micro-batches as ``input_specs`` lays them out — and must reach the same
server state bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import (
    FederatedConfig,
    InnerOptConfig,
    OuterOptConfig,
    ParticipationConfig,
    SyncAggregator,
    apply_aggregate_partial,
    get_codec,
    tile_rng,
)
from repro.launch.steps import build_train_step
from repro.models import build_model

TAU, BATCH, SEQ = 2, 2, 32


def _assert_trees_equal(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _copy(tree):
    return jax.tree_util.tree_map(jnp.array, tree)


@pytest.mark.parametrize(
    "uplink,cohort_tile",
    [("float32", None), ("topk", None), ("float32", 1)],
    ids=["flat-float32", "flat-topk", "tiled"],
)
def test_mesh_lowering_runs_the_trainers_round(uplink, cohort_tile):
    cfg = get_config("photon-125m").reduced()
    model = build_model(cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    # a one-device mesh holds one client: the cohort is the mesh's width
    fed = FederatedConfig(
        clients_per_round=1, local_steps=TAU,
        inner=InnerOptConfig(lr_max=1e-3, warmup_steps=1, total_steps=4 * TAU),
        outer=OuterOptConfig(name="fedmom", lr=0.7, momentum=0.9),
    )
    pcfg = ParticipationConfig(population=2, clients_per_round=1)
    codec = get_codec(uplink, 0.05) if uplink != "float32" else None
    agg = SyncAggregator(
        lambda p, b: model.loss(p, b), fed, pcfg, codec=codec, seed=0,
        cohort_tile=cohort_tile, params=model.init(jax.random.PRNGKey(0)),
        rng=jax.random.PRNGKey(1),
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (TAU, 1, BATCH, SEQ), 0, cfg.vocab_size, jnp.int32
    )
    plan = agg.plan(0)
    w = jnp.asarray(agg.round_weights(plan))
    tau = jnp.asarray(agg.tau_steps(plan))
    state = _copy(agg.state)
    res = agg.residual_store.gather(plan.selected) if codec is not None else None

    agg.run_round({"tokens": tokens}, plan)

    step = build_train_step(
        cfg, InputShape("tiny", SEQ, BATCH, "train"), mesh, tau_lowered=TAU,
        remat=False, fed=fed, uplink=uplink, topk_fraction=0.05,
        cohort_tile=cohort_tile,
    )
    split = {"tokens": tokens[:, :, None]}  # (τ, C, grad_accum=1, B, S)
    assert split["tokens"].shape == step.args[1]["tokens"].shape
    with mesh:
        if cohort_tile is None:
            new_state, _ = step.fn(state, split, w, res, tau)
        else:
            tile_state = {"params": state["params"], "round": state["round"],
                          "rng": tile_rng(state["rng"], 0)}
            out = step.fn(tile_state, split, w, res, tau)
            server = jax.jit(lambda *a: apply_aggregate_partial(fed, *a))
            new_state, _ = server(state, out["delta_sum"], w, out["delta_norms"])
    if codec is not None:
        _assert_trees_equal(
            new_state.pop("uplink_residuals"),
            agg.residual_store.gather(plan.selected),
        )
    _assert_trees_equal(new_state, agg.state)
