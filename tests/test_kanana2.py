"""kanana-2-30b-a3b (the DeepSeek-V3 block) against the benchmark's plain float32
reference (``bench/reference/kanana2.py``), on seeded weights at a small size on
the CPU: latent attention, the expert-share MoE layer (routing over all experts,
the held experts' part through the grouped product, no token dropped), the whole
model's loss and gradients, and the router bias's load rule in the client step."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))

from reference import kanana2 as ref  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import attention, build_model, moe  # noqa: E402
from repro.models.common import init_params  # noqa: E402

SMALL = dict(n_layers=3, d_model=64, n_heads=4, d_ff=128, vocab_size=500, padded_vocab=512,
             rms_norm_eps=1e-6, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, rope_theta=1e6, n_experts=16, moe_top_k=3, moe_d_ff=16,
             n_shared_experts=1, moe_experts_held=4, moe_first_expert=4,
             moe_routed_scale=2.448, router_bias_rate=1e-3, router_aux_coef=1e-4, z_loss=1e-4)
S = 64
F32 = lambda spec, a, b: jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)  # noqa: E731


def _cfg(**over):
    c = dict(SMALL, **over)
    skip = ("padded_vocab", "rms_norm_eps")
    return dataclasses.replace(
        get_config("kanana-2-30b-a3b"), n_kv_heads=c["n_heads"], max_seq_len=S,
        compute_dtype="float32", **{k: v for k, v in c.items() if k not in skip})


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * float(np.abs(want).max()))


def _tokens(seed=0, B=2):
    return jnp.asarray(np.random.RandomState(seed).randint(0, SMALL["vocab_size"], (B, S)),
                       jnp.int32)


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_weights_from_the_seed_match_the_programs_bitwise():
    seed = 2_147_483_659
    prog = _leaves(build_model(_cfg()).init(jax.random.PRNGKey(seed)))
    want = _leaves(ref.init_weights(SMALL, seed))
    assert prog.keys() == want.keys()
    for k in want:
        assert prog[k].shape == want[k].shape and bool(jnp.all(prog[k] == want[k])), k


def test_latent_attention_output_and_gradients_match_the_reference():
    cfg = _cfg()
    p = init_params(jax.random.PRNGKey(1), attention.mla_desc(cfg))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S, SMALL["d_model"]))
    pos = jnp.arange(S)

    def prog(x, p):
        return attention.mla(cfg, p, x, positions=pos)

    def want(x, p):
        return ref.latent_attention(SMALL, F32, x, p)

    with jax.default_matmul_precision("highest"):
        out, back = jax.vjp(prog, x, p)
        out_r, back_r = jax.vjp(want, x, p)
        ct = jax.random.normal(jax.random.PRNGKey(3), out.shape)
        _close(out, out_r)
        for g, gr in zip(jax.tree_util.tree_leaves(back(ct)),
                         jax.tree_util.tree_leaves(back_r(ct))):
            _close(g, gr, 5e-4)


def test_whole_model_loss_and_gradients_match_the_reference():
    model = build_model(_cfg())
    w = ref.init_weights(SMALL, 7)
    tokens = _tokens(1)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            w, {"tokens": tokens})
        (loss_r, loads_r), grads_r = jax.value_and_grad(
            lambda w: ref.loss(SMALL, w, tokens), has_aux=True)(w)
    _close(loss, loss_r, 1e-5)
    got, want = _leaves(grads), _leaves(grads_r)
    assert got.keys() == want.keys()
    for k in want:
        _close(got[k], want[k], 2e-3)
    assert not np.any(np.asarray(got[ref.BIAS_PATH]))  # the bias takes no gradient
    loads = metrics[moe.ROUTER_LOAD]["segments"][1]["pos0"]["ffn"]["router_bias"]
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(loads_r))
    np.testing.assert_array_equal(np.asarray(metrics[moe.HELD_LOAD]),
                                  np.asarray(loads_r)[:, 4:8])


def _moe_weights(seed, cfg, held_first=None):
    """Weights of the uncut 16-expert layer, and the share [first, first + 4) of them."""
    full = init_params(jax.random.PRNGKey(seed), moe.moe_share_desc(
        dataclasses.replace(cfg, moe_experts_held=16, moe_first_expert=0)))
    if held_first is None:
        return full
    sl = slice(held_first, held_first + 4)
    return {k: (v[sl] if k in ("w_in", "w_gate", "w_out") else v) for k, v in full.items()}


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 16 experts, the shared expert counted once, give
    the uncut layer of the reference (every expert held)."""
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, S, SMALL["d_model"]))
    full = _moe_weights(5, cfg)
    full["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    shared = moe.dense_ffn(cfg, full["shared"], x)
    total, loads = shared, []
    for first in (0, 4, 8, 12):
        part = dict(_moe_weights(5, cfg, first), router_bias=full["router_bias"])
        y, _, load, _ = moe.moe_share_ffn(dataclasses.replace(cfg, moe_first_expert=first), part, x)
        total, loads = total + (y - shared), loads + [load]
    with jax.default_matmul_precision("highest"):
        want, _, want_load = ref.moe_share(
            dict(SMALL, moe_experts_held=16, moe_first_expert=0), F32, x, full)
    _close(total, want)
    for load in loads:  # every share routes over all experts alike
        np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))


def _layer_against_reference(over, B, bias, seed):
    """The expert-share layer and the gradients of ``⟨y, ct⟩`` for its input and
    weights, against the reference's, and the rows it reports, trips × R;
    returns the slots routed to the held experts, R, the input, the weights,
    the output and the gradients."""
    c = dict(SMALL, **over)
    cfg = _cfg(**over)
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, S, c["d_model"]))
    p = init_params(jax.random.PRNGKey(seed + 1), moe.moe_share_desc(cfg))
    p["router_bias"] = jnp.asarray(bias, jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(seed + 2), x.shape)
    y, _, load, rows = moe.moe_share_ffn(cfg, p, x)
    grads = jax.grad(lambda x, p: jnp.sum(moe.moe_share_ffn(cfg, p, x)[0] * ct),
                     argnums=(0, 1))(x, p)
    with jax.default_matmul_precision("highest"):
        want, _, want_load = ref.moe_share(c, F32, x, p)
        want_grads = jax.grad(lambda x, p: jnp.sum(ref.moe_share(c, F32, x, p)[0] * ct),
                              argnums=(0, 1))(x, p)
    _close(y, want)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    got, exp = _leaves(grads), _leaves(want_grads)
    assert got.keys() == exp.keys()
    for k in exp:
        _close(got[k], exp[k], 2e-3)
    first, held = c["moe_first_expert"], c["moe_experts_held"]
    live = int(np.asarray(want_load)[first:first + held].sum())
    R = moe.trip_rows(B * S, c["moe_top_k"], held, c["n_experts"])
    assert float(rows) == max(1, -(-live // R)) * R  # trips × R
    return live, R, (x, p, y, grads)


def test_no_token_is_dropped_when_the_bias_sends_every_token_to_one_expert():
    bias = np.zeros(16)
    bias[5] = 100.0  # expert 5, held here
    live, _, _ = _layer_against_reference({}, 2, bias, 7)
    assert live >= 2 * S  # every token: 16/3 times an even split's load on expert 5


# E 32, 4 held from expert 4, top-3, 512 tokens: R = 512 of the 1,536 slots
WIDE = dict(n_experts=32)


@pytest.mark.parametrize("over,B,bias,trips", [
    (WIDE, 8, 0.01 * np.random.RandomState(9).randn(32), 1),  # in one trip
    (WIDE, 8, np.where(np.arange(32) == 5, 100.0, 0.0), 2),  # every token on held expert 5
    (WIDE, 8, np.where(np.arange(32) < 3, 100.0, 0.0), 0),  # every token on experts 0-2
    (dict(moe_experts_held=16, moe_first_expert=0), 2, np.zeros(16), 1),  # the whole layer
], ids=["one_trip", "several_trips", "no_live_slot", "whole_layer"])
def test_routed_part_and_its_gradients_match_the_reference_in_every_trip_regime(
        over, B, bias, trips):
    """The routed part runs the live slots R rows at a time: within one trip,
    over several, with none live (the shared experts alone), and with every
    expert held (R = T·K)."""
    live, R, (x, p, y, (_, dp)) = _layer_against_reference(over, B, bias, 13)
    assert max(1, -(-live // R)) == max(trips, 1) and (live == 0) == (trips == 0)
    if "moe_experts_held" in over:
        assert R == B * S * SMALL["moe_top_k"]
    if not live:  # the shared experts' output alone; no gradient reaches the routed part
        shared = moe.dense_ffn(_cfg(**over), p["shared"], x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(shared))
        for k in ("w_in", "w_gate", "w_out", "router"):
            assert not np.any(np.asarray(dp[k])), k


@pytest.mark.parametrize("optimizer,grad_accum", [("adamw", 1), ("sgd", 1), ("adamw", 2)])
def test_load_rule_moves_the_bias_and_the_optimizer_leaves_it_alone(optimizer, grad_accum):
    """One local step of a federated round: the bias moves by γ·sign(mean load
    − load), each entry by ±γ or 0, with no gradient step and no weight decay
    (it starts away from zero, where decay would show); other weights move.
    With the step's batch in two micro-batches, the rule and the counters read
    the whole batch's loads, once a step."""
    from repro.core.federated import FederatedConfig, federated_round, init_federated_state
    from repro.core.inner_opt import InnerOptConfig
    from repro.core.outer_opt import OuterOptConfig

    model = build_model(_cfg())
    w = ref.init_weights(SMALL, 11)
    seg = w["segments"][1]["pos0"]["ffn"]
    seg["router_bias"] = 0.01 * jax.random.normal(jax.random.PRNGKey(12), seg["router_bias"].shape)
    fed = FederatedConfig(
        clients_per_round=1, local_steps=1,
        inner=InnerOptConfig(name=optimizer, lr_max=1e-2, weight_decay=0.1, warmup_steps=0,
                             total_steps=10, router_bias_rate=SMALL["router_bias_rate"]),
        outer=OuterOptConfig(name="fedavg", lr=1.0), grad_accum=grad_accum)
    tokens = _tokens(3)
    state, metrics = jax.jit(lambda s, b: federated_round(model.loss, fed, s, b))(
        init_federated_state(fed, w), {"tokens": tokens[None, None]})
    _, loads = ref.loss(SMALL, w, tokens)
    rule = SMALL["router_bias_rate"] * np.sign(np.mean(loads, -1, keepdims=True) - loads)
    moved = _leaves(state["params"])[ref.BIAS_PATH] - _leaves(w)[ref.BIAS_PATH]
    np.testing.assert_allclose(np.asarray(moved), rule, atol=1e-9)
    assert set(np.unique(np.round(np.asarray(moved) / SMALL["router_bias_rate"]))) <= {-1, 0, 1}
    assert np.any(np.asarray(moved))
    router = "['segments'][1]['pos0']['ffn']['router']"
    assert not np.allclose(_leaves(state["params"])[router], _leaves(w)[router])
    held = np.asarray(loads)[:, 4:8]
    np.testing.assert_allclose(float(metrics["moe_local_slots"]), held.sum(-1).mean())
    np.testing.assert_allclose(float(metrics["moe_load_max_over_mean"]),
                               np.mean(held.max(-1) / (held.sum(-1) / 4)), rtol=1e-6)
    # one trip of R rows a micro-batch (R = T·K at this size), summed over the step
    R = moe.trip_rows(2 * S // grad_accum, SMALL["moe_top_k"], 4, SMALL["n_experts"])
    assert float(metrics["moe_routed_rows"]) == grad_accum * R == 2 * S * SMALL["moe_top_k"]
