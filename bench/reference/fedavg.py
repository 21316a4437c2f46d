"""Plain float32 reference of synchronous federated rounds (Photon, Algorithm 1;
FedAvg, McMahan et al. 2017; AdamW, Loshchilov & Hutter 2019).

One round, from the global weights θ:

1. every admitted client k starts from θ with fresh AdamW moments and takes
   τ steps on its own batches: global-norm clipping of the gradient, the
   cosine schedule with linear warm-up indexed by the sequential step
   ``round·τ + t``, then ``θ_k ← θ_k − lr·(m̂/(√v̂ + ε) + wd·θ_k)``;
2. its pseudo-gradient is ``Δ_k = θ − θ_k``;
3. the server takes the weighted mean ``Δ = Σ w_k Δ_k / Σ w_k`` and applies
   FedAvg's outer step ``θ ← θ − η_s·Δ``.

Clients run one after another, so memory holds one client at a time. The
recipe's numbers (learning rates, betas, clipping, schedule) come from the
configuration file, not from the program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference import model as ref_model


def cosine_lr(inner: dict, step: int) -> float:
    """Linear warm-up to ``lr_max`` over ``warmup_steps``, then cosine decay to
    ``alpha·lr_max`` at ``total_steps``."""
    lr_max, warm, total = inner["lr_max"], inner["warmup_steps"], inner["total_steps"]
    if step < warm:
        return lr_max * step / max(1.0, warm)
    prog = min(max((step - warm) / max(1.0, total - warm), 0.0), 1.0)
    lr_min = inner["alpha"] * lr_max
    return lr_min + 0.5 * (lr_max - lr_min) * (1.0 + math.cos(math.pi * prog))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _client_step(cfg_items, inner_items, precision, w, m, v, count, tokens, lr, mask):
    cfg, inner = dict(cfg_items), dict(inner_items)
    loss, g = jax.value_and_grad(
        lambda w: ref_model.loss(cfg, w, tokens, mask, precision)
    )(w)
    leaves = jax.tree_util.tree_leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, inner["grad_clip"] / (gnorm + 1e-9)), g
    )
    b1, b2 = inner["beta1"], inner["beta2"]
    count = count + 1.0
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count

    def upd(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + inner["eps"]) + inner["weight_decay"] * p
        return p - lr * step

    return jax.tree_util.tree_map(upd, w, m, v), m, v, count, loss


@jax.jit
def _accumulate(acc, w_global, w_client, weight):
    return jax.tree_util.tree_map(lambda a, g, c: a + weight * (g - c), acc, w_global, w_client)


@jax.jit
def _outer(w_global, acc, weight_sum, outer_lr):
    return jax.tree_util.tree_map(lambda g, a: g - outer_lr * (a / weight_sum), w_global, acc)


def _frozen(d: dict):
    return tuple(sorted(d.items()))


def run_round(cfg: dict, recipe: dict, w, round_idx: int, tokens, weights,
              precision: str = "f32", fault: str | None = None):
    """One round from global weights ``w``.

    ``tokens``: (τ, C, B, S) int32, the batches of each step and client;
    ``weights``: (C,) aggregation weights, 0 for a client that is not admitted.
    ``fault`` plants one of the program faults the check must catch:
    ``"half_batch"`` (the loss taken over the first half of each batch only) or
    ``"no_exchange"`` (the server applies client 0's delta alone).

    Returns the new weights and the round's mean training loss (the mean over
    steps of the mean over admitted clients)."""
    inner, outer = recipe["inner"], recipe["outer"]
    tau, C, B, S = tokens.shape
    mask = None
    if fault == "half_batch":
        flat = np.arange(B * S).reshape(B, S)
        mask = jnp.asarray(flat < (B * S) // 2)
    acc = jax.tree_util.tree_map(jnp.zeros_like, w)
    step_losses = np.zeros((tau, C))
    admitted = [k for k in range(C) if weights[k] > 0]
    aggregated = admitted[:1] if fault == "no_exchange" else admitted
    weight_sum = 0.0
    cfg_items, inner_items = _frozen(cfg), _frozen(inner)
    for k in admitted:
        wk = w
        m = jax.tree_util.tree_map(jnp.zeros_like, w)
        v = jax.tree_util.tree_map(jnp.zeros_like, w)
        count = jnp.zeros((), jnp.float32)
        for t in range(tau):
            lr = cosine_lr(inner, round_idx * tau + t)
            wk, m, v, count, loss = _client_step(
                cfg_items, inner_items, precision, wk, m, v, count,
                jnp.asarray(tokens[t, k]), jnp.float32(lr), mask,
            )
            step_losses[t, k] = float(loss)
        del m, v
        if k in aggregated:
            acc = _accumulate(acc, w, wk, jnp.float32(weights[k]))
            weight_sum += float(weights[k])
    new_w = _outer(w, acc, jnp.float32(weight_sum), jnp.float32(outer["lr"]))
    mean_loss = float(np.mean(step_losses[:, admitted].mean(axis=1)))
    return new_w, mean_loss
