"""The paper's core contribution: federated generative pre-training rounds (Photon).

One *round* (Algorithm 1) executes, inside a single jitted computation:

  1. broadcast θ_global to a client axis C (sharded over ('pod','data') on the mesh),
  2. τ local AdamW steps per client via ``lax.scan`` — NO cross-client collectives,
  3. pseudo-gradients Δ_k = θ_global − θ_k, per-client DP post-processing,
  4. ONE aggregation (mean over the client axis → a single all-reduce per round),
  5. outer-optimizer update of θ_global (FedAvg / FedMom / FedAdam).

This is the TPU-native mapping of Photon's client/server architecture: the client axis
is a leading parameter dimension, so per-device memory matches replicated DDP while the
round-boundary collective is the only cross-client traffic — the paper's τ×
communication reduction, visible directly in the compiled HLO.

The round is factored into two pure phases so synchronous and asynchronous
aggregation share one client code path:

  - :func:`run_clients`   — steps 1–3 (broadcast → τ local steps → post-processed
    pseudo-gradients). Used verbatim by the sync round and by the FedBuff-style
    async buffer (``core/async_agg``), whose clients train against stale params.
  - :func:`apply_aggregate` — steps 4–5 (ONE weighted aggregation → optional DP
    noise → outer update). The async buffer's flush calls this same function on
    its buffered, staleness-discounted deltas.
  - :func:`federated_round` — the two recomposed; with all-ones (or ``None``)
    weights this is bitwise-identical to the pre-refactor flat-mean round.

The client→server uplink between the two phases is where compression plugs in
(``core/compression.Codec``): with a ``codec``, ``run_clients`` emits *encoded*
payloads (the wire format) plus each client's updated error-feedback residual,
and ``apply_aggregate`` decodes under the participation weight vector before the
one collective. The identity codec keeps the whole pipeline bitwise-transparent
(rng and DP-noise lanes included — tested), so every elastic/async equivalence
guarantee survives compression being threaded through. Error-feedback residuals
are PER-CLIENT state keyed by population client id: :func:`init_uplink_residuals`
builds the (P, ...) store and :func:`federated_round_with_uplink` gathers the
round's cohort rows and scatters them back, masked so a client that did not
upload keeps its residual untouched.

Population scale (P ≈ 100k and beyond) removes both dense memory terms behind
the same seams: :class:`SparseResidualStore` keeps EF rows only for clients that
were ever selected (bitwise the dense store through its gather/scatter
contract), and :func:`run_client_tile` + :func:`apply_aggregate_partial` stream
a large cohort through fixed-size C_tile tiles, folding each tile into weighted
partial sums (the :func:`hierarchical_mean` algebra: Σ wΔ per tile, ONE divide
at the server) — bitwise the flat round when C_tile == C.

The same functions drive the single-host simulator (tests, benchmarks) and the
multi-pod dry-run (launch/dryrun.py); only the jit shardings differ.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import Codec
from repro.core.inner_opt import (
    InnerOptConfig,
    global_norm,
    init_inner_state,
    inner_update,
)
from repro.core.outer_opt import OuterOptConfig, init_outer_state, outer_update
from repro.models.moe import HELD_LOAD, ROUTED_ROWS, ROUTER_LOAD


# the expert-share layers' per-step counters, averaged over a round's steps
ROUTER_COUNTERS = ("moe_local_slots", "moe_load_max_over_mean", "moe_routed_rows")
# loss metrics summed over a step's micro-batches rather than averaged
SUMMED_METRICS = (ROUTER_LOAD, HELD_LOAD, ROUTED_ROWS)


def load_counters(held: jax.Array, rows: jax.Array) -> Dict[str, jax.Array]:
    """``ROUTER_COUNTERS`` from a step's slots per held expert, (layers, held),
    and rows its routed parts were sized for, (layers,), averaged over the
    layers: the slots routed to the held experts, the held experts' largest
    load over their mean, and the rows."""
    local = jnp.sum(held, -1)
    return {
        "moe_local_slots": jnp.mean(local),
        "moe_load_max_over_mean": jnp.mean(
            jnp.max(held, -1) / jnp.maximum(local / held.shape[-1], 1e-9)),
        "moe_routed_rows": jnp.mean(rows),
    }


def _take_router_loads(metrics: Dict[str, Any]):
    """Takes the expert-share layers' loads out of a step's loss metrics (in
    place), adds their counters, and returns the loads for the load rule."""
    held, rows = metrics.pop(HELD_LOAD, None), metrics.pop(ROUTED_ROWS, None)
    if held is not None:
        metrics.update(load_counters(held, rows))
    return metrics.pop(ROUTER_LOAD, None)


@dataclass(frozen=True)
class FederatedConfig:
    clients_per_round: int = 8  # K — the client axis size of the jitted round
    local_steps: int = 500  # τ (paper §6.5)
    inner: InnerOptConfig = field(default_factory=InnerOptConfig)
    outer: OuterOptConfig = field(default_factory=OuterOptConfig)
    keep_inner_state: bool = False  # paper Fig 10 'FedAvg-KeepOpt' (not recommended)
    grad_accum: int = 1  # micro-batches per local step (paper §2.1.1 device batch size)
    pre_split_micro: bool = False  # batches carry (τ, C, grad_accum, B_micro, ...)
    fedprox_mu: float = 0.0  # FedProx proximal term strength
    dp_clip: float = 0.0  # per-client pseudo-gradient clip (0 = off)
    dp_noise: float = 0.0  # Gaussian noise std on the aggregate (0 = off)
    pseudo_grad_dtype: str = "float32"  # 'bfloat16' = beyond-paper compressed uplink


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------


def init_federated_state(
    fed: FederatedConfig, params, rng: Optional[jax.Array] = None
) -> Dict[str, Any]:
    state: Dict[str, Any] = {
        "params": params,
        "outer": init_outer_state(fed.outer, params),
        "round": jnp.zeros((), jnp.int32),
        "rng": rng if rng is not None else jax.random.PRNGKey(0),
    }
    if fed.keep_inner_state:
        inner = init_inner_state(fed.inner, params)
        state["inner"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (fed.clients_per_round,) + x.shape),
            inner,
        )
    return state


# ---------------------------------------------------------------------------
# Round step
# ---------------------------------------------------------------------------


def _broadcast_clients(tree, c: int):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (c,) + x.shape), tree
    )


def _mean_clients(tree):
    return jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), tree)


def _weigh_clients(x, weights):
    """Broadcast a (C,) weight vector over a (C, ...) leaf: x_k ← w_k x_k."""
    return x * weights.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)


def _safe_weight_sum(weights):
    return jnp.maximum(jnp.sum(weights), 1e-12)  # all-masked round → zero update


def _weighted_mean_clients(tree, weights):
    """Σ_k w_k x_k / Σ_k w_k over the leading client axis. With all-ones weights this
    is bitwise-identical to ``_mean_clients`` (x·1.0 is exact, Σ1 = C exactly), which
    is what lets the elastic round subsume the legacy flat-mean round."""
    w_sum = _safe_weight_sum(weights)

    def wmean(x):
        return jnp.sum(_weigh_clients(x, weights), axis=0) / w_sum.astype(x.dtype)

    return jax.tree_util.tree_map(wmean, tree)


def _accum_value_and_grad(loss_fn, params, batch, n_micro: int, pre_split: bool = False):
    """value_and_grad with gradient accumulation over ``n_micro`` micro-batches,
    bounding activation memory like DDP micro-batching. With ``pre_split`` the batch
    leaves already carry a leading (n_micro, ...) dim — required on the mesh, where
    reshaping a sharded batch dim would break GSPMD sharding propagation."""
    def fwd(p, b):
        # the forward pass's scope: its ops read ``.../jvp(fwd)/...`` and the
        # backward pass's ``.../transpose(jvp(fwd))/...`` in the compiled HLO
        with jax.named_scope("fwd"):
            return loss_fn(p, b)

    if n_micro <= 1:
        if pre_split:  # (1, B, ...) -> (B, ...)
            batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        return jax.value_and_grad(fwd, has_aux=True)(params, batch)

    if pre_split:
        micro = batch
    else:
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]), batch
        )

    def body(carry, mb):
        (loss, metrics), grads = jax.value_and_grad(fwd, has_aux=True)(params, mb)
        acc_grads, acc_loss, acc_metrics = carry
        acc_grads = jax.tree_util.tree_map(lambda a, g: a + g / n_micro, acc_grads, grads)
        acc_metrics = {
            k: jax.tree_util.tree_map(
                lambda a, m: a + (m if k in SUMMED_METRICS else m / n_micro), acc, metrics[k]
            )
            for k, acc in acc_metrics.items()
        }
        return (acc_grads, acc_loss + loss / n_micro, acc_metrics), None

    zeros_g = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    mb0 = jax.tree_util.tree_map(lambda x: x[0], micro)
    _, m0 = jax.eval_shape(lambda p, b: loss_fn(p, b), params, mb0)
    zeros_m = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), m0)
    (grads, loss, metrics), _ = jax.lax.scan(
        body, (zeros_g, jnp.zeros((), jnp.float32), zeros_m), micro
    )
    return (loss, metrics), grads


def run_clients(
    loss_fn: Callable,  # (params, batch) -> (loss, metrics_dict)
    fed: FederatedConfig,
    state: Dict[str, Any],  # needs 'params', 'round' (+ 'inner' when keep_inner_state)
    batches: Dict[str, jax.Array],  # leaves (τ, C, ...) — per-step per-client batches
    client_weights: Optional[jax.Array] = None,  # (C,) elastic participation weights
    shard_clients: Optional[Callable] = None,  # sharding-constraint hook (mesh runs)
    codec: Optional[Codec] = None,  # uplink codec; encodes the emitted deltas
    residuals: Optional[Any] = None,  # (C, ...) per-client error-feedback residuals
    tau_steps: Optional[jax.Array] = None,  # (C,) int32 realized per-client steps τ_i
) -> Tuple[Any, Dict[str, Any]]:
    """Client phase of a federated round (Algorithm 1, L.4–7): broadcast θ_global
    over the client axis, τ local inner-optimizer steps per client (no cross-client
    collectives), then per-client pseudo-gradients Δ_k = θ_global − θ_k with DP
    clipping and uplink compression applied.

    ``tau_steps`` is the straggler PARTIAL-PROGRESS mask: a traced (C,) vector of
    realized step counts τ_i ≤ τ. The scan still runs all τ iterations, but a
    client whose budget is spent (t ≥ τ_i) holds its params and inner state
    frozen via an in-graph ``where`` — so a slow client's delta reflects exactly
    the τ_i steps it finished, and no recompile happens when the τ_i vector
    changes round to round. ``tau_steps=None`` IS the all-full vector (τ_i = τ
    everywhere): there is one masked path, so the two agree by construction.

    Pure in ``(state, batches, weights, residuals)``; shared verbatim by the
    synchronous round and the async buffered path (``core/async_agg``), so the two
    aggregation schedules can never drift apart in client semantics. In the async
    path the caller passes a *stale* ``state`` (the params snapshot the client was
    dispatched with), which is exactly how a buffered delta acquires staleness.

    With a ``codec`` the emitted deltas are ENCODED payloads (the uplink wire
    format; ``apply_aggregate`` decodes them) and, for stateful codecs,
    ``residuals`` must be each cohort member's own error-feedback state —
    ``aux['residuals']`` returns the updated rows, with zero-weight (masked)
    clients keeping their old residual bitwise (they never uploaded). The identity
    codec encodes/decodes as exact no-ops, so ``codec=IdentityCodec()`` is bitwise
    ``codec=None``.

    Returns ``(deltas, aux)``: without a codec, ``deltas`` leaves are (C, ...)
    float32 pseudo-gradients ready for aggregation; ``aux`` carries the per-client
    inner states plus the client-side metric pieces consumed by
    ``federated_round``.
    """
    C = fed.clients_per_round
    elastic = client_weights is not None
    if elastic:
        w = client_weights.astype(jnp.float32)
        part = (w > 0).astype(jnp.float32)  # participation mask (C,)
        eff_k = jnp.maximum(jnp.sum(part), 1.0)
        metric_w = part / eff_k
    global_params = state["params"]
    client_params = _broadcast_clients(global_params, C)
    if shard_clients is not None:
        client_params = shard_clients(client_params)

    if fed.keep_inner_state:
        inner_states = state["inner"]
    else:
        with jax.named_scope("opt"):
            inner_states = jax.vmap(lambda p: init_inner_state(fed.inner, p))(
                client_params
            )

    seq_step0 = state["round"].astype(jnp.int32) * fed.local_steps
    if tau_steps is None:
        tau_steps = jnp.full((C,), fed.local_steps, jnp.int32)
    tau_steps = tau_steps.astype(jnp.int32)

    def local_step(carry, batch_t):
        params_c, inner_c, t = carry

        def one_client(params, inner, batch):
            (loss, metrics), grads = _accum_value_and_grad(
                loss_fn, params, batch, fed.grad_accum, pre_split=fed.pre_split_micro
            )
            loads = _take_router_loads(metrics)
            if fed.fedprox_mu > 0.0:
                grads = jax.tree_util.tree_map(
                    lambda g, p, gp: g + fed.fedprox_mu * (p - gp),
                    grads,
                    params,
                    global_params,
                )
            with jax.named_scope("opt"):
                new_params, new_inner, opt_metrics = inner_update(
                    fed.inner, params, grads, inner, seq_step0 + t, bias_loads=loads
                )
            metrics = dict(metrics, **opt_metrics)
            return new_params, new_inner, metrics

        new_params_c, new_inner_c, metrics_c = jax.vmap(one_client)(
            params_c, inner_c, batch_t
        )
        # partial progress: clients whose step budget is spent hold their
        # params/inner state (the masked scan lanes still execute, their
        # results are discarded — exactly the elastic-weights discipline)
        active = t < tau_steps  # (C,)

        def _hold(new, old):
            return jnp.where(
                active.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
            )

        with jax.named_scope("opt"):  # the hold is the update's last op
            new_params_c = jax.tree_util.tree_map(_hold, new_params_c, params_c)
            new_inner_c = jax.tree_util.tree_map(_hold, new_inner_c, inner_c)
        act = active.astype(jnp.float32)
        # metrics weighted over the clients actually stepping at time t, so
        # masked clients' losses never pollute the round metrics
        raw_w = part * act if elastic else act
        n_active = jnp.sum(raw_w)
        step_w = raw_w / jnp.maximum(n_active, 1.0)
        step_metrics = {k: jnp.sum(v * step_w) for k, v in metrics_c.items()}
        step_metrics["_n_active"] = n_active
        return (new_params_c, new_inner_c, t + 1), step_metrics

    (client_params, inner_states, _), step_metrics = jax.lax.scan(
        local_step, (client_params, inner_states, jnp.zeros((), jnp.int32)), batches
    )
    # DEAD steps — every weighted client past its τ_i — reduced over an empty
    # set above: forward-fill each such step from the last step that had an
    # active client, so step_metrics[-1] is "the last training signal
    # observed" and the per-step series is never zero-diluted
    n_active = step_metrics.pop("_n_active")  # (τ,)
    t_idx = jnp.arange(n_active.shape[0], dtype=jnp.int32)
    last_live = jax.lax.cummax(jnp.where(n_active > 0, t_idx, -1))
    last_live = jnp.maximum(last_live, 0)  # step 0 is always live (τ_i ≥ 1)
    step_metrics = {k: v[last_live] for k, v in step_metrics.items()}

    if fed.keep_inner_state and elastic:
        # masked clients never actually ran this round: keep their previous inner
        # state instead of the τ steps of stale-data Adam statistics the masked
        # lanes of the scan just produced. (All-ones weights: where(True, new, _)
        # returns `new` exactly, preserving the bitwise flat-round identity.)
        keep = client_weights > 0

        def _restore(new, old):
            return jnp.where(keep.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)

        inner_states = jax.tree_util.tree_map(_restore, inner_states, state["inner"])

    # ---- pseudo-gradients + post-processing (Algorithm 1, L.7 & L.26) ----
    deltas = jax.tree_util.tree_map(
        lambda g, c: g[None].astype(jnp.float32) - c.astype(jnp.float32),
        global_params,
        client_params,
    )

    if fed.dp_clip > 0.0:
        norms = jax.vmap(global_norm)(deltas)  # (C,)
        scale = jnp.minimum(1.0, fed.dp_clip / (norms + 1e-9))
        deltas = jax.tree_util.tree_map(
            lambda d: d * scale.reshape((-1,) + (1,) * (d.ndim - 1)), deltas
        )

    new_residuals = None
    if codec is not None:  # encoded uplink: deltas leave as codec payloads
        enc_keys = None
        if codec.needs_rng:
            # derived, never consumed: fold_in leaves the server rng lane
            # untouched, so stochastic rounding can't perturb the DP-noise draw
            base = state["rng"] if "rng" in state else jax.random.PRNGKey(0)
            per_round = jax.random.fold_in(base, state["round"].astype(jnp.uint32))
            enc_keys = jax.random.split(per_round, C)
        if codec.stateful:
            if residuals is None:  # first-ever upload for this cohort
                residuals = jax.vmap(codec.init_residual)(deltas)
            if codec.needs_rng:
                deltas, new_residuals = jax.vmap(
                    lambda d, e, k: codec.encode(d, e, rng=k)
                )(deltas, residuals, enc_keys)
            else:
                deltas, new_residuals = jax.vmap(
                    lambda d, e: codec.encode(d, e)
                )(deltas, residuals)
            if elastic:
                # a masked client never uploaded: its dropped-mass residual must
                # stay bitwise untouched (all-ones weights: where(True, new, _)
                # is exact, preserving the identity-codec bitwise guarantee)
                keep = client_weights > 0

                def _keep_old(new, old):
                    return jnp.where(
                        keep.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
                    )

                new_residuals = jax.tree_util.tree_map(
                    _keep_old, new_residuals, residuals
                )
        elif codec.needs_rng:
            deltas = jax.vmap(lambda d, k: codec.encode(d, rng=k)[0])(deltas, enc_keys)
        else:
            deltas = jax.vmap(lambda d: codec.encode(d)[0])(deltas)
    elif fed.pseudo_grad_dtype != "float32":  # legacy flat-cast compressed uplink
        dt = jnp.dtype(fed.pseudo_grad_dtype)
        deltas = jax.tree_util.tree_map(
            lambda d: d.astype(dt).astype(jnp.float32), deltas
        )

    # client-side metric pieces (paper Figs 7, 8)
    client_norms = jax.vmap(global_norm)(client_params)  # (C,)
    if elastic:
        client_norm_mean = jnp.sum(client_norms * metric_w)
        avg_client_norm = global_norm(_weighted_mean_clients(client_params, w))
    else:
        client_norm_mean = jnp.mean(client_norms)
        avg_client_norm = global_norm(_mean_clients(client_params))

    aux = {
        "inner": inner_states,
        "step_metrics": step_metrics,
        "client_model_norm_mean": client_norm_mean,
        "avg_client_model_norm": avg_client_norm,
    }
    if new_residuals is not None:
        res_norms = jax.vmap(global_norm)(new_residuals)  # (C,) EF telemetry
        aux["residuals"] = new_residuals
        aux["uplink_residual_norm"] = (
            jnp.sum(res_norms * metric_w) if elastic else jnp.mean(res_norms)
        )
    return deltas, aux


def aggregation_metrics(
    delta_norms: jax.Array,  # (C,) per-client delta norms
    pg_norm: jax.Array,  # () norm of the aggregated (post-noise) pseudo-gradient
    client_weights: Optional[jax.Array],  # (C,) or None (flat mean)
) -> Dict[str, jax.Array]:
    """The scalar aggregation monitors (paper Figs 7, 8), shared by the jnp
    reference server phase and the fused flat-buffer phase
    (``kernels/fedcore.fused_apply_aggregate``) — ONE formula set, fed either
    from per-leaf norm passes (ref) or from in-kernel accumulators (fused), so
    the two paths can never drift apart on a metrics fix.

    Weighted consensus: Σw_k d_k = W·pg, so the cross terms are
    ||pg||²W² − Σ(w_k||d_k||)², normalized over the off-diagonal weight mass.
    The off-diagonal mass vanishes at K_eff=1 — the 0/ε there would amplify fp
    rounding into garbage, and a lone client trivially agrees with itself.
    """
    c = delta_norms.shape[0]
    elastic = client_weights is not None
    # NaN defense: a single non-finite client norm must not poison every
    # reduction below. Non-finite lanes are masked out of participation and
    # zeroed in the norm sums (0·NaN = NaN, so a zero *weight* alone is not
    # enough — the norm itself is rewritten), and surface as a dedicated
    # ``nonfinite_deltas`` count instead. All-finite cohorts take the same
    # ops through all-True masks, so the healthy path stays bitwise.
    finite = jnp.isfinite(delta_norms)
    dn = jnp.where(finite, delta_norms, 0.0)
    if elastic:
        w = jnp.where(finite, client_weights.astype(jnp.float32), 0.0)
        part = (w > 0).astype(jnp.float32)
        eff_k = jnp.maximum(jnp.sum(part), 1.0)
        metric_w = part / eff_k
        w_sum = jnp.sum(w)
        w_sq_sum = jnp.sum(jnp.square(w))
        sum_sq = jnp.sum(jnp.square(w * dn))
        norm_of_sum_sq = jnp.square(pg_norm) * jnp.square(w_sum)
        off_diag = jnp.square(w_sum) - w_sq_sum
        pairwise_dot = jnp.where(
            eff_k > 1.5,
            (norm_of_sum_sq - sum_sq) / jnp.maximum(off_diag, 1e-12),
            sum_sq / jnp.maximum(w_sq_sum, 1e-12),
        )
        mean_sq_norm = sum_sq / jnp.maximum(w_sq_sum, 1e-12)
        w_norm = w / jnp.maximum(w_sum, 1e-12)
        weight_entropy = -jnp.sum(
            jnp.where(w_norm > 0, w_norm * jnp.log(jnp.maximum(w_norm, 1e-30)), 0.0)
        )
        effective_clients = jnp.sum(part)
        delta_norm_mean = jnp.sum(dn * metric_w)
    else:
        sum_sq = jnp.sum(jnp.square(dn))
        norm_of_sum_sq = jnp.square(pg_norm) * c * c
        pairwise_dot = (norm_of_sum_sq - sum_sq) / jnp.maximum(1, c * (c - 1))
        mean_sq_norm = sum_sq / c
        weight_entropy = jnp.log(jnp.asarray(c, jnp.float32))
        effective_clients = jnp.sum(finite.astype(jnp.float32))
        delta_norm_mean = jnp.sum(dn) / jnp.maximum(
            jnp.sum(finite.astype(jnp.float32)), 1.0
        )
    consensus = pairwise_dot / (mean_sq_norm + 1e-12)  # ~cosine alignment
    return {
        "pseudo_grad_norm": pg_norm,
        "client_delta_norm_mean": delta_norm_mean,
        "client_consensus": consensus,
        "effective_clients": effective_clients,
        "weight_entropy": weight_entropy,
        "nonfinite_deltas": jnp.sum((~finite).astype(jnp.float32)),
    }


#: round metrics worth attaching to telemetry spans (the divergence
#: leading-indicators, paper Figs 7/8) — a curated subset so span attrs stay
#: small and schema-stable
TRACE_METRIC_KEYS = (
    "train_loss",
    "pseudo_grad_norm",
    "client_consensus",
    "weight_entropy",
    "effective_clients",
    "model_norm",
)


def trace_attrs(metrics: Dict[str, Any], keys=TRACE_METRIC_KEYS) -> Dict[str, float]:
    """Host-side float view of a round's telemetry-worthy metrics.

    The device→host sync happens HERE, once, and only when a caller is
    actually tracing — the jitted round itself never knows telemetry exists,
    which is what keeps traced and untraced runs bitwise identical.
    """
    return {k: float(metrics[k]) for k in keys if k in metrics}


def apply_aggregate(
    fed: FederatedConfig,
    state: Dict[str, Any],  # needs 'params', 'outer', 'round', 'rng'
    deltas,  # pytree with leading client/buffer axis (C, ...) — pseudo-gradients
    client_weights: Optional[jax.Array] = None,  # (C,) aggregation weights
    codec: Optional[Codec] = None,  # uplink codec; decodes encoded deltas first
) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """Server phase of a federated round (Algorithm 1, L.8–9): ONE weighted
    aggregation of the pseudo-gradients (the round's single cross-client
    collective), optional DP noise on the aggregate, and the outer-optimizer
    update. Pure in ``(state, deltas, weights)`` — jit it.

    With a ``codec``, ``deltas`` arrive as encoded payloads (``run_clients``'s
    wire format) and are decoded to float32 per client *before* the weighted
    mean — the weight vector therefore applies to the decoded deltas, so elastic
    participation and compression compose without either knowing about the other.

    The leading axis of ``deltas`` need not be a synchronous cohort: the async
    aggregator's flush (``core/async_agg.flush_buffer``) calls this exact function
    on its delta *buffer* with staleness-discounted weights, which is what keeps
    the sync and async server updates algebraically (and, at matched inputs,
    bitwise) identical.
    """
    if codec is not None:
        deltas = jax.vmap(codec.decode)(deltas)

    # THE once-per-round collective on the mesh (weighted when elastic)
    if client_weights is not None:
        pseudo_grad = _weighted_mean_clients(
            deltas, client_weights.astype(jnp.float32)
        )
    else:
        pseudo_grad = _mean_clients(deltas)

    delta_norms = jax.vmap(global_norm)(deltas)
    return _finish_aggregate(fed, state, pseudo_grad, delta_norms, client_weights)


def _finish_aggregate(
    fed: FederatedConfig,
    state: Dict[str, Any],  # needs 'params', 'outer', 'round', 'rng'
    pseudo_grad,  # pytree, NO client axis — the aggregated update direction
    delta_norms: jax.Array,  # (C,) per-client decoded delta norms (metrics)
    client_weights: Optional[jax.Array],  # (C,) or None (flat mean)
) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """Shared tail of every server phase: rng split → optional DP noise →
    outer update → aggregation metrics → new state. Factored out of
    :func:`apply_aggregate` so robust estimators (``core/robust.py``) can swap
    the weighted mean for a trimmed mean / coordinate median and reuse the
    identical noise/update/metrics sequence. Same ops in the same order as the
    pre-refactor tail, so the plain-mean path through here is bitwise unchanged.
    """
    elastic = client_weights is not None
    # the leading axis is the cohort for the sync round but the *buffer* for the
    # async flush — size it from the data, not from fed.clients_per_round
    C = delta_norms.shape[0]

    rng, noise_rng = jax.random.split(state["rng"])
    if fed.dp_noise > 0.0:
        # noise must cover the worst single client's influence on the aggregate:
        # for the weighted mean that is max_k w_k/Σw (= 1/C when uniform), NOT
        # 1/K_eff — with skewed data-size weights one heavy client can dominate
        if elastic:
            w = client_weights.astype(jnp.float32)
            scale = fed.dp_noise * jnp.max(w) / jnp.maximum(jnp.sum(w), 1e-12)
        else:
            scale = fed.dp_noise / C
        leaves, treedef = jax.tree_util.tree_flatten(pseudo_grad)
        keys = jax.random.split(noise_rng, len(leaves))
        leaves = [
            l + scale * jax.random.normal(k, l.shape, l.dtype)
            for l, k in zip(leaves, keys)
        ]
        pseudo_grad = jax.tree_util.tree_unflatten(treedef, leaves)

    new_global, new_outer = outer_update(
        fed.outer, state["params"], pseudo_grad, state["outer"]
    )

    # ---- aggregation metrics (paper Figs 7, 8) — shared formula set ----
    metrics = dict(
        aggregation_metrics(delta_norms, global_norm(pseudo_grad), client_weights),
        global_model_norm=global_norm(new_global),
    )

    new_state = {
        "params": new_global,
        "outer": new_outer,
        "round": state["round"] + 1,
        "rng": rng,
    }
    return new_state, metrics


def federated_round(
    loss_fn: Callable,  # (params, batch) -> (loss, metrics_dict)
    fed: FederatedConfig,
    state: Dict[str, Any],
    batches: Dict[str, jax.Array],  # leaves (τ, C, ...) — per-step per-client batches
    client_weights: Optional[jax.Array] = None,  # (C,) elastic participation weights
    shard_clients: Optional[Callable] = None,  # sharding-constraint hook (mesh runs)
    codec: Optional[Codec] = None,  # uplink codec (encode client-side, decode server-side)
    residuals: Optional[Any] = None,  # (C, ...) cohort error-feedback residuals
    tau_steps: Optional[jax.Array] = None,  # (C,) int32 realized per-client steps τ_i
    apply_fn: Optional[Callable] = None,  # server-phase override (fused Pallas path)
) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """One full federated round — :func:`run_clients` composed with
    :func:`apply_aggregate`. Pure function of (state, batches, weights, residuals,
    tau_steps) — jit it.

    ``apply_fn`` swaps the server phase for a drop-in replacement with
    ``apply_aggregate``'s exact signature and state/metrics contract — the
    ``--fused-server`` flag plugs ``kernels/fedcore.fused_apply_aggregate``
    (the flat-buffer Pallas pass) in here. ``None`` keeps this jnp reference
    phase, bitwise-unchanged.

    ``tau_steps`` enables straggler partial progress (see :func:`run_clients`);
    the caller's weight policy (``core/aggregator``) is expected to scale the
    weights by τ_i/τ so a partial delta is credited fractionally.
    ``tau_steps=None`` is the all-full τ-vector.

    ``client_weights`` makes the round *elastic*: a (C,) vector of aggregation
    weights (e.g. FedAvg data sizes from a ``ParticipationPlan``), where a zero
    marks a dropped/straggling/unavailable client whose delta is excluded from the
    aggregate. Because the weights are a traced array argument, any effective
    cohort K_eff ≤ C runs inside the one compiled computation — no recompile when
    participation changes round to round. ``None`` (and equivalently all-ones
    weights, bitwise) reproduces the legacy flat-mean round.

    ``codec`` compresses the uplink between the two phases; the identity codec
    (and ``None``) keep the round bitwise the uncompressed one. For stateful
    codecs the updated cohort residuals come back as
    ``new_state['uplink_residuals']`` (plus in-graph ``uplink_residual_norm``
    telemetry); use :func:`federated_round_with_uplink` when the residuals live
    in a population-keyed store.
    """
    with jax.named_scope("client"):
        deltas, aux = run_clients(
            loss_fn, fed, state, batches,
            client_weights=client_weights, shard_clients=shard_clients,
            codec=codec, residuals=residuals, tau_steps=tau_steps,
        )
    with jax.named_scope("server"):
        new_state, agg_metrics = (apply_fn or apply_aggregate)(
            fed, state, deltas, client_weights=client_weights, codec=codec
        )

    step_metrics = aux["step_metrics"]
    metrics = {
        "train_loss": step_metrics["loss"][-1],
        "train_loss_mean": jnp.mean(step_metrics["loss"]),
        "client_grad_norm": step_metrics["grad_norm"][-1],
        "applied_update_norm": step_metrics["applied_update_norm"][-1],
        "lr": step_metrics["lr"][-1],
        "client_model_norm_mean": aux["client_model_norm_mean"],
        "avg_client_model_norm": aux["avg_client_model_norm"],
        **{k: jnp.mean(step_metrics[k]) for k in ROUTER_COUNTERS if k in step_metrics},
        **agg_metrics,
    }

    if fed.keep_inner_state:
        new_state["inner"] = aux["inner"]
    if "residuals" in aux:
        new_state["uplink_residuals"] = aux["residuals"]
        metrics["uplink_residual_norm"] = aux["uplink_residual_norm"]
    return new_state, metrics


# ---------------------------------------------------------------------------
# Population-keyed error-feedback residual store
# ---------------------------------------------------------------------------


def init_uplink_residuals(codec: Optional[Codec], params, population: int):
    """The per-client error-feedback store: one zero residual row per POPULATION
    client, leaves (P, ...) float32. This is the ownership story for compression
    residuals — a client's row follows it across rounds, cohorts, and (async)
    dispatches, and the store checkpoints/resumes as ordinary state. ``None`` for
    stateless codecs (no residual to own)."""
    if codec is None or not codec.stateful:
        return None
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((population,) + p.shape, jnp.float32), params
    )


class SparseResidualStore:
    """Population-keyed error-feedback store that materializes rows ONLY for
    clients that have ever sat in a cohort — the flat-memory replacement for the
    dense ``(P, ...)`` array :func:`init_uplink_residuals` builds.

    The store is a host-side ``id → row`` map (each row a params-shaped float32
    pytree, no leading axis). Its observable semantics are bitwise the dense
    store's: a dense store starts all-zero, so gathering a never-materialized id
    returns the same zero row ``jnp.take`` would, and scattering a cohort's rows
    back writes the same values ``r.at[sel].set(n)`` would. Memory, however, is
    ``O(#ever-selected · N)`` instead of ``O(P · N)`` — at P=100k with a small
    ever-selected set the dense store is never allocated at all.

    Checkpointing: :meth:`stacked` emits the rows as one ``(n_ids, ...)`` pytree
    in sorted-id order (the manifest records the id list); :meth:`to_dense`
    reproduces the legacy PR-3 dense layout; :meth:`from_dense` ingests a legacy
    dense checkpoint, leaving all-zero rows unmaterialized (indistinguishable
    through ``gather``).
    """

    def __init__(self, params_like):
        self._template = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(tuple(p.shape), jnp.float32), params_like
        )
        self._rows: Dict[int, Any] = {}

    @classmethod
    def create(cls, codec: Optional[Codec], params) -> Optional["SparseResidualStore"]:
        """``None`` for stateless codecs — mirrors :func:`init_uplink_residuals`."""
        if codec is None or not codec.stateful:
            return None
        return cls(params)

    # ---- row accounting ----

    def ids(self):
        """Sorted population ids that own a materialized row."""
        return sorted(self._rows)

    def __len__(self) -> int:
        return len(self._rows)

    def __contains__(self, cid) -> bool:
        return int(cid) in self._rows

    @property
    def row_nbytes(self) -> int:
        return sum(
            int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
            for s in jax.tree_util.tree_leaves(self._template)
        )

    @property
    def nbytes(self) -> int:
        """Exact bytes held: rows × params size. The dense equivalent is P × params."""
        return len(self._rows) * self.row_nbytes

    def _zero_row(self):
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self._template
        )

    def row(self, cid):
        """One client's row; never-materialized ids read as the zero row."""
        cid = int(cid)
        if cid in self._rows:
            return self._rows[cid]
        return self._zero_row()

    # ---- the gather/scatter contract the round functions use ----

    def gather(self, ids):
        """Stacked ``(C, ...)`` cohort rows for ``plan.selected`` — bitwise what
        ``jnp.take(dense, sel, axis=0)`` returns (unmaterialized ids are zero)."""
        rows = [self.row(i) for i in np.asarray(ids).tolist()]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)

    def scatter(self, ids, stacked, mask=None) -> None:
        """Write a cohort's updated rows back, materializing on first touch.

        ``mask[k]`` False marks slot ``k`` as a tile PADDING slot (not a real
        cohort member) and skips it, so padding never materializes a row. Real
        cohort members always materialize — including zero-weight (dropped /
        straggling) ones, whose rows come back bitwise unchanged from
        ``run_clients``; that matches the dense scatter, which also writes their
        unchanged rows back.
        """
        for k, cid in enumerate(np.asarray(ids).tolist()):
            if mask is not None and not bool(mask[k]):
                continue
            self._rows[int(cid)] = jax.tree_util.tree_map(lambda x: x[k], stacked)

    # ---- checkpoint lanes ----

    def stacked(self):
        """All rows as one ``(n_ids, ...)`` pytree in sorted-id order (the canonical
        checkpoint lane; pair with :meth:`ids` in the manifest)."""
        ids = self.ids()
        if not ids:
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros((0,) + tuple(s.shape), s.dtype), self._template
            )
        rows = [self._rows[i] for i in ids]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)

    def to_dense(self, population: int):
        """Materialize the legacy dense ``(P, ...)`` layout (PR-3 schema)."""
        dense = jax.tree_util.tree_map(
            lambda s: jnp.zeros((population,) + tuple(s.shape), s.dtype),
            self._template,
        )
        ids = self.ids()
        if not ids:
            return dense
        sel = jnp.asarray(ids, jnp.int32)
        return jax.tree_util.tree_map(
            lambda d, s: d.at[sel].set(s), dense, self.stacked()
        )

    @classmethod
    def from_stacked(cls, params_like, ids, stacked) -> "SparseResidualStore":
        """Rebuild from the canonical checkpoint lane (manifest ids + stacked rows)."""
        store = cls(params_like)
        for k, cid in enumerate(int(i) for i in ids):
            store._rows[cid] = jax.tree_util.tree_map(lambda x: jnp.asarray(x[k]), stacked)
        return store

    @classmethod
    def from_dense(cls, params_like, dense) -> "SparseResidualStore":
        """Ingest a legacy dense ``(P, ...)`` store. All-zero rows stay
        unmaterialized — a zero row and no row are indistinguishable through
        :meth:`gather`, so the conversion is semantics-preserving."""
        store = cls(params_like)
        leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(dense)]
        population = leaves[0].shape[0]
        owned = np.zeros(population, dtype=bool)
        for leaf in leaves:
            owned |= leaf.reshape(population, -1).any(axis=1)
        for cid in np.nonzero(owned)[0].tolist():
            store._rows[int(cid)] = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x[cid]), dense
            )
        return store


def federated_round_with_uplink(
    loss_fn: Callable,
    fed: FederatedConfig,
    codec: Optional[Codec],
    state: Dict[str, Any],
    batches: Dict[str, jax.Array],
    client_weights: Optional[jax.Array] = None,
    selected: Optional[jax.Array] = None,  # (C,) population ids bound to the client axis
    shard_clients: Optional[Callable] = None,
    tau_steps: Optional[jax.Array] = None,  # (C,) int32 realized per-client steps τ_i
    apply_fn: Optional[Callable] = None,  # server-phase override (fused Pallas path)
) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """:func:`federated_round` wired to the population-keyed residual store.

    ``state['uplink_residuals']`` holds one error-feedback row per population
    client; ``selected`` binds this round's client axis to population ids (the
    ``ParticipationPlan.selected`` vector, traced — changing cohorts never
    recompiles). The cohort's rows are gathered, the round runs, and the updated
    rows scatter back — masked clients' rows come back bitwise unchanged (the
    gather/scatter is then a no-op for them), so padding slots can never clobber
    a live client's residual. ``selected`` always holds distinct ids (sampler
    contract), so the scatter is order-independent.

    Stateless codecs (and ``codec=None``) reduce to plain ``federated_round``.
    """
    if codec is None or not codec.stateful:
        return federated_round(
            loss_fn, fed, state, batches, client_weights=client_weights,
            shard_clients=shard_clients, codec=codec, tau_steps=tau_steps,
            apply_fn=apply_fn,
        )
    if selected is None:
        raise ValueError("stateful uplink codec requires the cohort's population ids")
    store = state["uplink_residuals"]
    core = {k: v for k, v in state.items() if k != "uplink_residuals"}
    sel = selected.astype(jnp.int32)
    cohort_res = jax.tree_util.tree_map(lambda r: jnp.take(r, sel, axis=0), store)
    new_core, metrics = federated_round(
        loss_fn, fed, core, batches, client_weights=client_weights,
        shard_clients=shard_clients, codec=codec, residuals=cohort_res,
        tau_steps=tau_steps, apply_fn=apply_fn,
    )
    new_cohort_res = new_core.pop("uplink_residuals")
    new_core["uplink_residuals"] = jax.tree_util.tree_map(
        lambda r, n: r.at[sel].set(n), store, new_cohort_res
    )
    return new_core, metrics


# ---------------------------------------------------------------------------
# Streamed cohorts: tile client phase + partial-sum server phase
# ---------------------------------------------------------------------------
#
# A large cohort C is streamed through the jitted client phase in fixed-size
# tiles of C_tile clients, and the tiles fold into the round via the
# `hierarchical_mean` algebra: each tile forwards Σ_k w_k Δ_k (and its decoded
# per-client delta norms), the server accumulates the tile sums, and divides by
# Σ w ONCE in `apply_aggregate_partial`. The (C, N) delta buffer and the
# (C,)-batched client state are therefore bounded by C_tile regardless of C.
# With one tile (C_tile == C) the op sequence is exactly
# `_weighted_mean_clients` split across two jits — bitwise the flat round.


#: rng stream tag for tiles t > 0 — tile 0 keeps state['rng'] untouched so the
#: single-tile round is bitwise the flat round, rng-consuming codecs included.
TILE_RNG_TAG = 0x7113


def tile_rng(rng: jax.Array, tile_index: int) -> jax.Array:
    """Per-tile rng lane: tile 0 is the round rng itself (the bitwise identity);
    later tiles fold in a tagged tile index so their codec encode keys are
    decorrelated from each other and from the server's DP-noise lane."""
    if tile_index == 0:
        return rng
    return jax.random.fold_in(rng, TILE_RNG_TAG + tile_index)


def run_client_tile(
    loss_fn: Callable,
    fed: FederatedConfig,  # clients_per_round == C_tile
    state: Dict[str, Any],  # needs 'params', 'round', 'rng' (a per-tile rng lane)
    batches: Dict[str, jax.Array],  # leaves (τ, C_tile, ...)
    client_weights: jax.Array,  # (C_tile,) — REQUIRED (pads carry weight 0)
    shard_clients: Optional[Callable] = None,
    codec: Optional[Codec] = None,
    residuals: Optional[Any] = None,  # (C_tile, ...) cohort error-feedback rows
    tau_steps: Optional[jax.Array] = None,  # (C_tile,) int32
    return_deltas: bool = False,  # also return the decoded (C_tile, ...) deltas
) -> Dict[str, Any]:
    """One cohort TILE of a streamed round: :func:`run_clients` on ``C_tile``
    clients, folded to weighted partial sums. Pure — jit it once and replay it
    over every tile of every round.

    ``return_deltas`` adds the decoded per-client deltas to the output —
    required by the robust tiled fold (``core/robust.py``), whose order
    statistics cannot be recovered from the weighted partial sum alone. The
    default path never materializes them past this function.

    Returns a dict of partial results:

    - ``delta_sum``  — Σ_k w_k Δ_k over the tile (decoded), the island-style
      partial numerator of the weighted mean (``hierarchical_mean`` algebra).
    - ``delta_norms`` — (C_tile,) decoded per-client delta norms (for
      :func:`aggregation_metrics`, concatenated across tiles).
    - ``residuals`` / ``uplink_residual_norm`` — updated EF rows (stateful codecs).
    - ``eff_k`` + the :func:`run_clients` telemetry pieces, recombined across
      tiles by :func:`combine_tile_metrics`.

    The partial numerator uses the exact op sequence of
    ``_weighted_mean_clients`` (``jnp.sum(_weigh_clients(x, w), axis=0)``), and
    :func:`apply_aggregate_partial` performs the identical final divide — with a
    single tile the round is bitwise :func:`federated_round`.
    """
    if fed.keep_inner_state:
        raise ValueError(
            "streamed cohorts cannot keep per-client inner state across rounds "
            "(the (C,)-batched inner store is exactly the memory term tiling "
            "removes); use keep_inner_state=False"
        )
    with jax.named_scope("client"):
        deltas, aux = run_clients(
            loss_fn, fed, state, batches,
            client_weights=client_weights, shard_clients=shard_clients,
            codec=codec, residuals=residuals, tau_steps=tau_steps,
        )
    if codec is not None:
        deltas = jax.vmap(codec.decode)(deltas)
    w = client_weights.astype(jnp.float32)
    out = {
        "delta_sum": jax.tree_util.tree_map(
            lambda x: jnp.sum(_weigh_clients(x, w), axis=0), deltas
        ),
        "delta_norms": jax.vmap(global_norm)(deltas),
        "eff_k": jnp.sum((w > 0).astype(jnp.float32)),
        "step_metrics": aux["step_metrics"],
        "client_model_norm_mean": aux["client_model_norm_mean"],
        "avg_client_model_norm": aux["avg_client_model_norm"],
    }
    if "residuals" in aux:
        out["residuals"] = aux["residuals"]
        out["uplink_residual_norm"] = aux["uplink_residual_norm"]
    if return_deltas:
        out["deltas"] = deltas
    return out


def apply_aggregate_partial(
    fed: FederatedConfig,
    state: Dict[str, Any],  # needs 'params', 'outer', 'round', 'rng'
    delta_sum,  # pytree — Σ over ALL tiles of Σ_k w_k Δ_k (no client axis)
    client_weights: jax.Array,  # (C_total,) full-cohort weights (pads at w=0)
    delta_norms: jax.Array,  # (C_total,) decoded per-client delta norms
) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """Server phase of a streamed round: the ONE divide of the two-tier
    aggregation, then DP noise and the outer update — :func:`apply_aggregate`
    with the weighted mean's numerator precomputed by the tiles.

    Mirrors ``apply_aggregate`` operation for operation (same rng split, same
    elastic DP-noise scale, same metrics formulas), so a single-tile round is
    bitwise the flat round. Zero-weight padding slots are invisible: they add
    exact zeros to ``delta_sum``, nothing to Σw / max(w), and
    :func:`aggregation_metrics` masks them out via ``w > 0``.
    """
    w = client_weights.astype(jnp.float32)
    w_sum = _safe_weight_sum(w)
    pseudo_grad = jax.tree_util.tree_map(
        lambda s: s / w_sum.astype(s.dtype), delta_sum
    )

    rng, noise_rng = jax.random.split(state["rng"])
    if fed.dp_noise > 0.0:
        scale = fed.dp_noise * jnp.max(w) / jnp.maximum(jnp.sum(w), 1e-12)
        leaves, treedef = jax.tree_util.tree_flatten(pseudo_grad)
        keys = jax.random.split(noise_rng, len(leaves))
        leaves = [
            l + scale * jax.random.normal(k, l.shape, l.dtype)
            for l, k in zip(leaves, keys)
        ]
        pseudo_grad = jax.tree_util.tree_unflatten(treedef, leaves)

    new_global, new_outer = outer_update(
        fed.outer, state["params"], pseudo_grad, state["outer"]
    )
    metrics = dict(
        aggregation_metrics(delta_norms, global_norm(pseudo_grad), client_weights),
        global_model_norm=global_norm(new_global),
    )
    new_state = {
        "params": new_global,
        "outer": new_outer,
        "round": state["round"] + 1,
        "rng": rng,
    }
    return new_state, metrics


def combine_tile_metrics(tile_outs) -> Dict[str, jax.Array]:
    """Fold per-tile client telemetry into :func:`federated_round`'s metric dict
    (everything except the ``apply_aggregate_partial`` server metrics).

    One tile: passed through verbatim (bitwise the flat round's assembly). More
    tiles: each tile's participation-weighted means recombine weighted by its
    effective client count — exact algebra for the per-step scalar series (which
    are already Σ v·part/eff within the tile), a documented approximation for
    ``avg_client_model_norm`` and ``uplink_residual_norm`` (norms of means do
    not decompose across tiles; these are monitoring-only quantities)."""
    if len(tile_outs) == 1:
        t = tile_outs[0]
        sm = t["step_metrics"]
        out = {
            "train_loss": sm["loss"][-1],
            "train_loss_mean": jnp.mean(sm["loss"]),
            "client_grad_norm": sm["grad_norm"][-1],
            "applied_update_norm": sm["applied_update_norm"][-1],
            "lr": sm["lr"][-1],
            "client_model_norm_mean": t["client_model_norm_mean"],
            "avg_client_model_norm": t["avg_client_model_norm"],
        }
        if "uplink_residual_norm" in t:
            out["uplink_residual_norm"] = t["uplink_residual_norm"]
        return out

    eff = jnp.stack([t["eff_k"].astype(jnp.float32) for t in tile_outs])
    tile_w = eff / jnp.maximum(jnp.sum(eff), 1.0)  # all-pad tiles weigh 0

    def fold(vals):
        v = jnp.stack(vals)
        return jnp.sum(v * tile_w.reshape((-1,) + (1,) * (v.ndim - 1)), axis=0)

    sm = {
        k: fold([t["step_metrics"][k] for t in tile_outs])
        for k in tile_outs[0]["step_metrics"]
    }
    out = {
        "train_loss": sm["loss"][-1],
        "train_loss_mean": jnp.mean(sm["loss"]),
        "client_grad_norm": sm["grad_norm"][-1],
        "applied_update_norm": sm["applied_update_norm"][-1],
        "lr": sm["lr"][-1],
        "client_model_norm_mean": fold(
            [t["client_model_norm_mean"] for t in tile_outs]
        ),
        "avg_client_model_norm": fold(
            [t["avg_client_model_norm"] for t in tile_outs]
        ),
    }
    if "uplink_residual_norm" in tile_outs[0]:
        out["uplink_residual_norm"] = fold(
            [t["uplink_residual_norm"] for t in tile_outs]
        )
    return out


# ---------------------------------------------------------------------------
# Centralized baseline (paper's comparison target)
# ---------------------------------------------------------------------------


def init_centralized_state(inner: InnerOptConfig, params) -> Dict[str, Any]:
    return {
        "params": params,
        "inner": init_inner_state(inner, params),
        "step": jnp.zeros((), jnp.int32),
    }


def centralized_step(
    loss_fn: Callable,
    inner: InnerOptConfig,
    state: Dict[str, Any],
    batch: Dict[str, jax.Array],  # leaves (B, ...) — the full global batch
    grad_accum: int = 1,
    pre_split: bool = False,
) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """Standard synchronous data-parallel step: per-step gradient all-reduce."""
    (loss, metrics), grads = _accum_value_and_grad(
        loss_fn, state["params"], batch, grad_accum, pre_split=pre_split
    )
    new_params, new_inner, opt_metrics = inner_update(
        inner, state["params"], grads, state["inner"], state["step"],
        bias_loads=_take_router_loads(metrics),
    )
    metrics = dict(metrics, **opt_metrics)
    metrics["global_model_norm"] = global_norm(new_params)
    return (
        {"params": new_params, "inner": new_inner, "step": state["step"] + 1},
        metrics,
    )


# ---------------------------------------------------------------------------
# Hierarchical (two-level) aggregation — Photon's sub-federation (Alg. 1 L.19–24)
# ---------------------------------------------------------------------------


def hierarchical_mean(deltas, n_groups: int, weights: Optional[jax.Array] = None):
    """Two-phase mean: partial aggregation within node groups (Photon LLM Node islands),
    then across groups. With equal group sizes this equals the flat mean (tested); on
    the mesh it pins the reduce-within-pod → reduce-across-pods schedule.

    With ``weights`` (C,) each island forwards Σ_k w_k Δ_k and Σ_k w_k; the server
    divides once — algebraically identical to the weighted flat mean, so elastic
    participation composes with sub-federation for free.

    Uneven islands: when ``C % n_groups != 0`` the weighted form zero-pads the
    client axis up to the next multiple — a pad slot carries weight 0 and a zero
    delta, so the partial sums are untouched (0·0 = 0 is exact in fp) and the
    final divide uses the REAL weight mass only. The unweighted form has no way
    to mark a pad as absent (every slot counts 1/C) and raises ``ValueError``
    instead — a real error, not a bare ``assert`` that vanishes under
    ``python -O``."""

    def _check_divisible(c: int):
        if c % n_groups != 0:
            raise ValueError(
                f"client axis of size {c} does not divide into {n_groups} equal "
                "groups; pass weights= to use the zero-weight padding path"
            )

    if weights is None:

        def two_level(x):
            _check_divisible(x.shape[0])
            grouped = x.reshape(n_groups, x.shape[0] // n_groups, *x.shape[1:])
            partial = jnp.mean(grouped, axis=1)  # within-island partial aggregation
            return jnp.mean(partial, axis=0)  # server aggregation of island results

        return jax.tree_util.tree_map(two_level, deltas)

    w = weights.astype(jnp.float32)
    w_sum = _safe_weight_sum(w)  # real clients only — pads never enter the divide
    c = int(w.shape[0])
    pad = (-c) % n_groups
    w_padded = jnp.concatenate([w, jnp.zeros((pad,), jnp.float32)]) if pad else w

    def two_level_weighted(x):
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0
            )
        grouped = _weigh_clients(x, w_padded).reshape(
            n_groups, (c + pad) // n_groups, *x.shape[1:]
        )
        partial = jnp.sum(grouped, axis=1)  # within-island weighted partial sums
        return jnp.sum(partial, axis=0) / w_sum.astype(x.dtype)

    return jax.tree_util.tree_map(two_level_weighted, deltas)
