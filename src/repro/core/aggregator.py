"""The unified server-side ``Aggregator`` seam (Photon's Aggregator, §4.1/§5.3).

Before this module, three code paths each owned an ad-hoc slice of server
state: the sync deadline round (``launch/train.py``'s loop), the async buffer
(``AsyncFederationDriver``'s event loop) and the checkpoint code all decided
independently who is admitted, at what weight, and what survives a restart.
That made the paper's resilience claims half-reproducible: a straggler's
partial work could not be credited anywhere, and async training could not be
resumed at all. This module centralizes the three server-side policies behind
one abstraction:

  (a) **admission rule** — who contributes to the next outer update.
      Sync: the ``ParticipationPlan`` mask (availability → dropout → deadline
      cut, or the partial-progress τ_i ≥ 1 rule). Async: the buffer door —
      zero-weight and over-``max_staleness`` arrivals are refused, everything
      else lands in a slot (``core/async_agg.admit_delta``).
  (b) **weight policy** — what an admitted delta counts for.
      Sync: FedAvg data-size weights scaled by the realized fraction τ_i/τ
      (:func:`partial_progress_weights` — the FedProx/FedNova-tradition
      fractional credit). Async: the same fractional weight, then the FedBuff
      staleness discount w/(1+s)^α at admission.
  (c) **canonical checkpoint schema** — what a resumable server IS.
      ``checkpoint()`` returns ``(state_pytree, manifest)``: the pytree holds
      every array lane (params, outer state, rng, buffer lanes, per-client
      error-feedback residuals, in-flight params snapshots) and the JSON-able
      manifest holds the host-side dispatch machine (cursor, per-slot
      completion times / dispatch indices / version tags) whose floats must
      round-trip exactly (JSON reprs do; float32 npz casts would not).

:class:`SyncAggregator` and :class:`AsyncBufferAggregator` implement the
seam; ``federated_round`` / ``federated_round_with_uplink`` stay the pure
jitted kernels underneath, and :class:`AsyncFederationDriver` is now a thin
event-loop shell over the async aggregator — it owns no state of its own.

Async resume (ROADMAP item 2) falls out of (c): the dispatch timeline is pure
in ``(cfg, seed, n)`` (``core/sampler.AsyncTimeline``), so persisting the
dispatch cursor plus each in-flight slot's ``(finish_time, dispatch_index,
version_tag, params_snapshot)`` is sufficient to replay the event loop from a
checkpoint *bitwise* — every future event, admission, flush and rng draw comes
out identical to the uninterrupted run (tested). The cost is explicit: a
checkpoint carries up to K in-flight params snapshots (leaves ``(K, ...)``).
"""
from __future__ import annotations

import heapq
from dataclasses import replace
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.async_agg import (
    AsyncAggConfig,
    admission_record,
    admit_delta,
    flush_buffer,
    init_async_state,
)
from repro.core.compression import Codec
from repro.core.federated import (
    FederatedConfig,
    SparseResidualStore,
    combine_tile_metrics,
    init_federated_state,
    run_clients,
    tile_rng,
    trace_attrs,
)
from repro.core.inner_opt import global_norm
from repro.core.robust import (
    RobustAggConfig,
    RobustState,
    make_robust_apply_fn,
    tile_fold_init,
    tile_fold_size,
)
from repro.core.round_program import (
    RoundPrograms,
    build_round_programs,
    check_round_options,
)
from repro.obs import programs
from repro.obs.metrics import observe_staleness
from repro.obs.tracer import get_tracer
from repro.core.sampler import (
    AsyncTimeline,
    ParticipationConfig,
    ParticipationPlan,
    plan_round,
)

#: Version tag of the canonical checkpoint schema. Bump when the (pytree,
#: manifest) layout changes incompatibly; restore refuses a mismatched tag
#: instead of silently replaying a different state machine.
AGGREGATOR_SCHEMA_VERSION = 1


def _own(tree):
    """Copy a pytree's arrays so the aggregator exclusively owns them.

    Aggregators DONATE their state to the round/flush jits (in-place updates of
    the params-sized lanes instead of double-buffering). Donation invalidates
    the input arrays, so state built from caller-held arrays (the initial
    ``params``, a restored checkpoint pytree) must be copied once at
    construction — otherwise the first donated call would delete arrays the
    caller still references. Every later state is a jit output the aggregator
    owns outright."""
    return jax.tree_util.tree_map(jnp.array, tree)


# ---------------------------------------------------------------------------
# (b) the weight policy, shared by both aggregators
# ---------------------------------------------------------------------------


def partial_progress_weights(weights, local_steps, tau: int) -> np.ndarray:
    """Fractional-credit weight policy for straggler partial progress:
    w_i = n_k,i · τ_i/τ (zero where masked).

    A client that realized τ_i of the τ requested local steps contributed a
    proportionally smaller pseudo-gradient; scaling its FedAvg data-size weight
    by τ_i/τ keeps the aggregate an unbiased convex combination of per-step
    progress (the FedNova normalization, property-tested). With τ_i = τ for
    every client the scale is 1.0 exactly, so the policy is bitwise the plain
    FedAvg weight vector — the partial-progress round then reproduces the
    deadline round bit for bit.
    """
    w = np.asarray(weights, np.float32)
    if local_steps is None:
        return w
    frac = np.asarray(local_steps, np.float32) / np.float32(tau)
    return (w * frac).astype(np.float32)


# ---------------------------------------------------------------------------
# The seam
# ---------------------------------------------------------------------------


class Aggregator:
    """Base of the server-side aggregation seam.

    A concrete aggregator is a serializable state machine owning (a) the
    admission rule, (b) the weight policy and (c) the canonical checkpoint
    schema; the drivers (the sync training loop, the async event loop) only
    move data and never decide policy. ``checkpoint()`` returns
    ``(state_pytree, manifest)`` — the pytree goes through
    ``checkpoint.save_pytree`` (exact array round-trip), the manifest through
    the JSON round-side manifest (exact float64 round-trip).
    """

    kind = "base"
    #: optional :class:`repro.control.FederationController` closing the loop
    #: between observed metrics and this aggregator's knobs; ``None`` (or a
    #: static controller) keeps every code path bitwise the uncontrolled run
    controller = None

    def checkpoint(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        raise NotImplementedError

    # --- closed-loop control (docs/control.md) -----------------------------
    def apply_knobs(self, update) -> None:
        """Apply a :class:`~repro.control.KnobUpdate` to this aggregator's
        configuration. Only ever called between jitted steps (a round/flush
        boundary), so a knob change is a host-side config replace + jit
        rebuild at the new bucketed shape — never a mid-graph mutation."""
        raise NotImplementedError

    def control_step(self, row: Dict[str, Any]):
        """Feed one boundary metrics row to the attached controller and apply
        whatever it returns. A no-op without an active controller — the
        control seam costs the uncontrolled run nothing (bitwise, tested).
        Returns the applied ``KnobUpdate`` or ``None``."""
        c = self.controller
        if c is None or not c.enabled:
            return None
        update = c.observe(row)
        if update is None:
            return None
        self.apply_knobs(update)
        self._trace_knob_update(update)
        return update

    def _trace_knob_update(self, update) -> None:
        """Emit the applied update as an obs instant (with its evidence) and
        refresh the ``control_*`` gauges the metrics endpoint exports."""
        t = self.tracer
        if not t.enabled:
            return
        attrs: Dict[str, Any] = {
            f"knob_{k}": v for k, v in update.knob_dict().items()
        }
        attrs.update({f"evidence_{k}": v for k, v in update.evidence.items()})
        t.point("knob_update", parent=getattr(self, "_round_span", None), **attrs)
        t.count("knob_updates")
        for k, v in self.controller.knobs().items():
            t.gauge(f"control_{k}", float(v))

    @staticmethod
    def validate_manifest(manifest: Dict[str, Any], kind: str) -> None:
        """Refuse to restore from a manifest of the wrong kind or schema
        version — a silent mismatch would replay a different state machine."""
        if not isinstance(manifest, dict) or manifest.get("kind") != kind:
            raise ValueError(
                f"aggregator manifest kind {manifest.get('kind') if isinstance(manifest, dict) else manifest!r} "
                f"does not match this aggregator ({kind!r})"
            )
        if int(manifest.get("schema", -1)) != AGGREGATOR_SCHEMA_VERSION:
            raise ValueError(
                f"aggregator checkpoint schema {manifest.get('schema')!r} != "
                f"supported version {AGGREGATOR_SCHEMA_VERSION}"
            )

    def _manifest_header(self) -> Dict[str, Any]:
        return {"schema": AGGREGATOR_SCHEMA_VERSION, "kind": self.kind}


class SyncAggregator(Aggregator):
    """Synchronous federated aggregation as a state machine.

    Owns the server state pytree and the three policies:

      (a) admission — the ``ParticipationPlan``'s mask: availability → dropout
          → straggler handling. With ``partial_progress`` a slow client is
          admitted with the τ_i = min(τ, ⌊τ·speed·deadline⌋) steps it realized
          (cut only when τ_i < 1) instead of being dropped at the deadline.
      (b) weight policy — FedAvg data-size weights, scaled by τ_i/τ under
          partial progress (:func:`partial_progress_weights`).
      (c) checkpoint schema — the state pytree (params/outer/round/rng, plus a
          sparse ``uplink_residuals`` lane for stateful codecs: the rows of
          every ever-selected client, stacked in sorted-id order, with the id
          list in the manifest) and a ``{"schema", "kind", "round"
          [, "uplink_ids"]}`` manifest.

    ``run_round`` drives the pure jitted kernel (``federated_round``); weights,
    cohort residual rows and the τ-mask all enter as traced arguments, so
    per-round participation and per-client realized step counts never trigger
    a recompile. Error-feedback residuals live OUTSIDE the jitted state in a
    :class:`~repro.core.federated.SparseResidualStore` — the host gathers the
    cohort's rows before the round and scatters the updated rows back after,
    bitwise what the in-graph dense take/set did, with memory
    O(#ever-selected · N) instead of O(P · N).

    ``cohort_tile`` streams the cohort through the client phase ``C_tile``
    clients at a time (two-tier aggregation: Σ wΔ per tile, ONE divide) so the
    (C, N) delta buffer is bounded by C_tile; a single tile (C_tile == C) is
    bitwise the flat round (tested).
    """

    kind = "sync"

    def __init__(
        self,
        loss_fn: Callable,
        fed: FederatedConfig,
        pcfg: ParticipationConfig,
        *,
        codec: Optional[Codec] = None,
        seed: int = 0,
        partial_progress: bool = False,
        params=None,
        rng: Optional[jax.Array] = None,
        state: Optional[Dict[str, Any]] = None,
        shard_clients: Optional[Callable] = None,
        fused_server: bool = False,
        cohort_tile: Optional[int] = None,
        donate: bool = True,
        tracer=None,
        controller=None,
        robust: Optional[RobustAggConfig] = None,
    ):
        self.tracer = get_tracer(tracer)
        self.controller = controller
        self.robust = robust
        self.robust_state = (
            RobustState(robust) if robust is not None and robust.stateful else None
        )
        if partial_progress or pcfg.partial_progress:
            # the aggregator owns the policy: it teaches the participation
            # layer the round's τ so plan_round can derive per-client τ_i
            pcfg = replace(pcfg, partial_progress=True, local_steps=fed.local_steps)
        self.fed = fed
        self.pcfg = pcfg
        self.codec = codec
        self.seed = seed
        self.fused_server = fused_server
        self.cohort_tile = None if cohort_tile is None else int(cohort_tile)
        self.donate = donate
        self._loss_fn = loss_fn
        self._shard_clients = shard_clients
        self._build_round_fn()
        self.residual_store = SparseResidualStore.create(
            codec, params if params is not None else (state or {}).get("params")
        )
        if state is None:
            state = init_federated_state(fed, params, rng)
            # take ownership: the round jit donates the state (see _own)
            self.state = _own(state) if donate else state
        else:
            self.restore(state, None)

    def _build_round_fn(self) -> None:
        """(Re)build the round programs from the CURRENT ``self.fed``/codec
        (:func:`~repro.core.round_program.build_round_programs`, which also
        refuses incompatible options).

        Called at construction and again by :meth:`apply_knobs` when the
        cohort-size knob changes: the round jit closes over ``fed`` (the
        cohort broadcast width), so a new K needs a fresh closure — XLA then
        retraces once at the new bucketed cohort shape."""
        built = build_round_programs(
            self._loss_fn, self.fed, codec=self.codec, robust=self.robust,
            fused_server=self.fused_server, cohort_tile=self.cohort_tile,
            shard_clients=self._shard_clients, donate=self.donate,
        )
        self._progs = RoundPrograms(
            *(None if p is None else programs.register(p) for p in built)
        )

    def apply_knobs(self, update) -> None:
        """Apply a sync :class:`KnobUpdate` between rounds.

        The deadline is a host-side planning scalar (free); a new
        ``clients_per_round`` changes the cohort broadcast width, so both the
        participation config and the federated config move together and the
        round jit is rebuilt (one retrace per bucketed K)."""
        if update.staleness_alpha is not None or update.buffer_size is not None:
            raise ValueError(
                "sync aggregator has no async knobs (staleness_alpha/"
                "buffer_size belong to --aggregation async)"
            )
        if update.deadline is not None:
            self.pcfg = replace(
                self.pcfg,
                straggler=replace(
                    self.pcfg.straggler, deadline=float(update.deadline)
                ),
            )
        if update.clients_per_round is not None:
            k = int(update.clients_per_round)
            if self.fed.keep_inner_state:
                raise ValueError(
                    "cohort control cannot resize the keep_inner_state lanes "
                    "(the persisted inner optimizer state is (K, ...)-shaped) "
                    "— drop --keep-opt or use --control static"
                )
            self.pcfg = replace(self.pcfg, clients_per_round=k)
            self.fed = replace(self.fed, clients_per_round=k)
            self._build_round_fn()

    # --- (a) admission ---------------------------------------------------
    def plan(self, round_idx: int) -> ParticipationPlan:
        """Resolve the round's admission decisions — pure in (cfg, seed, r)."""
        return plan_round(self.pcfg, self.seed, round_idx)

    # --- (b) weight policy -----------------------------------------------
    def round_weights(self, plan: ParticipationPlan) -> np.ndarray:
        """(K,) aggregation weights for the plan's cohort under this
        aggregator's policy (fractional τ_i/τ credit when partial progress)."""
        return partial_progress_weights(
            plan.weights, plan.local_steps, self.fed.local_steps
        )

    def tau_steps(self, plan: ParticipationPlan) -> np.ndarray:
        """The (K,) τ-mask handed to the jitted round: all-full τ without
        partial progress. Masked (zero-weight) slots keep the FULL τ so their
        lanes compute exactly what the non-partial round computed (their
        output is weight-masked anyway) — this is what keeps 'everyone at
        full speed' bitwise identical even when dropout masks part of the
        cohort."""
        if plan.local_steps is None:
            return np.full(len(plan.selected), self.fed.local_steps, np.int32)
        return np.where(
            plan.mask, plan.local_steps, self.fed.local_steps
        ).astype(np.int32)

    # --- the round -------------------------------------------------------
    def run_round(self, batches, plan: ParticipationPlan) -> Dict[str, jax.Array]:
        """One full round under this aggregator's policies; advances the
        owned state and returns the jitted round's metrics."""
        t = self.tracer
        rs = self.robust_state
        if t.enabled or rs is not None:
            rid = int(self.state["round"])
        if t.enabled:
            t.begin("round", span_id=f"r{rid}", round=rid,
                    effective_k=float(plan.effective_k), track=0)
        w = jnp.asarray(self.round_weights(plan))
        if rs is not None and rs.quarantine:
            # quarantined population ids are zero-weighted for this round —
            # the same masked-round mechanism dropout uses, so no recompiles.
            # Skipped entirely when the table is empty (bitwise-neutral).
            q = np.asarray(
                [rs.is_quarantined(int(c), rid) for c in np.asarray(plan.selected)]
            )
            if q.any():
                w = jnp.where(jnp.asarray(q), 0.0, w)
        if self.cohort_tile is not None:
            metrics = self._run_round_tiled(batches, plan, w)
        else:
            metrics = self._run_round_flat(batches, plan, w)
        metrics = dict(metrics)
        screen_mask = metrics.pop("screen_mask", None)
        if screen_mask is not None and rs is not None:
            flagged = np.nonzero(np.asarray(screen_mask) > 0)[0]
            if len(flagged):
                sel = np.asarray(plan.selected)
                cids = [int(sel[i]) for i in flagged]
                rs.note_screen_rejects(len(cids))
                rs.add_quarantine(cids, rid)
                if t.enabled:
                    for cid in cids:
                        t.point("screen_reject", parent=f"r{rid}",
                                client=cid, round=rid)
                        t.count("screen_rejects")
        if t.enabled:
            attrs = trace_attrs(metrics)  # the one device sync tracing pays
            t.end(f"r{rid}", **attrs)
            t.count("rounds")
            t.gauge("round", rid + 1)
            for k, v in attrs.items():
                t.gauge(k, v)
        return metrics

    def _run_round_flat(self, batches, plan: ParticipationPlan, w) -> Dict[str, jax.Array]:
        """One cohort-wide jitted round; host gather/scatter of the cohort's
        error-feedback rows around it (bitwise the old in-graph dense
        take/set — the gathered values are identical)."""
        stateful = self.residual_store is not None
        res = self.residual_store.gather(plan.selected) if stateful else None
        self.state, metrics = self._progs.round(
            self.state, batches, w, res, jnp.asarray(self.tau_steps(plan))
        )
        if stateful:
            # `federated_round` returns the cohort's updated rows in-state;
            # they belong in the population store, not the jitted state
            self.residual_store.scatter(
                plan.selected, self.state.pop("uplink_residuals")
            )
        return metrics

    def _run_round_tiled(self, batches, plan: ParticipationPlan, w) -> Dict[str, jax.Array]:
        """Streamed round: the cohort crosses the client phase ``cohort_tile``
        clients at a time; each tile folds into Σ wΔ partial sums
        (:func:`run_client_tile`), and :func:`apply_aggregate_partial` performs
        the single server-side divide — the ``hierarchical_mean`` algebra, so
        the (C, N) delta buffer never materializes. The last tile pads to the
        tile width with zero-weight slots (zero batch, zero residual row);
        pads add exact zeros everywhere and never touch the residual store.

        One tile (``cohort_tile == C``) is bitwise the flat round: tile 0 runs
        on the round's own rng lane and the partial divide/DP-noise/outer
        sequence mirrors ``apply_aggregate`` op for op."""
        C = self.fed.clients_per_round
        ct = self.cohort_tile
        n_tiles = -(-C // ct)
        stateful = self.residual_store is not None
        progs = self._progs
        w_np = np.asarray(w, np.float32)
        tau_np = self.tau_steps(plan)
        w_full = np.zeros(n_tiles * ct, np.float32)
        w_full[:C] = w_np
        core = {"params": self.state["params"], "round": self.state["round"]}
        base_rng = self.state["rng"]
        delta_sum = None
        delta_norms = []
        tile_outs = []
        fold = None
        if progs.fold is not None:
            k = tile_fold_size(
                self.robust.rule, self.robust.trim_fraction, n_tiles * ct
            )
            fold = tile_fold_init(self.state["params"], k)
        for t_idx in range(n_tiles):
            lo, hi = t_idx * ct, min((t_idx + 1) * ct, C)
            n_real = hi - lo

            def _pad(x, axis=0):
                if n_real == ct:
                    return x
                shape = list(x.shape)
                shape[axis] = ct - n_real
                return jnp.concatenate(
                    [x, jnp.zeros(shape, x.dtype)], axis=axis
                )

            b_t = jax.tree_util.tree_map(
                lambda x: _pad(x[:, lo:hi], axis=1), batches
            )
            w_t = jnp.asarray(w_full[t_idx * ct:(t_idx + 1) * ct])
            res_t = None
            if stateful:
                res_t = jax.tree_util.tree_map(
                    _pad, self.residual_store.gather(plan.selected[lo:hi])
                )
            # pad slots take the FULL τ (the tau_steps() discipline: their
            # output is weight-masked anyway, and full-τ lanes keep the
            # non-partial bitwise identity)
            tau_t = jnp.asarray(
                np.concatenate(
                    [tau_np[lo:hi],
                     np.full(ct - n_real, self.fed.local_steps, np.int32)]
                )
            )
            s_t = dict(core, rng=tile_rng(base_rng, t_idx))
            out = progs.tile(s_t, b_t, w_t, res_t, tau_t)
            if stateful:
                rows = out.pop("residuals")
                self.residual_store.scatter(
                    plan.selected[lo:hi],
                    jax.tree_util.tree_map(lambda x: x[:n_real], rows),
                )
            ds = out.pop("delta_sum")
            dn_t = out.pop("delta_norms")
            if progs.fold is not None:
                # robust tiled (trimmed/median): fold per-tile order-statistic
                # moments instead of the weighted partial sum
                fold = progs.fold(fold, out.pop("deltas"), dn_t, w_t)
            elif progs.tile_clip is not None:
                # robust tiled normclip: clip each client within its tile at
                # the absolute τ, then the standard Σ wΔ accumulation
                ds = progs.tile_clip(out.pop("deltas"), dn_t, w_t)
                delta_sum = ds if delta_sum is None else jax.tree_util.tree_map(
                    jnp.add, delta_sum, ds
                )
            else:
                delta_sum = ds if delta_sum is None else jax.tree_util.tree_map(
                    jnp.add, delta_sum, ds
                )
            delta_norms.append(dn_t)
            tile_outs.append(out)
        if progs.fold is not None:
            new_state, agg_metrics = progs.fold_server(
                fold, self.state, jnp.concatenate(delta_norms),
                jnp.asarray(w_full),
            )
        else:
            new_state, agg_metrics = progs.server(
                self.state, delta_sum, jnp.asarray(w_full),
                jnp.concatenate(delta_norms),
            )
        self.state = new_state
        return dict(combine_tile_metrics(tile_outs), **agg_metrics)

    # --- (c) checkpoint schema -------------------------------------------
    def checkpoint(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        # a COPY, not the live state: the round jit donates self.state, so a
        # caller that serializes the checkpoint after the next round would
        # otherwise hold deleted arrays
        manifest = dict(self._manifest_header(), round=int(self.state["round"]))
        tree = _own(self.state)
        if self.residual_store is not None:
            # sparse lane: every ever-selected client's row, stacked in
            # sorted-id order; the id list rides the manifest so the load
            # template can be sized without touching the npz
            manifest["uplink_ids"] = self.residual_store.ids()
            tree["uplink_residuals"] = _own(self.residual_store.stacked())
        if self.controller is not None and self.controller.enabled:
            # controller state rides the manifest (JSON floats round-trip
            # exactly); absent entirely for static/None, keeping the default
            # checkpoint byte-identical to the uncontrolled schema
            manifest["control"] = self.controller.state_dict()
        if self.robust_state is not None:
            # defense state (quarantine table, guard window, counters) rides
            # the manifest like the controller's — absent when the defense is
            # off, keeping the undefended checkpoint byte-identical to PR-9's
            manifest["robust"] = self.robust_state.state_dict()
        return tree, manifest

    def adopt_model(self, tree: Dict[str, Any]) -> None:
        """Adopt a rolled-back ``{params, outer}`` subset (divergence rollback):
        the model and outer-optimizer lanes rewind to the blessed checkpoint
        while ``round`` and ``rng`` keep advancing monotonically — a resumed
        run replays the same rollback at the same round, bitwise, and the
        round counter can never livelock."""
        self.state = dict(
            self.state, params=_own(tree["params"]), outer=_own(tree["outer"])
        )

    def restore(self, state: Dict[str, Any], manifest: Optional[Dict[str, Any]] = None) -> None:
        """Adopt a restored checkpoint pytree (+ its aggregator manifest).

        The ``uplink_residuals`` lane is routed into the sparse store: with
        ``manifest['uplink_ids']`` it is the sparse stacked layout; without
        (a legacy dense checkpoint) a ``(population, ...)`` lane converts via
        ``from_dense`` — all-zero (never-selected) rows stay unmaterialized,
        which is how a PR-8 dense checkpoint resumes bitwise with flat memory.
        """
        state = dict(state)
        res = state.pop("uplink_residuals", None)
        stateful = self.codec is not None and self.codec.stateful
        if res is not None and not stateful:
            raise ValueError(
                "restored state carries per-client error-feedback residuals "
                "but this aggregator's codec is not stateful — pass the codec "
                "the checkpoint was written with"
            )
        if res is not None:
            params_like = state["params"]
            ids = manifest.get("uplink_ids") if isinstance(manifest, dict) else None
            leading = jax.tree_util.tree_leaves(res)[0].shape[0]
            if ids is not None:
                self.residual_store = SparseResidualStore.from_stacked(
                    params_like, ids, res
                )
            elif leading == self.pcfg.population:
                self.residual_store = SparseResidualStore.from_dense(
                    params_like, res
                )
            else:
                raise ValueError(
                    f"uplink_residuals lane has leading dim {leading}, which "
                    f"matches neither the manifest's uplink_ids (absent) nor "
                    f"the dense (population={self.pcfg.population}, ...) layout"
                )
        if (
            self.robust_state is not None
            and isinstance(manifest, dict)
            and "robust" in manifest
        ):
            # a legacy (PR-9) manifest simply has no 'robust' key: the defense
            # starts from a clean slate, and the restored lanes are untouched
            self.robust_state.load_state_dict(manifest["robust"])
        self.state = _own(state) if self.donate else state

    @classmethod
    def checkpoint_template(
        cls,
        fed: FederatedConfig,
        pcfg: ParticipationConfig,
        params_like,
        codec: Optional[Codec] = None,
        uplink_ids=None,
    ) -> Dict[str, Any]:
        """Abstract state pytree matching ``checkpoint()[0]`` — the ``like``
        argument for ``checkpoint.load_pytree``.

        ``uplink_ids`` (the manifest's recorded id set) sizes the sparse
        residual lane; ``None`` falls back to the legacy dense ``(P, ...)``
        layout. Either way the lane is ``jax.ShapeDtypeStruct`` leaves — a
        template never allocates the store it describes."""
        state = init_federated_state(fed, params_like, jax.random.PRNGKey(0))
        if codec is not None and codec.stateful:
            n = pcfg.population if uplink_ids is None else len(uplink_ids)
            state["uplink_residuals"] = jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(
                    (n,) + tuple(p.shape), jnp.float32
                ),
                params_like,
            )
        return state


class AsyncBufferAggregator(Aggregator):
    """Asynchronous (FedBuff-style) buffered aggregation as a state machine.

    Everything the old event-loop driver used to own now lives here, split by
    the seam's three concerns:

      (a) admission — ``admit()``: the jitted buffer door (staleness tagged
          against the server version, zero-weight / over-``max_staleness``
          arrivals refused without consuming a slot) plus the dispatch-side
          rule that a population client holds at most one slot at a time.
      (b) weight policy — ``event_weight()`` credits a completion its
          fractional τ_i/τ under partial progress; the staleness discount
          w/(1+s)^α is applied in-graph at admission.
      (c) checkpoint schema — ``checkpoint()``: the server pytree (buffer
          lanes included), the per-client error-feedback residual store, the
          K in-flight params snapshots (stacked ``(K, ...)``) and the
          host-side dispatch manifest (cursor, per-slot finish/index/version).
          Because the timeline is pure in ``(cfg, seed, n)``, restoring these
          replays the run bitwise from the checkpoint.

    The event loop (``step``/``run_updates``) lives in the thin
    :class:`AsyncFederationDriver` subclass; this class never touches data or
    loss functions.
    """

    kind = "async"

    def __init__(
        self,
        fed: FederatedConfig,
        acfg: AsyncAggConfig,
        pcfg: ParticipationConfig,
        *,
        seed: int = 0,
        params=None,
        rng: Optional[jax.Array] = None,
        state: Optional[Dict[str, Any]] = None,
        codec: Optional[Codec] = None,
        dispatch: Optional[Dict[str, Any]] = None,
        fused_server: bool = False,
        tracer=None,
        controller=None,
        robust: Optional[RobustAggConfig] = None,
    ):
        self.fed = fed
        self.acfg = acfg
        self.pcfg = pcfg
        self.codec = codec
        self.seed = seed
        self.fused_server = fused_server
        self.tracer = get_tracer(tracer)
        self.controller = controller
        check_round_options(fed, robust=robust, fused_server=fused_server)
        self.robust = robust
        self.robust_state = (
            RobustState(robust) if robust is not None and robust.stateful else None
        )
        #: optional host hook corrupting a delta before admission — the
        #: Byzantine-client simulator for benches (``make_byzantine_fn``);
        #: None on every honest run
        self.corrupt_fn = None
        if pcfg.partial_progress and pcfg.local_steps != fed.local_steps:
            raise ValueError(
                "pcfg.local_steps must equal fed.local_steps under partial "
                f"progress (got {pcfg.local_steps} vs {fed.local_steps})"
            )
        stateful = codec is not None and codec.stateful
        self._stateful = stateful
        apply_fn = None
        if fused_server:
            from repro.kernels.fedcore import fused_apply_aggregate

            apply_fn = fused_apply_aggregate
        elif robust is not None and robust.rule != "none":
            # the robust rule guards each FLUSH over the buffer lanes; the
            # screen is enforced earlier, at the admission door, so the flush
            # phase runs with screening off (the buffer only holds admitted
            # deltas — but may still hold pre-warmup poison, which sanitize
            # and the NaN-aware metrics inside the robust phase absorb)
            apply_fn = make_robust_apply_fn(fed, replace(robust, screen=False))
        self._apply_fn = apply_fn
        self._build_agg_fns()
        if state is None:
            state = init_async_state(fed, acfg, params, rng)
        else:
            state = dict(state)  # may carry residuals/in-flight lanes
        inflight = state.pop("inflight_params", None)
        uplink_rng = state.pop("uplink_rng", None)
        restored_res = state.pop("uplink_residuals", None)
        # take ownership of everything the admit/flush jits donate (every lane
        # but params — params is aliased by in-flight snapshots, never donated)
        self.state = dict(
            state, **_own({k: v for k, v in state.items() if k != "params"})
        )
        if restored_res is not None and not stateful:
            raise ValueError(
                "restored state carries per-client error-feedback residuals but "
                "the driver's codec is not stateful — pass the codec the "
                "checkpoint was written with, or strip 'uplink_residuals' to "
                "deliberately discard the clients' accumulated feedback"
            )
        # the residual store is SPARSE: an empty id→row map at a fresh start
        # (flat memory in P — a row materializes the first time its client is
        # dispatched), rebuilt from the checkpoint's recorded id set on resume
        self.residuals: Optional[SparseResidualStore] = None
        if stateful:
            params_like = self.state["params"]
            if restored_res is None:
                self.residuals = SparseResidualStore(params_like)
            else:
                ids = (
                    dispatch.get("uplink_ids")
                    if isinstance(dispatch, dict) else None
                )
                leading = jax.tree_util.tree_leaves(restored_res)[0].shape[0]
                if ids is not None:
                    self.residuals = SparseResidualStore.from_stacked(
                        params_like, ids, restored_res
                    )
                elif leading == pcfg.population:
                    # legacy PR-3 dense (P, ...) layout: all-zero rows stay
                    # unmaterialized, so the resume is bitwise AND flat-memory
                    self.residuals = SparseResidualStore.from_dense(
                        params_like, restored_res
                    )
                else:
                    raise ValueError(
                        f"uplink_residuals lane has leading dim {leading}, "
                        f"which matches neither the dispatch manifest's "
                        f"uplink_ids (absent) nor the dense "
                        f"(population={pcfg.population}, ...) layout"
                    )
            self._res_norm_fn = jax.jit(global_norm)
        self._bytes_per_upload = (
            float(codec.nbytes(self.state["params"])) if codec is not None
            else 4.0 * sum(
                x.size for x in jax.tree_util.tree_leaves(self.state["params"])
            )
        )
        if codec is not None:
            # derived once per RUN from the then-current rng, never consumed in
            # graph — restored verbatim from the checkpoint so a resumed run's
            # stochastic-rounding draws match the uninterrupted run's
            self._uplink_rng = (
                uplink_rng if uplink_rng is not None
                else jax.random.fold_in(self.state["rng"], 0x55504C4B)
            )
        else:
            self._uplink_rng = None
        self.uplink_bytes_total = 0.0  # bytes actually uploaded (incl. rejected)
        self.timeline = AsyncTimeline(pcfg, seed)
        self.sim_time = 0.0
        self.work_completed = 0.0  # simulated client-time that reached the buffer
        self.work_wasted = 0.0  # dropout / rejected-staleness client-time
        self.n_dispatched = 0  # the dispatch CURSOR — serialized for resume
        self._heap: List[Tuple[float, int, Any, Any, int]] = []
        self._busy: set = set()  # population client ids currently holding a slot
        self._losses: List[float] = []  # client train losses since last flush
        self._staleness: List[float] = []  # admitted staleness since last flush
        self._res_norms: List[float] = []  # EF residual norms since last flush
        # the server-side round span: dispatch spans of version v parent into
        # "u{v}"; _flush_row rotates it when a flush bumps the version
        self._round_span = f"u{int(self.state['round'])}" if self.tracer.enabled else None
        if self.tracer.enabled:
            self.tracer.begin("round", span_id=self._round_span,
                              round=int(self.state["round"]), track=0)
        if dispatch is not None:
            if self.robust_state is not None and "robust" in dispatch:
                # a legacy (PR-9) manifest has no 'robust' key: the defense
                # starts from a clean slate over the restored lanes
                self.robust_state.load_state_dict(dispatch["robust"])
            self._restore_dispatch(dispatch, inflight)
        else:
            for _ in range(pcfg.clients_per_round):
                self._dispatch()

    def _build_agg_fns(self) -> None:
        """(Re)build the admission/flush jits from the CURRENT ``self.acfg``.

        Called at construction and again by :meth:`apply_knobs`: both jits
        close over ``acfg`` (α enters the staleness discount in-graph, M fixes
        the buffer-lane shapes), so a knob change needs fresh closures — the
        governor's bucketed grids (α on 1/16 steps, M on powers of two) bound
        the retraces to a handful per run.

        (a) admission + flush as standalone jits: the flush then compiles in
        the same fusion context as the sync server phase, keeping the
        buffer_size==K / α==0 path bitwise-equal to federated_round.
        DONATION: the buffer lanes, outer state and rng are exclusively owned
        and replaced on every call, so they donate — but ``params`` must NOT:
        the in-flight dispatch slots snapshot the params pytree BY REFERENCE,
        and donating it would invalidate those snapshots. The state splits
        into (params, rest) at each call so only ``rest`` donates."""
        fed, acfg, codec = self.fed, self.acfg, self.codec
        apply_fn = self._apply_fn
        self._screen = self.robust is not None and self.robust.screen
        if self._screen:
            # the screened door: non-finite rejection always, plus the
            # adaptive norm bound (a traced scalar — the host recomputes it
            # from the admitted-norm history, so no recompiles as it tightens)
            self._admit_fn = jax.jit(
                lambda p, rest, d, r, w, nb: admit_delta(
                    fed, acfg, dict(rest, params=p), d, r, w, auto_flush=False,
                    codec=codec, screen=True, norm_bound=nb,
                ),
                donate_argnums=(1,),
            )
        else:
            self._admit_fn = jax.jit(
                lambda p, rest, d, r, w: admit_delta(
                    fed, acfg, dict(rest, params=p), d, r, w, auto_flush=False,
                    codec=codec,
                ),
                donate_argnums=(1,),
            )
        self._flush_fn = jax.jit(
            lambda p, rest: flush_buffer(
                fed, acfg, dict(rest, params=p), apply_fn=apply_fn
            ),
            donate_argnums=(1,),
        )

    def apply_knobs(self, update) -> None:
        """Apply an async :class:`KnobUpdate` at a flush boundary.

        ``staleness_alpha`` changes the in-graph discount (jit rebuild);
        ``buffer_size`` additionally reshapes the buffer lanes, which is only
        sound when the buffer is EMPTY — every flush drains it, and
        ``control_step`` runs inside ``_flush_row``, so the invariant holds by
        construction (and is asserted here against misuse). The dispatch
        timeline is pure in ``(pcfg, seed)`` and neither knob touches it, so a
        governed run stays exactly resumable."""
        if update.clients_per_round is not None or update.deadline is not None:
            raise ValueError(
                "async control drives staleness_alpha/buffer_size only: the "
                "dispatch timeline is pure in (participation config, seed) "
                "and cannot change mid-run (cohort/deadline are sync knobs)"
            )
        acfg = self.acfg
        if update.staleness_alpha is not None:
            acfg = replace(acfg, staleness_alpha=float(update.staleness_alpha))
        if (
            update.buffer_size is not None
            and int(update.buffer_size) != acfg.buffer_size
        ):
            if int(self.state["buf_count"]) != 0:
                raise RuntimeError(
                    f"buffer resize with {int(self.state['buf_count'])} "
                    f"buffered deltas — knob updates must land at a flush "
                    f"boundary (the buffer drains at every flush)"
                )
            m = int(update.buffer_size)
            acfg = replace(acfg, buffer_size=m)
            params = self.state["params"]
            self.state = dict(
                self.state,
                buffer=jax.tree_util.tree_map(
                    lambda p: jnp.zeros((m,) + p.shape, jnp.float32), params
                ),
                buf_weights=jnp.zeros((m,), jnp.float32),
                buf_staleness=jnp.zeros((m,), jnp.float32),
            )
        if acfg != self.acfg:
            self.acfg = acfg
            self._build_agg_fns()
        self._notify_knobs(update)

    def _notify_knobs(self, update) -> None:
        """Hook fired after a knob update is applied server-side; the
        cross-process runtime overrides this to expose the live knob values
        through the backend's metrics extras."""

    # --- dispatch machinery (serialized state) ----------------------------
    def _dispatch(self) -> None:
        # a client can only run in one slot at a time: skip timeline entries for
        # clients already in flight (zero simulated cost — the scheduler simply
        # picks the next free client from the sampler stream). Termination: at
        # refill time at most K−1 clients are busy and every wave holds K
        # distinct clients, so a free client appears within two waves.
        for _ in range(64 * self.timeline.cfg.clients_per_round):
            ev = self.timeline.dispatch(self.n_dispatched)
            self.n_dispatched += 1
            if ev.client not in self._busy:
                break
        else:  # pragma: no cover — unreachable by the argument above
            raise RuntimeError("async dispatch starved: every client busy")
        # every dispatch holds its client for the event duration — including an
        # unavailable client's connect probe, during which no other slot should
        # be contacting it either
        self._busy.add(ev.client)
        # snapshot by reference: jax arrays are immutable, so holding the params
        # of up to K in-flight versions costs no copies
        snapshot = self.state["params"] if ev.completes else None
        version = int(self.state["round"])
        heapq.heappush(
            self._heap, (self.sim_time + ev.duration, ev.index, ev, snapshot, version)
        )
        self._on_dispatch(ev, snapshot, version)
        self._trace_dispatch(ev, version)

    # --- telemetry (read-only: never touches the aggregation math) ---------
    def _trace_dispatch(self, ev, version: int) -> None:
        """Open the dispatch span ``d{index}`` under the round span of the
        version its params snapshot was taken at. One display track per
        population client so concurrent slots render as parallel bars."""
        if not self.tracer.enabled:
            return
        self.tracer.begin(
            "dispatch", span_id=f"d{ev.index}", parent=f"u{version}",
            index=ev.index, client=int(ev.client), version=version,
            completes=bool(ev.completes), track=1 + int(ev.client),
        )
        self.tracer.count("dispatches")

    def _trace_complete(self, ev, outcome: str, staleness=None) -> None:
        """Close a dispatch span with its terminal outcome."""
        if not self.tracer.enabled:
            return
        attrs: Dict[str, Any] = {"outcome": outcome}
        if staleness is not None:
            attrs["staleness"] = float(staleness)
        self.tracer.end(f"d{ev.index}", **attrs)
        self.tracer.count(f"outcome_{outcome}")

    def _trace_admit(self, ev, metrics) -> Dict[str, Any]:
        """Record one admission decision (instant + counters + histogram) and
        return the host-side record; ``{}`` when tracing is off."""
        if not self.tracer.enabled:
            return {}
        rec = admission_record(metrics)
        self.tracer.point("admit", parent=f"d{ev.index}", index=ev.index,
                          client=int(ev.client), **rec)
        if rec["accepted"]:
            self.tracer.count("admits")
            observe_staleness(self.tracer, rec["staleness"])
        else:
            self.tracer.count("admit_rejects")
        self.tracer.gauge(
            "buffer_occupancy", rec.get("buf_count", 0.0) / self.acfg.buffer_size
        )
        return rec

    def _on_dispatch(self, ev, snapshot, version: int) -> None:
        """Hook fired once per dispatched slot — including replayed slots on
        restore. The cross-process runtime overrides this to hand the slot's
        fully self-describing work assignment (params snapshot, version tag,
        residual row, per-dispatch rng) to a client backend; the in-process
        simulator needs nothing."""

    def _pop_completion(self):
        finish, _, ev, snapshot, version = heapq.heappop(self._heap)
        self.sim_time = max(self.sim_time, finish)
        self._busy.discard(ev.client)
        return ev, snapshot, version

    # --- per-client error-feedback rows (sparse store accessors) ----------
    @staticmethod
    def _res_gather(store: SparseResidualStore, cid):
        """One client's EF row as a (1, ...) tree — what the old dense
        ``r[cid][None]`` jit returned; a never-dispatched client reads zeros
        (the dense store's initial value, bitwise)."""
        return jax.tree_util.tree_map(lambda r: r[None], store.row(int(cid)))

    @staticmethod
    def _res_scatter(store: SparseResidualStore, cid, new):
        """Write a client's updated (1, ...) row back, materializing it on
        first touch; returns the store (the old donating-jit calling
        convention, so the drivers' ``self.residuals = _res_scatter(...)``
        call sites read identically)."""
        store.scatter([int(cid)], new)
        return store

    # --- (a)/(b): admission + weight policy -------------------------------
    def event_weight(self, ev) -> float:
        """Pre-discount credit of a completion: the plan's FedAvg weight,
        scaled by the realized fraction τ_i/τ under partial progress (the
        staleness discount is applied in-graph at admission)."""
        if self.pcfg.partial_progress and ev.local_steps:
            return float(ev.weight) * ev.local_steps / self.pcfg.local_steps
        return float(ev.weight)

    def _split_state(self):
        """(params, rest): params is aliased by in-flight snapshots and never
        donated; everything else is exclusively owned and donates."""
        return (
            self.state["params"],
            {k: v for k, v in self.state.items() if k != "params"},
        )

    def admit(self, delta, version: int, weight: float) -> Dict[str, jax.Array]:
        """Admit one (decoded-at-the-door) upload tagged with the model version
        it was computed against; rejected arrivals consume nothing."""
        params, rest = self._split_state()
        args = (
            params, rest, delta,
            jnp.asarray(version, jnp.int32), jnp.asarray(weight, jnp.float32),
        )
        if self._screen:
            bound = (
                self.robust_state.norm_bound()
                if self.robust_state is not None else float("inf")
            )
            args = args + (jnp.asarray(bound, jnp.float32),)
        self.state, m = self._admit_fn(*args)
        return m

    def _note_admission(self, ev, m) -> None:
        """Host-side defense bookkeeping for one admission outcome. Every
        finite norm seen at the door — admitted or screened — feeds the
        adaptive bound: median/MAD is contamination-robust as long as
        attackers stay a minority of recent traffic, and learning only from
        accepted norms would freeze the bound the moment it started rejecting
        honest drift. Screen rejections are traced as ``screen_reject``
        instants; only *non-finite* payloads quarantine the sender — a single
        norm-bound miss is weak temporal evidence, and quarantine release is
        round-indexed, so quarantining the honest majority would halt round
        progress and never expire."""
        rs = self.robust_state
        if rs is None or "delta_norm" not in m:
            return
        norm = float(m["delta_norm"])
        finite = norm == norm and abs(norm) != float("inf")
        if finite:
            rs.observe_norm(norm)
        if float(m["accepted"]) <= 0 and float(m.get("screened", 0.0)) > 0:
            rs.note_screen_rejects()
            if not finite:
                rs.add_quarantine([int(ev.client)], int(self.state["round"]))
            if self.tracer.enabled:
                self.tracer.point(
                    "screen_reject", parent=f"d{ev.index}", index=ev.index,
                    client=int(ev.client), norm=norm if finite else -1.0,
                )
                self.tracer.count("screen_rejects")

    def flush(self) -> Dict[str, jax.Array]:
        """One outer update from the buffered deltas; bumps the version."""
        params, rest = self._split_state()
        self.state, m = self._flush_fn(params, rest)
        return m

    def should_flush(self) -> bool:
        return int(self.state["buf_count"]) >= self.acfg.buffer_size

    def _flush_row(self, flush_metrics, deadline: bool = False) -> Dict[str, float]:
        row = {k: float(v) for k, v in flush_metrics.items()}
        row["sim_time"] = self.sim_time
        row["train_loss_mean"] = (
            float(jnp.mean(jnp.asarray(self._losses))) if self._losses else 0.0
        )
        row["admitted_staleness"] = list(self._staleness)
        row["uplink_bytes_total"] = self.uplink_bytes_total
        if self.residuals is not None:
            row["uplink_residual_norm"] = (
                sum(self._res_norms) / len(self._res_norms) if self._res_norms else 0.0
            )
        self._losses, self._staleness, self._res_norms = [], [], []
        self._trace_flush(row, deadline)
        # the flush boundary is the async control point: the buffer just
        # drained, so a knob update (α rebuild, buffer resize) is always safe
        # here. Applied knobs are echoed into the row for the CSV/bench trail.
        update = self.control_step(row)
        if update is not None:
            for k, v in update.knob_dict().items():
                row[f"knob_{k}"] = v
        return row

    def _trace_flush(self, row: Dict[str, Any], deadline: bool) -> None:
        """Record a flush instant and rotate the round span when the flush
        actually bumped the model version (an empty deadline flush does not)."""
        t = self.tracer
        if not t.enabled:
            return
        new_round = int(self.state["round"])
        attrs = {
            "round": new_round,
            "deadline": deadline,
            "sim_time": row["sim_time"],
            "train_loss": row["train_loss_mean"],
        }
        for k in ("buffer_fill", "staleness_mean", "staleness_max"):
            if k in row:
                attrs[k] = row[k]
        t.point("flush", parent=self._round_span, **attrs)
        t.count("deadline_flushes" if deadline else "flushes")
        if f"u{new_round}" != self._round_span:
            t.end(self._round_span, **{k: v for k, v in attrs.items()
                                       if k != "round"})
            self._round_span = f"u{new_round}"
            t.begin("round", span_id=self._round_span, round=new_round, track=0)
        t.gauge("round", new_round)
        t.gauge("sim_time", row["sim_time"])
        t.gauge("train_loss", row["train_loss_mean"])
        t.gauge("uplink_bytes_total", row["uplink_bytes_total"])
        if "buffer_fill" in row:
            t.gauge("last_flush_fill", row["buffer_fill"])

    def finalize_trace(self) -> None:
        """End-of-run span hygiene: K slots are by construction still in
        flight when a run stops, and the current round span is open — close
        them with the ``inflight_at_exit`` outcome so the report CLI's
        "all spans closed" check distinguishes a clean exit from a leak."""
        if not self.tracer.enabled:
            return
        for _, _, ev, _, _ in sorted(self._heap):
            self._trace_complete(ev, "inflight_at_exit")
        self.tracer.end(self._round_span)

    def force_flush(self) -> Optional[Dict[str, float]]:
        """Apply a final outer update from a partially filled buffer (end of
        run). Returns a row shaped exactly like the drivers' flush rows."""
        if int(self.state["buf_count"]) == 0:
            return None
        return self._flush_row(self.flush())

    # --- (c) canonical checkpoint schema ----------------------------------
    def checkpoint_state(self) -> Dict[str, Any]:
        """Server state + the per-client error-feedback store as ONE pytree
        with a fixed structure (the legacy PR-3 schema, kept for buffer-only
        round-trips): the residual lane is the DENSE ``(P, ...)`` expansion of
        the sparse store — use :meth:`checkpoint` for the population-scale
        sparse lane. Returns a COPY: the admit/flush jits donate the non-params
        lanes, so a checkpoint held past the next event must not alias them."""
        if self.residuals is None:
            return _own(self.state)
        return dict(
            _own(self.state),
            uplink_residuals=self.residuals.to_dense(self.pcfg.population),
        )

    def checkpoint(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """The canonical resumable checkpoint: ``(state_pytree, manifest)``.

        The pytree holds the server state, the SPARSE error-feedback lane (the
        ever-dispatched clients' rows stacked in sorted-id order — the id list
        rides the manifest as ``uplink_ids``, never a dense ``(P, ...)``
        expansion), ``inflight_params`` (the K in-flight slots' params
        snapshots, stacked ``(K, ...)`` in manifest slot order) and, with a
        codec, the run's ``uplink_rng`` lane. The manifest carries the host
        floats that must round-trip exactly (finish times, sim clock) plus the
        dispatch cursor and per-slot ``(index, version)`` tags — everything
        else about an in-flight event is recomputed from the pure timeline at
        restore.
        """
        entries = sorted(self._heap)  # (finish, index, ...): deterministic order
        tree = _own(self.state)
        if self.residuals is not None:
            tree["uplink_residuals"] = _own(self.residuals.stacked())
        snaps = [
            snap if snap is not None else self.state["params"]  # non-completing
            for _, _, _, snap, _ in entries                     # slot: unused filler
        ]
        tree["inflight_params"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *snaps
        )
        if self._uplink_rng is not None:
            tree["uplink_rng"] = self._uplink_rng
        manifest = dict(
            self._manifest_header(),
            cursor=int(self.n_dispatched),
            sim_time=float(self.sim_time),
            work_completed=float(self.work_completed),
            work_wasted=float(self.work_wasted),
            uplink_bytes_total=float(self.uplink_bytes_total),
            slots=[
                {"finish": float(finish), "index": int(index), "version": int(ver)}
                for finish, index, _, _, ver in entries
            ],
        )
        if self.residuals is not None:
            manifest["uplink_ids"] = self.residuals.ids()
        if self.controller is not None and self.controller.enabled:
            # controller state rides the manifest (JSON floats round-trip
            # exactly); absent entirely for static/None, keeping the default
            # checkpoint byte-identical to the uncontrolled schema
            manifest["control"] = self.controller.state_dict()
        if self.robust_state is not None:
            # defense state rides the manifest like the controller's — absent
            # when the defense is off (undefended schema byte-identical)
            manifest["robust"] = self.robust_state.state_dict()
        return tree, manifest

    def adopt_model(self, tree: Dict[str, Any]) -> None:
        """Adopt a rolled-back ``{params, outer}`` subset (divergence
        rollback). Beyond the sync semantics (model/outer rewind; round, rng
        and the dispatch machinery keep advancing), the async rollback also
        DRAINS the buffer: buffered deltas were computed against — and
        admitted into — the poisoned trajectory, and flushing them onto the
        restored model would re-apply the damage. In-flight snapshots keep
        their old params references; their uploads age normally against the
        (monotone) version counter."""
        m = self.acfg.buffer_size
        params = _own(tree["params"])
        self.state = dict(
            self.state,
            params=params,
            outer=_own(tree["outer"]),
            buffer=jax.tree_util.tree_map(
                lambda p: jnp.zeros((m,) + p.shape, jnp.float32), params
            ),
            buf_weights=jnp.zeros((m,), jnp.float32),
            buf_staleness=jnp.zeros((m,), jnp.float32),
            buf_count=jnp.zeros((), jnp.int32),
        )

    def _restore_dispatch(self, manifest: Dict[str, Any], inflight) -> None:
        self.validate_manifest(manifest, self.kind)
        slots = manifest["slots"]
        K = self.pcfg.clients_per_round
        if len(slots) != K:
            raise ValueError(
                f"dispatch manifest has {len(slots)} in-flight slots but this "
                f"configuration runs {K} — resume with the checkpoint's "
                f"clients_per_round"
            )
        if inflight is None:
            raise ValueError(
                "dispatch manifest given but the state pytree carries no "
                "'inflight_params' — load through the aggregator's "
                "checkpoint_template"
            )
        self.n_dispatched = int(manifest["cursor"])
        self.sim_time = float(manifest["sim_time"])
        self.work_completed = float(manifest["work_completed"])
        self.work_wasted = float(manifest["work_wasted"])
        self.uplink_bytes_total = float(manifest["uplink_bytes_total"])
        for pos, slot in enumerate(slots):
            # the event itself is pure in (cfg, seed, index): replay it
            ev = self.timeline.dispatch(int(slot["index"]))
            snapshot = (
                jax.tree_util.tree_map(lambda x, p=pos: x[p], inflight)
                if ev.completes else None
            )
            heapq.heappush(
                self._heap,
                (float(slot["finish"]), ev.index, ev, snapshot, int(slot["version"])),
            )
            self._busy.add(ev.client)
            self._on_dispatch(ev, snapshot, int(slot["version"]))
            self._trace_dispatch(ev, int(slot["version"]))

    @classmethod
    def checkpoint_template(
        cls,
        fed: FederatedConfig,
        acfg: AsyncAggConfig,
        pcfg: ParticipationConfig,
        params_like,
        codec: Optional[Codec] = None,
        uplink_ids=None,
    ) -> Dict[str, Any]:
        """Abstract state pytree matching ``checkpoint()[0]`` — the ``like``
        argument for ``checkpoint.load_pytree`` when resuming.

        ``uplink_ids`` (the dispatch manifest's recorded id set) sizes the
        sparse residual lane; ``None`` falls back to the legacy dense
        ``(P, ...)`` layout. Both it and the in-flight lane are built as
        ``jax.ShapeDtypeStruct`` leaves — a template never allocates the
        stores it describes (at P=100k the dense fallback would otherwise
        materialize P params-sized rows just to name their shapes)."""
        state = init_async_state(fed, acfg, params_like, jax.random.PRNGKey(0))
        if codec is not None and codec.stateful:
            n = pcfg.population if uplink_ids is None else len(uplink_ids)
            state["uplink_residuals"] = jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(
                    (n,) + tuple(p.shape), jnp.float32
                ),
                params_like,
            )
        K = pcfg.clients_per_round
        state["inflight_params"] = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct((K,) + tuple(p.shape), p.dtype),
            params_like,
        )
        if codec is not None:
            state["uplink_rng"] = jax.random.PRNGKey(0)
        return state


class AsyncFederationDriver(AsyncBufferAggregator):
    """Event-driven simulator of the asynchronous federation (Photon §5.3) —
    now a THIN driver over :class:`AsyncBufferAggregator`.

    The driver owns only the data/compute plane: the jitted client phase
    (``run_clients`` at C=1 against each dispatch's params snapshot) and the
    per-update metric rows. Every policy decision and every byte of resumable
    state — buffer lanes, residual store, dispatch cursor, in-flight slots —
    belongs to the aggregator base, so ``checkpoint()``/``dispatch`` restore
    replays a killed run bitwise.

    ``make_batches(client_id) -> batches`` keeps the data plane outside:
    leaves must be (τ, 1, ...) — the client axis of the shared client phase is
    1 here, one jitted computation reused for every completion (no
    recompiles). With ``pcfg.partial_progress`` the completion's realized τ_i
    rides in as a traced (1,) τ-mask and the admission weight is scaled by
    τ_i/τ (the aggregator's weight policy).
    """

    def __init__(
        self,
        loss_fn: Callable,
        fed: FederatedConfig,
        acfg: AsyncAggConfig,
        pcfg: ParticipationConfig,
        make_batches: Callable[[int], Dict[str, jax.Array]],
        *,
        seed: int = 0,
        params=None,
        rng: Optional[jax.Array] = None,
        state: Optional[Dict[str, Any]] = None,
        codec: Optional[Codec] = None,
        dispatch: Optional[Dict[str, Any]] = None,
        fused_server: bool = False,
        tracer=None,
        controller=None,
        robust: Optional[RobustAggConfig] = None,
    ):
        super().__init__(
            fed, acfg, pcfg, seed=seed, params=params, rng=rng, state=state,
            codec=codec, dispatch=dispatch, fused_server=fused_server,
            tracer=tracer, controller=controller, robust=robust,
        )
        self.make_batches = make_batches
        fed1 = replace(fed, clients_per_round=1, keep_inner_state=False)
        stateful, partial = self._stateful, pcfg.partial_progress

        # one client phase for every (codec, partial) shape: the optional lanes
        # (per-dispatch rng for stochastic rounding, the client's EF residual
        # row, the (1,) τ-mask) ride in a dict of traced extras
        def _client(p, r, b, extra):
            st = {"params": p, "round": r}
            kw: Dict[str, Any] = {}
            if codec is not None:
                st["rng"] = extra["rng"]
            if stateful:
                kw["residuals"] = extra["res"]
            if partial:
                kw["tau_steps"] = extra["tau"]
            return run_clients(loss_fn, fed1, st, b, codec=codec, **kw)

        self._client_fn = jax.jit(_client)

    def step(self) -> Optional[Dict[str, float]]:
        """Advance the timeline by one completion event; dispatch a replacement.

        Returns the flush metrics row when this event's admission triggered an
        outer update, else None.
        """
        ev, snapshot, version = self._pop_completion()
        row = None
        rs = self.robust_state
        if (
            ev.completes
            and rs is not None
            and rs.is_quarantined(int(ev.client), int(self.state["round"]))
        ):
            # a quarantined client never runs its phase: its slot's simulated
            # time is wasted work and the dispatch machinery moves on
            self.work_wasted += ev.duration
            self._trace_complete(ev, "quarantined")
            self._dispatch()
            return None
        if ev.completes:
            # the client trained and consumed its data either way — but when the
            # server is certain to reject the upload (staleness is known at pop
            # time: no flush can intervene), skip the simulation's τ-step compute.
            # Not with an error-feedback codec: the client compresses and uploads
            # before learning of the rejection, so its residual must advance —
            # run the client phase and let admission refuse the payload.
            staleness = int(self.state["round"]) - version
            rejected = 0 < self.acfg.max_staleness < staleness
            batches = self.make_batches(ev.client)
            if rejected and self.residuals is None:
                self.work_wasted += ev.duration
                self._trace_complete(ev, "rejected_stale", staleness=staleness)
            else:
                extra: Dict[str, Any] = {}
                if self.codec is not None:
                    # unique per dispatch: fold_in by the event's dispatch index
                    extra["rng"] = jax.random.fold_in(self._uplink_rng, ev.index)
                if self.pcfg.partial_progress:
                    extra["tau"] = jnp.asarray(
                        [ev.local_steps or self.fed.local_steps], jnp.int32
                    )
                if self.residuals is not None:
                    cid = jnp.asarray(ev.client, jnp.int32)
                    extra["res"] = self._res_gather(self.residuals, cid)
                deltas, aux = self._client_fn(
                    snapshot, jnp.asarray(version, jnp.int32), batches, extra
                )
                if self.residuals is not None:
                    # the residual belongs to the client regardless of what the
                    # server decides about this upload
                    self.residuals = self._res_scatter(
                        self.residuals, cid, aux["residuals"]
                    )
                    self._res_norms.append(float(self._res_norm_fn(aux["residuals"])))
                delta = jax.tree_util.tree_map(lambda d: d[0], deltas)
                if self.corrupt_fn is not None:
                    # Byzantine-client simulation: corrupt the honest delta at
                    # the (virtual) push side, before the admission door
                    delta = self.corrupt_fn(int(ev.client), int(ev.index), delta)
                self.uplink_bytes_total += self._bytes_per_upload
                m = self.admit(delta, version, self.event_weight(ev))
                self._note_admission(ev, m)
                rec = self._trace_admit(ev, m)
                if float(m["accepted"]) > 0:
                    self.work_completed += ev.duration
                    self._staleness.append(float(m["staleness"]))
                    self._losses.append(float(aux["step_metrics"]["loss"][-1]))
                    self._trace_complete(ev, "admitted",
                                         staleness=rec.get("staleness"))
                else:  # rejected at admission: must not skew the flush row
                    self.work_wasted += ev.duration
                    self._trace_complete(ev, "rejected",
                                         staleness=rec.get("staleness"))
            if self.should_flush():
                row = self._flush_row(self.flush())
        else:
            self.work_wasted += ev.duration
            self._trace_complete(ev, "no_show")
        self._dispatch()
        return row

    def run_updates(
        self,
        n_updates: int,
        on_update: Optional[Callable[[int, Dict[str, float]], None]] = None,
        max_events: Optional[int] = None,
    ) -> List[Dict[str, float]]:
        """Run the event loop until ``n_updates`` outer updates have been applied.

        Raises if the event budget runs out first (pathologically offline
        populations or aggressive ``max_staleness`` rejection) — a silently
        truncated history would corrupt any wall-clock-to-loss comparison.
        """
        history: List[Dict[str, float]] = []
        budget = max_events if max_events is not None else 1000 * max(1, n_updates)
        while len(history) < n_updates and budget > 0:
            budget -= 1
            row = self.step()
            if row is not None:
                row["update"] = len(history)
                history.append(row)
                if on_update is not None:
                    on_update(len(history) - 1, row)
        if len(history) < n_updates:
            raise RuntimeError(
                f"async event budget exhausted after {len(history)}/{n_updates} "
                f"outer updates (buffer admits too rarely: mostly-offline "
                f"population, zero weights, or max_staleness rejecting "
                f"everything) — raise max_events or loosen the configuration"
            )
        return history
