"""The synchronous round programs, built in one place.

:func:`build_round_programs` compiles the federated round that
:class:`~repro.core.aggregator.SyncAggregator` drives, and
``launch/steps.build_train_step`` lowers the same programs on a device mesh
(``shard_clients`` carries the mesh's client-axis sharding constraint).
:func:`check_round_options` holds the rules for which server phase can run
with which cohort layout; the builder and the async aggregator both call it,
so a refused combination is refused in the same words everywhere.

Flat path: one program a round, ``fed_round(state, batches, weights,
residuals, tau)``. The cohort's error-feedback rows enter as a traced argument
(``None`` without a stateful codec), so the program never sees the
population. Tiled path (``cohort_tile``): ``fed_round_tile`` replayed per
C_tile slice plus the partial-sum server program ``fed_round_server``; under a
robust rule, ``fed_round_fold``/``fed_round_fold_server`` (trimmed, median) or
``fed_round_tile_clip`` (normclip) take the place of the plain partial sum.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.compression import Codec
from repro.core.federated import (
    FederatedConfig,
    _finish_aggregate,
    _weigh_clients,
    apply_aggregate_partial,
    federated_round,
    run_client_tile,
)
from repro.core.robust import (
    RobustAggConfig,
    make_robust_apply_fn,
    normclip_scale,
    sanitize_deltas,
    tile_fold_finish,
    tile_fold_update,
)


class RoundPrograms(NamedTuple):
    """The jitted programs of one sync round layout; ``None`` where the
    layout has no such program."""

    round: Optional[Callable] = None  # flat: fed_round
    tile: Optional[Callable] = None  # tiled: fed_round_tile
    server: Optional[Callable] = None  # tiled: fed_round_server
    fold: Optional[Callable] = None  # tiled trimmed/median: fed_round_fold
    fold_server: Optional[Callable] = None  # ... fed_round_fold_server
    tile_clip: Optional[Callable] = None  # tiled normclip: fed_round_tile_clip


def check_round_options(
    fed: FederatedConfig,
    *,
    robust: Optional[RobustAggConfig] = None,
    fused_server: bool = False,
    cohort_tile: Optional[int] = None,
) -> None:
    """Refuse the option combinations no round program can run."""
    if robust is not None and robust.active and fused_server:
        raise ValueError(
            "--fused-server is a plain weighted-mean flat-buffer pass and "
            "cannot host a robust rule or the delta screen — drop one of "
            "--fused-server / --robust-agg / --screen"
        )
    if cohort_tile is None:
        return
    if robust is not None and robust.screen:
        raise ValueError(
            "the median/MAD delta screen needs the whole cohort's norms in "
            "one pass and cannot compose with --cohort-tile (tiles fold "
            "before the cohort median exists) — drop --screen or "
            "--cohort-tile, or use --robust-agg trimmed/median"
        )
    if robust is not None and robust.rule == "normclip" and robust.clip_norm <= 0.0:
        raise ValueError(
            "adaptive norm-clipping (clip_norm=0) needs the cohort median "
            "norm before any tile folds — use an absolute --clip-norm with "
            "--cohort-tile"
        )
    if cohort_tile < 1:
        raise ValueError(f"cohort_tile must be >= 1, got {cohort_tile}")
    if fed.keep_inner_state:
        raise ValueError(
            "cohort tiling cannot keep per-client inner state across rounds "
            "(the (K, ...)-shaped inner store is the memory term tiling "
            "removes) — drop --keep-opt or --cohort-tile"
        )
    if fused_server:
        raise ValueError(
            "--fused-server consumes the full (C, N) delta buffer with "
            "pre-normalized weights, not the tiled partial-sum layout — drop "
            "one of --fused-server / --cohort-tile"
        )


def build_round_programs(
    loss_fn: Callable,
    fed: FederatedConfig,
    *,
    codec: Optional[Codec] = None,
    robust: Optional[RobustAggConfig] = None,
    fused_server: bool = False,
    cohort_tile: Optional[int] = None,
    shard_clients: Optional[Callable] = None,
    donate: bool = True,
) -> RoundPrograms:
    """The jitted programs of one sync round under these options.

    The server state is exclusively the caller's and every round replaces it
    wholesale: donating it lets XLA update the params-sized lanes in place
    instead of double-buffering them (a no-op on backends without donation
    support). The gathered residual rows are freshly stacked per round and
    replaced by the round's output rows, so they donate too."""
    check_round_options(
        fed, robust=robust, fused_server=fused_server, cohort_tile=cohort_tile
    )
    if cohort_tile is not None:
        return _tiled_programs(
            loss_fn, fed, codec, robust, int(cohort_tile), shard_clients, donate
        )
    apply_fn = None
    if fused_server:
        # deferred: kernels/fedcore imports core modules for the seam types
        from repro.kernels.fedcore import fused_apply_aggregate

        apply_fn = fused_apply_aggregate
    elif robust is not None and robust.active:
        # the robust server phase is a drop-in at the same apply_fn seam the
        # fused phase uses; the tiled path composes differently (a per-tile
        # order-statistic fold)
        apply_fn = make_robust_apply_fn(fed, robust)
    stateful = codec is not None and codec.stateful
    donate_kw = {"donate_argnums": (0, 3) if stateful else (0,)} if donate else {}

    def fed_round(s, b, w, res, tau):
        return federated_round(
            loss_fn, fed, s, b, client_weights=w, codec=codec,
            residuals=res, shard_clients=shard_clients, tau_steps=tau,
            apply_fn=apply_fn,
        )

    return RoundPrograms(round=jax.jit(fed_round, **donate_kw))


def _tiled_programs(loss_fn, fed, codec, robust, cohort_tile, shard_clients, donate):
    fed_tile = replace(fed, clients_per_round=cohort_tile)
    robust_tiled = robust is not None and robust.active
    # the robust fold needs the tile's decoded per-client deltas (order
    # statistics cannot be recovered from the weighted partial sum); the
    # default path keeps the memory-minimal partial-sum-only output
    return_deltas = robust_tiled

    def donating(*argnums):
        return {"donate_argnums": argnums} if donate else {}

    def fed_round_tile(s, b, w, res, tau):
        return run_client_tile(
            loss_fn, fed_tile, s, b, w, shard_clients=shard_clients,
            codec=codec, residuals=res, tau_steps=tau,
            return_deltas=return_deltas,
        )

    def fed_round_server(s, dsum, w, dn):
        with jax.named_scope("server"):
            return apply_aggregate_partial(fed, s, dsum, w, dn)

    programs = RoundPrograms(
        # every tile of a round reads the same params snapshot, so only the
        # tile's residual rows donate
        tile=jax.jit(fed_round_tile, **donating(3)),
        # donate the server state only: the Σ wΔ partial sums feed the
        # pseudo-gradient metrics as well as the update, so XLA cannot alias
        # their buffers (donating them would just warn)
        server=jax.jit(fed_round_server, **donating(0)),
    )
    if robust_tiled and robust.rule in ("trimmed", "median"):
        rule, trim = robust.rule, robust.trim_fraction

        def fed_round_fold(fold, deltas, norms, w):
            admit = (w > 0) & jnp.isfinite(norms)
            return tile_fold_update(
                fold, sanitize_deltas(deltas, jnp.isfinite(norms)), admit
            )

        def fed_round_fold_server(fold, s, dn, w):
            with jax.named_scope("server"):
                pg = tile_fold_finish(fold, rule, trim)
                return _finish_aggregate(fed, s, pg, dn, w)

        programs = programs._replace(
            fold=jax.jit(fed_round_fold, **donating(0)),
            fold_server=jax.jit(fed_round_fold_server, **donating(1)),
        )
    elif robust_tiled and robust.rule == "normclip":
        tau_clip = float(robust.clip_norm)  # absolute-only with tiles

        def fed_round_tile_clip(deltas, norms, w):
            admit = (w > 0) & jnp.isfinite(norms)
            scale = normclip_scale(norms, admit, jnp.asarray(tau_clip, jnp.float32))
            clean = sanitize_deltas(deltas, jnp.isfinite(norms))
            return jax.tree_util.tree_map(
                lambda x: jnp.sum(
                    _weigh_clients(x, w.astype(jnp.float32) * scale), axis=0
                ),
                clean,
            )

        programs = programs._replace(tile_clip=jax.jit(fed_round_tile_clip))
    return programs
