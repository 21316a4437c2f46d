"""End-to-end federated pre-training driver (Photon Aggregator + LLM Nodes in one
process for CPU; the same round step pjit-shards onto the production mesh on TPU).

Implements Algorithm 1 faithfully: reproducible client sampling, per-round stream
binding, local training via the jitted federated round, checkpoint/auto-resume,
held-out validation, and the paper's norm monitors.

Elastic participation (paper §7 robustness claims): ``--participation`` picks the
client-availability model (``uniform`` | ``dirichlet`` popularity skew | ``markov``
on/off churn), ``--dropout-rate`` injects seeded mid-round client failures, and
``--straggler-profile`` (``none`` | ``mild`` | ``heavy``, with ``--deadline`` to
override the cut-off) simulates hardware heterogeneity — clients that miss the round
deadline are masked out of the aggregate. Dropped/straggling clients contribute
zero-weight deltas inside the same jitted round, so the effective cohort varies per
round with no recompilation. ``--client-weighting examples`` switches the aggregate
to FedAvg data-size weighting. Per-round effective-K, weight entropy, and straggler
counts are logged alongside the paper's norm monitors.

Async buffered aggregation (Photon's FedBuff-style aggregator, arXiv 2411.02908):
``--aggregation async`` replaces the deadline-masking synchronous round with an
event-driven timeline — K client slots stay busy, each completed client's
pseudo-gradient is admitted into a server-side delta buffer with a staleness
discount ``w/(1+s)^α``, and one outer update fires per ``--buffer-size`` admitted
deltas. Slow clients land in later buffers instead of being masked to zero, so
under straggler-heavy profiles the simulated wall-clock per unit of aggregated
work drops (logged as ``sim_time`` + ``wallclock_speedup`` per update, with
staleness histograms and buffer occupancy). ``--staleness-alpha`` sets the
discount exponent; ``--max-staleness`` rejects deltas older than that many server
rounds.

Compressed uplink (``core/compression.py`` codecs): ``--uplink {float32,bf16,
int8,topk}`` encodes each client's pseudo-gradient before it crosses the
client→server boundary — bf16 stochastic rounding (2x), per-tensor int8 (~4x), or
top-k sparsification with per-client error feedback (``--topk-fraction``, 10-100x).
The identity (float32) uplink is bitwise the uncompressed round. Error-feedback
residuals are keyed by population client id (one row per client, under sync
cohorts AND async dispatch), live inside the checkpointed state, and resume
exactly; per-round uplink bytes / compression ratio / residual norms are logged.

Straggler partial progress (``--partial-progress``, ROADMAP item 1): instead of
cutting a slow client at the deadline, credit the τ_i = min(τ,
⌊τ·speed·deadline⌋) local steps it actually finished — the jitted round holds a
spent client's lanes via a traced (K,) τ-mask (no recompile as τ_i varies) and
the Aggregator's weight policy scales its delta by τ_i/τ. Under async the
deadline becomes a per-dispatch budget and the partial delta admits at the
fractional weight. Per-round mean τ_i/τ, full-τ fraction and rescued-compute
estimates are logged.

Cross-process runtime (``--runtime sockets``, docs/runtime.md): the simulated
single-process timeline becomes a real deployment — ``--role server`` owns the
buffered aggregator, the dispatch manifest and every client's data cursor
behind a length-prefixed socket protocol; N ``--role client`` worker processes
pull self-describing assignments, run the same jitted client phase and push
encoded uplink payloads back. Leases redispatch work from dead workers,
``--flush-deadline`` keeps rounds progressing past stragglers, ``--chaos-*``
injects drop/delay/kill faults, and because the server alone owns resumable
state, ``--resume`` after a server kill replays the remainder bitwise. With
the same seeds the socket run's final params are bitwise the in-process run's.

Server-side aggregation is driven through the unified ``Aggregator`` seam
(``core/aggregator.py``): ``SyncAggregator`` / ``AsyncFederationDriver`` own
the admission rule, the weight policy and the canonical checkpoint schema —
which is what makes ``--aggregation async --resume`` exact: every update
checkpoints the buffer lanes, residual store, dispatch cursor and in-flight
params snapshots, and a killed-and-resumed run is bitwise the uninterrupted one.

Adaptive aggregation control (``--control``, docs/control.md): close the loop
between the observed telemetry and the aggregation knobs. ``--control
staleness`` (async) drives ``--staleness-alpha``/``--buffer-size`` toward a
target admitted-staleness quantile read off the cumulative histogram;
``--control cohort`` (sync) tunes the straggler deadline and
``--clients`` from the realized effective-K fraction. ``--control static``
(the default) is the identity — bitwise the uncontrolled run. Knob updates
land only at round/flush boundaries on bucketed grids (α on 1/16 steps, buffer
on powers of two, K in steps of 2), are emitted as ``knob_update`` obs events
with their triggering evidence, and the controller state rides the checkpoint
manifest so a governed run kills and ``--resume``\\ s bitwise.

Byzantine-resilient aggregation (docs/robustness.md): ``--robust-agg
{none,trimmed,median,normclip}`` swaps the server's plain weighted mean for a
robust rule (coordinate-wise trimmed mean / median, or per-delta norm
clipping); ``--screen`` adds a delta screen at the admission boundary —
non-finite deltas are rejected unconditionally, norm outliers past
``--screen-z`` robust z-scores are zero-weighted (sync cohort) or rejected at
the buffer door (async, with a ``--screen-warmup`` adaptive bound) and
quarantined for ``--quarantine-rounds``; ``--rollback`` (requires
``--ckpt-dir``) arms the divergence guard — an update norm spiking past
``--rollback-factor`` × the trailing ``--rollback-window`` median restores
the server from the last good checkpoint. All three compose freely and ride
the checkpoint manifest, so a defended run kills and ``--resume``\\ s bitwise;
with everything off the round is bitwise the undefended one. Attacks come
from ``--chaos-corrupt`` (socket runtime: worker payloads poisoned on the
wire side) or ``--byzantine-fraction``/``--byzantine-kind`` (async inproc:
deterministic attacker clients — the bench harness). ``--robust-agg`` is
incompatible with ``--fused-server``; under ``--cohort-tile`` the trimmed and
median rules stream per-tile fold buffers, normclip needs an absolute
``--clip-norm``, and ``--screen`` (whole-cohort norms) is unavailable.

The full flag matrix — how ``--aggregation`` × ``--uplink`` × ``--runtime`` ×
``--control`` × ``--robust-agg`` compose, and which doc covers which layer —
is mapped in docs/architecture.md.

Usage (CPU, minutes):
  PYTHONPATH=src python -m repro.launch.train --arch photon-75m --reduced \
      --rounds 4 --local-steps 8 --clients 4 --population 8
  PYTHONPATH=src python -m repro.launch.train --reduced --rounds 2 \
      --participation markov --dropout-rate 0.25 --straggler-profile mild
  PYTHONPATH=src python -m repro.launch.train --reduced --rounds 4 \
      --straggler-profile heavy --partial-progress
  PYTHONPATH=src python -m repro.launch.train --reduced --rounds 4 \
      --aggregation async --buffer-size 2 --straggler-profile heavy \
      --uplink topk --topk-fraction 0.05 --ckpt-dir /tmp/ck   # then --resume
  PYTHONPATH=src python -m repro.launch.train --reduced --rounds 6 \
      --aggregation async --straggler-profile heavy --control staleness \
      --control-target 4 --trace /tmp/run.jsonl
  PYTHONPATH=src python -m repro.launch.train --reduced --rounds 6 \
      --aggregation async --byzantine-fraction 0.2 --byzantine-kind nan \
      --robust-agg trimmed --screen --rollback --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.control import (
    CohortTuner,
    FederationController,
    KnobUpdate,
    StalenessGovernor,
)
from repro.core import (
    CORRUPT_KINDS,
    ROBUST_RULES,
    STRAGGLER_PROFILES,
    UPLINK_SCHEMES,
    AsyncAggConfig,
    AsyncBufferAggregator,
    AsyncFederationDriver,
    FederatedConfig,
    InnerOptConfig,
    OuterOptConfig,
    ParticipationConfig,
    RobustAggConfig,
    SyncAggregator,
    get_codec,
    make_byzantine_fn,
    plan_round,
)
from repro.core.round_program import check_round_options
from repro.data import build_client_streams, round_batches, validation_stream
from repro.launch.compile_env import CompileCounter, enable_compile_cache
from repro.metrics import (
    MetricLogger,
    evaluate_perplexity,
    partial_progress_metrics,
    participation_metrics,
    perplexity,
    staleness_stats,
    uplink_round_metrics,
    wallclock_speedup,
)
from repro.models import build_model
from repro.obs import JsonlSink, MetricsServer, Tracer
from repro.runtime import ChaosConfig, ClientWorker, FederationDriver, SocketBackend


def _chaos_from_args(args):
    chaos = ChaosConfig(
        drop=args.chaos_drop, delay=args.chaos_delay, kill=args.chaos_kill,
        corrupt=args.chaos_corrupt,
        corrupt_kinds=tuple(
            k.strip() for k in args.chaos_corrupt_kinds.split(",") if k.strip()
        ),
        seed=args.chaos_seed,
    )
    return chaos if chaos.active else None


def _robust_from_args(args):
    """``--robust-agg``/``--screen``/``--rollback`` → a
    :class:`RobustAggConfig`, or None when every defense is off (the
    aggregators then install no robust apply_fn at all — trivially bitwise
    the undefended round)."""
    if args.robust_agg == "none" and not args.screen and not args.rollback:
        return None
    return RobustAggConfig(
        rule=args.robust_agg,
        trim_fraction=args.trim_fraction,
        clip_mult=args.clip_mult,
        clip_norm=args.clip_norm,
        screen=args.screen,
        screen_z=args.screen_z,
        screen_warmup=args.screen_warmup,
        rollback=args.rollback,
        rollback_window=args.rollback_window,
        rollback_factor=args.rollback_factor,
        quarantine_rounds=args.quarantine_rounds,
    )


def _build_tracer(args, proc):
    """One tracer per process: events go to ``--trace`` (JSONL), counters feed
    ``--metrics-port``. Returns None when neither flag is set — every
    instrumented seam then sees the zero-overhead NULL_TRACER."""
    if args.trace is None and args.metrics_port is None:
        return None
    sink = JsonlSink(args.trace) if args.trace else None
    return Tracer(sink=sink, proc=proc, trace_id=f"seed{args.seed}")


def _start_metrics(args, tracer, extra=None):
    if tracer is None or args.metrics_port is None:
        return None
    srv = MetricsServer(tracer, port=args.metrics_port, extra=extra)
    print(f"metrics serving on {srv.host}:{srv.port}", flush=True)
    return srv


def _build_controller(args, acfg=None, straggler=None):
    """``--control`` → a :class:`FederationController` (or None for static).

    Validates the policy/aggregation pairing up front: the staleness governor
    only has async knobs, the cohort tuner only sync ones, and cohort resizing
    is incompatible with ``--keep-opt`` (the persisted inner state is
    K-shaped)."""
    if args.control == "static":
        return None  # no controller object at all: the bitwise-default path
    if args.control == "staleness":
        if args.aggregation != "async":
            raise SystemExit(
                "--control staleness drives the async buffer knobs "
                "(--staleness-alpha/--buffer-size) — it requires "
                "--aggregation async; for sync runs use --control cohort"
            )
        policy = StalenessGovernor(
            staleness_alpha=args.staleness_alpha,
            buffer_size=acfg.buffer_size,
            target=args.control_target if args.control_target is not None else 1.0,
            quantile=args.control_quantile,
            gain=args.control_gain if args.control_gain is not None else 0.5,
            buffer_max=max(acfg.buffer_size, args.clients),
        )
    else:  # cohort
        if args.aggregation != "sync":
            raise SystemExit(
                "--control cohort drives the sync deadline/cohort knobs — it "
                "requires --aggregation sync; for async runs use "
                "--control staleness"
            )
        if args.keep_opt:
            raise SystemExit(
                "--control cohort resizes the cohort, which is incompatible "
                "with --keep-opt (the persisted inner optimizer state is "
                "(K, ...)-shaped)"
            )
        if straggler.deadline <= 0.0:
            raise SystemExit(
                "--control cohort needs a finite straggler deadline to tune: "
                "pick --straggler-profile mild/heavy or set --deadline"
            )
        policy = CohortTuner(
            clients_per_round=args.clients,
            deadline=straggler.deadline,
            population=args.population,
            target=args.control_target if args.control_target is not None else 0.9,
            gain=args.control_gain if args.control_gain is not None else 0.25,
        )
    return FederationController(
        policy, window=args.control_window, interval=args.control_interval
    )


def _restore_controller(controller, manifest, latest):
    """Reconcile ``--control`` with the checkpoint's controller state.

    Returns the restored controller (None for a static resume). Refuses every
    asymmetric combination — a governed run resumed statically (or vice versa)
    would silently follow a different knob trajectory than the original."""
    ctrl_state = manifest.get("control") if isinstance(manifest, dict) else None
    if controller is None:
        if ctrl_state is not None:
            raise SystemExit(
                f"--resume: checkpoint round {latest} carries live "
                f"--control {ctrl_state.get('policy')} state but this run asked "
                f"for --control static — the knob trajectory would diverge; "
                f"resume with the original policy"
            )
        return None
    if ctrl_state is None:
        raise SystemExit(
            f"--resume: --control {controller.policy.name} requested but "
            f"checkpoint round {latest} was written without a controller — "
            f"resume with --control static or start fresh"
        )
    try:
        controller.load_state_dict(ctrl_state)
    except ValueError as e:
        raise SystemExit(f"--resume: {e}")
    return controller


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="photon-75m")
    ap.add_argument("--reduced", action="store_true", help="use the smoke-scale config")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=8, help="τ")
    ap.add_argument("--clients", type=int, default=4, help="K sampled per round")
    ap.add_argument("--population", type=int, default=8, help="P total clients")
    ap.add_argument("--batch", type=int, default=4, help="per-client batch size")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--heterogeneous", action="store_true", help="Pile-style partition")
    ap.add_argument("--outer", default="fedavg", choices=["fedavg", "fedmom", "fedadam"])
    ap.add_argument("--outer-lr", type=float, default=1.0)
    ap.add_argument("--inner-lr", type=float, default=3e-4)
    ap.add_argument("--keep-opt", action="store_true")
    ap.add_argument("--fedprox-mu", type=float, default=0.0)
    ap.add_argument("--dp-clip", type=float, default=0.0)
    ap.add_argument("--dp-noise", type=float, default=0.0)
    ap.add_argument("--pseudo-grad-dtype", default="float32",
                    help="legacy flat-cast uplink; superseded by --uplink")
    ap.add_argument(
        "--uplink", default="float32", choices=list(UPLINK_SCHEMES),
        help="pseudo-gradient uplink codec: float32 (identity, bitwise the "
             "uncompressed round), bf16 stochastic-rounding cast, per-tensor "
             "int8, or top-k sparsification with per-client error feedback",
    )
    ap.add_argument("--topk-fraction", type=float, default=0.05,
                    help="--uplink topk: fraction of entries kept per tensor")
    ap.add_argument(
        "--fused-server", action="store_true",
        help="fused Pallas federation path (kernels/fedcore): the server "
             "weighted-mean + DP noise + outer update run as ONE pass over the "
             "flat (C, N) delta buffer, and --uplink codecs use the fused "
             "flat-buffer kernels. Compiled on TPU; on CPU hosts the identical "
             "math runs as a flat XLA chain. Off (default) keeps the per-leaf "
             "jnp reference path, bitwise-unchanged",
    )
    ap.add_argument(
        "--cohort-tile", type=int, default=None,
        help="sync: stream the cohort through the round in fixed-size tiles "
             "of this many clients, folding each tile into weighted partial "
             "sums (two-tier aggregation, docs/aggregation.md) so the (C, N) "
             "delta buffer is bounded by the tile size regardless of cohort "
             "size. Bitwise the flat round when the tile equals --clients. "
             "Incompatible with --fused-server and --keep-opt",
    )
    ap.add_argument(
        "--participation", default="uniform", choices=["uniform", "dirichlet", "markov"],
        help="client-availability model: uniform sampling, Dirichlet popularity "
             "skew, or per-client Markov on/off churn",
    )
    ap.add_argument("--dirichlet-alpha", type=float, default=0.3,
                    help="popularity concentration for --participation dirichlet")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="per-round probability each selected client fails mid-round")
    ap.add_argument(
        "--straggler-profile", default="none", choices=sorted(STRAGGLER_PROFILES),
        help="hardware-heterogeneity preset; stragglers past the deadline are masked",
    )
    ap.add_argument("--deadline", type=float, default=None,
                    help="round deadline in median-client-round units (overrides profile)")
    ap.add_argument(
        "--partial-progress", action="store_true",
        help="straggler partial progress: a client that misses the deadline "
             "contributes the τ_i = min(τ, ⌊τ·speed·deadline⌋) local steps it "
             "actually finished, weighted by τ_i/τ, instead of being cut "
             "(sync) or arriving late (async: the deadline becomes a "
             "per-dispatch budget and partial deltas admit at fractional "
             "weight)",
    )
    ap.add_argument(
        "--client-weighting", default="uniform", choices=["uniform", "examples"],
        help="aggregation weights: uniform mean or FedAvg data-size (n_k) weighting",
    )
    ap.add_argument(
        "--aggregation", default="sync", choices=["sync", "async"],
        help="sync: deadline-masked federated rounds; async: FedBuff-style "
             "buffered aggregation — stragglers land in later buffers with "
             "staleness-discounted weights instead of being dropped",
    )
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="async: deltas per outer update (M); default max(1, K//2)")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="async: staleness discount exponent in w/(1+s)^alpha")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="async: reject deltas older than this many server rounds "
                         "(0 = accept any age)")
    ap.add_argument(
        "--runtime", default="inproc", choices=["inproc", "sockets"],
        help="inproc: the simulated single-process timeline; sockets: a real "
             "cross-process deployment — this process is the aggregation "
             "server (--role server) or one client worker (--role client) "
             "speaking the length-prefixed socket protocol (docs/runtime.md). "
             "Requires --aggregation async",
    )
    ap.add_argument("--role", default="server", choices=["server", "client"],
                    help="--runtime sockets: which process this is")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="server: listen port (0 = pick a free one, printed at "
                         "startup); client: the server's port")
    ap.add_argument("--worker-id", default="worker-0",
                    help="--role client: this worker's name (lease bookkeeping)")
    ap.add_argument("--lease-timeout", type=float, default=30.0,
                    help="server: seconds before a granted-but-unreturned "
                         "assignment is redispatched to another worker")
    ap.add_argument("--io-timeout", type=float, default=30.0,
                    help="sockets: per-request socket timeout")
    ap.add_argument("--flush-deadline", type=float, default=None,
                    help="server: flush a partially filled buffer when the next "
                         "in-order result stalls this many seconds (default: "
                         "wait forever — preserves exact parity with inproc)")
    ap.add_argument("--chaos-drop", type=float, default=0.0,
                    help="fault injection: P(outbound message dropped)")
    ap.add_argument("--chaos-delay", type=float, default=0.0,
                    help="fault injection: P(outbound message delayed)")
    ap.add_argument("--chaos-kill", type=float, default=0.0,
                    help="fault injection: P(process hard-exits before a send)")
    ap.add_argument("--chaos-corrupt", type=float, default=0.0,
                    help="fault injection: P(a worker's push payload is "
                         "poisoned before send — NaN/Inf fill, ×64 scale, "
                         "sign flip or replay of the previous push; "
                         "docs/robustness.md)")
    ap.add_argument("--chaos-corrupt-kinds", default=",".join(CORRUPT_KINDS),
                    help="comma-separated corruption kinds the --chaos-corrupt "
                         f"die picks from (any of: {', '.join(CORRUPT_KINDS)})")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument(
        "--robust-agg", default="none", choices=list(ROBUST_RULES),
        help="Byzantine-resilient aggregation rule (docs/robustness.md): "
             "none (plain weighted mean, bitwise the undefended round), "
             "trimmed (coordinate-wise trimmed mean), median (coordinate-wise "
             "median), or normclip (per-delta norm clipping before the "
             "weighted mean)",
    )
    ap.add_argument("--trim-fraction", type=float, default=0.1,
                    help="--robust-agg trimmed: fraction of extreme values "
                         "trimmed from EACH tail per coordinate")
    ap.add_argument("--clip-mult", type=float, default=3.0,
                    help="--robust-agg normclip: clip threshold as a multiple "
                         "of the cohort's median delta norm (used when "
                         "--clip-norm is 0)")
    ap.add_argument("--clip-norm", type=float, default=0.0,
                    help="--robust-agg normclip: absolute clip threshold "
                         "(0 = derive from --clip-mult; required >0 with "
                         "--cohort-tile)")
    ap.add_argument(
        "--screen", action="store_true",
        help="delta screen at the admission boundary: non-finite deltas are "
             "rejected unconditionally and norm outliers (median/MAD z-score "
             "past --screen-z) are zero-weighted (sync) or rejected at the "
             "buffer door (async)",
    )
    ap.add_argument("--screen-z", type=float, default=6.0,
                    help="--screen: robust z-score threshold for norm outliers")
    ap.add_argument("--screen-warmup", type=int, default=8,
                    help="async --screen: admitted norms observed before the "
                         "adaptive bound engages (unbounded until then)")
    ap.add_argument(
        "--rollback", action="store_true",
        help="divergence guard + automatic rollback (requires --ckpt-dir): "
             "when the update norm spikes past --rollback-factor × the "
             "trailing window median (or goes non-finite), the server "
             "restores params/outer from the last good checkpoint and "
             "quarantines the round's contributors (sync)",
    )
    ap.add_argument("--rollback-window", type=int, default=8,
                    help="--rollback: trailing update norms in the guard window")
    ap.add_argument("--rollback-factor", type=float, default=4.0,
                    help="--rollback: spike multiple over the window median "
                         "that trips the guard")
    ap.add_argument("--quarantine-rounds", type=int, default=4,
                    help="rounds a screened/rolled-back client is excluded "
                         "from aggregation")
    ap.add_argument("--byzantine-fraction", type=float, default=0.0,
                    help="simulated attack (async inproc, bench harness): "
                         "population clients below floor(fraction·P) corrupt "
                         "every delta they push")
    ap.add_argument("--byzantine-kind", default="scale",
                    choices=[k for k in CORRUPT_KINDS if k != "replay"],
                    help="what the --byzantine-fraction attackers send")
    ap.add_argument(
        "--control", default="static", choices=["static", "staleness", "cohort"],
        help="closed-loop aggregation control (docs/control.md): static = the "
             "identity policy, bitwise the uncontrolled run; staleness (async "
             "only) governs --staleness-alpha/--buffer-size toward a target "
             "admitted-staleness quantile; cohort (sync only) tunes the "
             "straggler deadline and --clients from the effective-K fraction",
    )
    ap.add_argument("--control-target", type=float, default=None,
                    help="policy setpoint: the admitted-staleness quantile "
                         "value in server rounds (staleness, default 1.0) or "
                         "the effective-K fraction (cohort, default 0.9)")
    ap.add_argument("--control-quantile", type=float, default=0.9,
                    help="--control staleness: which staleness quantile to "
                         "hold at the target")
    ap.add_argument("--control-gain", type=float, default=None,
                    help="proportional gain of the control law (default 0.5 "
                         "staleness / 0.25 cohort); lower it if the policy "
                         "oscillates (docs/control.md tuning guide)")
    ap.add_argument("--control-window", type=int, default=4,
                    help="metric rows the controller aggregates per decision")
    ap.add_argument("--control-interval", type=int, default=1,
                    help="boundaries between control decisions (1 = every "
                         "round/flush)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="append structured trace events to this JSONL file "
                         "(docs/observability.md); under --runtime sockets "
                         "give each process its own path, then merge with "
                         "python -m repro.obs.report. Tracing never changes "
                         "aggregation results (bitwise, tested)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve a Prometheus-style text endpoint on "
                         "127.0.0.1:PORT/metrics (0 = pick a free port, "
                         "printed at startup)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--eval-batches", type=int, default=2)
    return ap.parse_args(argv)


def run(args, cfg=None) -> dict:
    if cfg is None:
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, max_seq_len=max(cfg.max_seq_len, args.seq_len))
    model = build_model(cfg)

    fed = FederatedConfig(
        clients_per_round=args.clients,
        local_steps=args.local_steps,
        inner=InnerOptConfig(
            lr_max=args.inner_lr,
            warmup_steps=max(1, args.rounds * args.local_steps // 20),
            total_steps=args.rounds * args.local_steps,
            router_bias_rate=cfg.router_bias_rate,
        ),
        outer=OuterOptConfig(name=args.outer, lr=args.outer_lr),
        keep_inner_state=args.keep_opt,
        fedprox_mu=args.fedprox_mu,
        dp_clip=args.dp_clip,
        dp_noise=args.dp_noise,
        pseudo_grad_dtype=args.pseudo_grad_dtype,
    )

    straggler = STRAGGLER_PROFILES[args.straggler_profile]
    if args.deadline is not None:
        straggler = dataclasses.replace(straggler, deadline=args.deadline)
    pcfg = ParticipationConfig(
        population=args.population,
        clients_per_round=args.clients,
        model=args.participation,
        dirichlet_alpha=args.dirichlet_alpha,
        dropout_rate=args.dropout_rate,
        straggler=straggler,
        weighting=args.client_weighting,
    )

    # --- Photon Data Sources: one stream per population member -----------
    streams = build_client_streams(
        args.population, args.seq_len, cfg.vocab_size,
        heterogeneous=args.heterogeneous, seed=args.seed,
    )
    val_stream = validation_stream(args.seq_len, cfg.vocab_size, args.heterogeneous)

    # --- server state ------------------------------------------------------
    params = model.init(jax.random.PRNGKey(args.seed))

    if args.uplink != "float32" and args.pseudo_grad_dtype != "float32":
        raise SystemExit(
            "--uplink and the legacy --pseudo-grad-dtype are mutually exclusive: "
            "the codec already defines the wire format"
        )
    codec = (
        get_codec(args.uplink, args.topk_fraction, fused=args.fused_server)
        if args.uplink != "float32" else None
    )

    if args.runtime == "sockets" and args.aggregation != "async":
        raise SystemExit(
            "--runtime sockets requires --aggregation async: the socket server "
            "IS the buffered-aggregation event loop (docs/runtime.md)"
        )
    try:
        robust = _robust_from_args(args)
    except ValueError as e:
        raise SystemExit(f"--robust-agg: {e}")
    if robust is not None and args.rollback and not args.ckpt_dir:
        raise SystemExit(
            "--rollback restores the server from the last good checkpoint — "
            "it requires --ckpt-dir"
        )
    try:
        # the round builder's rules, refused before anything is built
        # (--cohort-tile under async is refused below: sync only)
        check_round_options(
            fed, robust=robust, fused_server=args.fused_server,
            cohort_tile=args.cohort_tile if args.aggregation == "sync" else None,
        )
    except ValueError as e:
        raise SystemExit(str(e))
    if args.byzantine_fraction > 0.0 and (
        args.aggregation != "async" or args.runtime != "inproc"
    ):
        raise SystemExit(
            "--byzantine-fraction is the in-process async attack simulator "
            "(the bench harness hook); under --runtime sockets inject payload "
            "corruption with --chaos-corrupt instead"
        )
    if args.aggregation == "async":
        if args.cohort_tile:
            raise SystemExit(
                "--cohort-tile applies to --aggregation sync only: the async "
                "path already streams one client delta at a time into the "
                "buffer, so its memory is bounded by the buffer size M, not "
                "the cohort"
            )
        if args.keep_opt:
            raise SystemExit(
                "--keep-opt with --aggregation async is not supported: async "
                "clients are stateless (paper §7.8) — a client's next dispatch "
                "may serve a different model version, so persisted inner Adam "
                "state would be silently stale"
            )
        if args.runtime == "sockets" and args.role == "client":
            return _run_worker(args, model, fed, pcfg, streams, codec)
        return _run_async(args, cfg, model, fed, pcfg, streams, val_stream, params, codec)

    def loss_fn(p, b):
        return model.loss(p, b)

    # the Aggregator seam owns (a) the admission rule (the plan's mask /
    # partial-progress τ_i), (b) the weight policy (FedAvg n_k scaled by τ_i/τ)
    # and (c) the checkpoint schema. Weights, cohort ids and the τ-mask enter
    # the jitted round as traced arguments: per-round participation changes
    # (dropouts, stragglers, K_eff < K, realized τ_i) never trigger a recompile.
    tracer = _build_tracer(args, "server")
    controller = _build_controller(args, straggler=straggler)
    agg = SyncAggregator(
        loss_fn, fed, pcfg, codec=codec, seed=args.seed,
        partial_progress=args.partial_progress, fused_server=args.fused_server,
        cohort_tile=args.cohort_tile, robust=robust,
        params=params, rng=jax.random.PRNGKey(args.seed + 1),
        tracer=tracer, controller=controller,
    )
    metrics_srv = _start_metrics(args, tracer)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_round = 0
    if ckpt and args.resume:
        latest = ckpt.latest_round()
        if latest is not None:
            agg_man = ckpt.load_manifest(latest).get("extra", {}).get("aggregator")
            if agg_man is not None:
                if agg_man.get("kind") != "sync":
                    # load_pytree would silently satisfy the sync template from
                    # an async checkpoint's npz (the sync keys are a strict
                    # subset of the async schema) — refuse the kind mismatch
                    raise SystemExit(
                        f"--resume: checkpoint round {latest} was written by a "
                        f"--aggregation {agg_man.get('kind')} run; resuming it "
                        f"synchronously would silently drop the buffer lanes "
                        f"and the in-flight dispatch queue — resume with the "
                        f"original aggregation mode or start fresh"
                    )
                try:
                    SyncAggregator.validate_manifest(agg_man, "sync")
                except ValueError as e:
                    raise SystemExit(f"--resume: {e}")
            # the load template comes from the checkpoint schema, not from
            # agg.state: the residual lane is sized by the manifest's recorded
            # id set (sparse checkpoints) or by the population (legacy dense
            # checkpoints) — either way nothing population-sized is allocated
            like = SyncAggregator.checkpoint_template(
                fed, pcfg, params, codec,
                uplink_ids=(
                    agg_man.get("uplink_ids")
                    if isinstance(agg_man, dict) else None
                ),
            )
            try:
                state, manifest = ckpt.load_server(latest, like)
            except KeyError as e:
                raise SystemExit(
                    f"--resume: checkpoint round {latest} does not carry the "
                    f"state this run needs (missing {e}); error-feedback "
                    f"residuals only round-trip when the checkpoint was written "
                    f"with the same --uplink codec"
                )
            ckpt_uplink = manifest.get("extra", {}).get("args", {}).get(
                "uplink", "float32"
            )
            if get_codec(ckpt_uplink).stateful and not (
                codec is not None and codec.stateful
            ):
                # the reverse direction of the KeyError above: load_pytree
                # ignores npz keys absent from the template, so without this
                # check the clients' accumulated residual mass would be
                # silently dropped
                raise SystemExit(
                    f"--resume: checkpoint round {latest} was written with "
                    f"--uplink {ckpt_uplink} and carries per-client "
                    f"error-feedback residuals; resuming with --uplink "
                    f"{args.uplink} would silently discard them — use the "
                    f"original codec or start fresh"
                )
            controller = _restore_controller(
                controller, agg_man if isinstance(agg_man, dict) else {}, latest
            )
            if controller is not None:
                # the checkpoint may have been taken mid-trajectory: rebuild
                # the aggregator at the controller's CURRENT knob values, not
                # the CLI defaults, before any round runs
                knobs = controller.knobs()
                agg.apply_knobs(KnobUpdate(
                    clients_per_round=int(knobs["clients_per_round"]),
                    deadline=knobs["deadline"],
                ))
            agg.restore(state, agg_man if isinstance(agg_man, dict) else None)
            start_round = latest + 1
            for i, s in enumerate(streams):
                try:
                    s.load_state_dict(ckpt.load_client(latest, i))
                except FileNotFoundError:
                    pass
            print(f"resumed from round {latest}")

    logger = MetricLogger(args.log) if args.log else None

    history = []
    try:
        with CompileCounter() as compiles:
            _run_sync_rounds(
                args, model, agg, streams, val_stream, ckpt, logger, history,
                start_round, params, codec, compiles,
            )
    finally:
        if metrics_srv is not None:
            metrics_srv.close()
        if tracer is not None:
            tracer.close()

    return {"history": history, "state": agg.state, "model": model, "config": cfg,
            "aggregator": agg}


def _run_sync_rounds(args, model, agg, streams, val_stream, ckpt, logger,
                     history, start_round, params, codec, compiles):
    """The sync loop. Each iteration is one ``iter`` span (id ``i{rnd}``) whose
    children name what the host does in turn: ``plan``, ``data``
    (``round_batches``), ``h2d``, ``launch`` (``agg.run_round``: the round
    program is enqueued), ``sync`` (the metric reads, which wait for it, and
    the row), ``eval``, ``control`` (divergence guard and controller, where
    configured), ``ckpt`` (with ``--ckpt-dir``) and ``log``. In a profile each
    lands on the host plane as ``obs.<name>`` (``obs/tracer.py``). The row's
    ``seconds`` is the iteration up to its ``log``: every other child, eval
    included."""
    span = agg.tracer.span
    guarded = agg.robust_state is not None and agg.robust is not None and agg.robust.rollback
    for rnd in range(start_round, args.rounds):
        it = f"i{rnd}"
        # monotonic: durations, never wall timestamps; read before the span's
        # begin, so that the row's seconds cover every child span whole
        t0 = time.perf_counter()
        with span("iter", it, round=rnd):
            c0, s0 = compiles.count, compiles.seconds
            with span("plan", f"{it}/plan", it):
                plan = agg.plan(rnd)
            sel = plan.selected
            with span("data", f"{it}/data", it):
                batches_np = round_batches(
                    [streams[i] for i in sel], args.local_steps, args.batch
                )
            with span("h2d", f"{it}/h2d", it):
                batches = {k: jnp.asarray(v) for k, v in batches_np.items()}
            with span("launch", f"{it}/launch", it):
                metrics = agg.run_round(batches, plan)
            with span("sync", f"{it}/sync", it):
                metrics = {k: float(v) for k, v in metrics.items()}
                metrics.update(
                    round=rnd,
                    selected=",".join(map(str, sel)),  # slot ids, incl. zero-weight padding
                    contributors=",".join(map(str, sel[plan.mask])),  # actually aggregated
                    train_ppl=perplexity(metrics["train_loss"]),
                    **participation_metrics(plan),
                    **partial_progress_metrics(plan, args.local_steps),
                    **uplink_round_metrics(
                        args.uplink, params, plan.effective_k, args.topk_fraction,
                        codec=codec,
                    ),
                )
            with span("eval", f"{it}/eval", it):
                metrics["val_ppl"] = evaluate_perplexity(
                    model, agg.state["params"], val_stream, batches=args.eval_batches,
                    batch_size=args.batch,
                )
            good = True  # the round may become the divergence guard's resume point
            if guarded or (agg.controller is not None and agg.controller.enabled):
                with span("control", f"{it}/control", it):
                    good = _control_sync_round(agg, ckpt, metrics, plan, rnd, guarded)
            if ckpt:
                with span("ckpt", f"{it}/ckpt", it):
                    _checkpoint_sync_round(args, agg, ckpt, streams, rnd, good)
            # XLA compilations this round triggered (round step, eval, eager
            # ops) and their seconds: nonzero after the first round means
            # something recompiles
            metrics["compiles"] = compiles.count - c0
            metrics["compile_s"] = compiles.seconds - s0
            metrics["seconds"] = time.perf_counter() - t0
            history.append(metrics)
            with span("log", f"{it}/log", it):
                partial = (
                    f" tau={metrics['partial_tau_mean']:.2f} "
                    f"rescued={metrics['partial_rescued_clients']:.0f}"
                    if args.partial_progress else ""
                )
                print(
                    f"round {rnd}: loss={metrics['train_loss']:.4f} "
                    f"val_ppl={metrics['val_ppl']:.2f} "
                    f"pg_norm={metrics['pseudo_grad_norm']:.4f} "
                    f"consensus={metrics['client_consensus']:.3f} "
                    f"eff_K={plan.effective_k}/{len(plan.selected)} "
                    f"stragglers={plan.n_stragglers} dropped={plan.n_dropped}"
                    f"{partial} [{metrics['seconds']:.1f}s]"
                )
                if logger:
                    logger.log(metrics)


def _control_sync_round(agg, ckpt, metrics, plan, rnd, guarded) -> bool:
    """The round boundary's control point: the divergence guard, then the
    controller. Both may add fields to ``metrics``. Returns whether the round
    may be marked good: the guard did not trip, or it rolled the model back."""
    # the guard sees this round's update norm BEFORE the checkpoint save, so a
    # poisoned round is rolled back and never becomes a resume point
    rs = agg.robust_state
    good = True
    if guarded:
        metrics["rolled_back"] = 0.0
        tripped = rs.observe_update(metrics["pseudo_grad_norm"])
        if tripped:
            good = False
            last = rs.last_good
            if last >= 0 and ckpt is not None:
                like = {"params": agg.state["params"],
                        "outer": agg.state["outer"]}
                restored, _ = ckpt.load_server(last, like)
                agg.adopt_model(restored)
                contributors = [int(c) for c in plan.selected[plan.mask]]
                rs.add_quarantine(contributors, rnd)
                rs.note_rollback()
                metrics["rolled_back"] = 1.0
                good = True
                if agg.tracer.enabled:
                    agg.tracer.point(
                        "rollback", round=rnd, restored_round=last,
                        pg_norm=float(metrics["pseudo_grad_norm"])
                        if metrics["pseudo_grad_norm"]
                        == metrics["pseudo_grad_norm"] else -1.0,
                        quarantined=len(contributors),
                    )
                    agg.tracer.count("rollbacks")
                print(
                    f"  ROLLBACK: update norm "
                    f"{metrics['pseudo_grad_norm']:.4g} tripped the "
                    f"divergence guard — restored round {last}, "
                    f"quarantined {contributors} for "
                    f"{agg.robust.quarantine_rounds} rounds"
                )
            else:
                print(
                    "  divergence guard tripped but no good checkpoint "
                    "exists yet — continuing without rollback"
                )
    # the cohort tuner sees this round's composed row and may move the
    # deadline/cohort knobs for the NEXT round (applied knobs echo into the
    # logged row)
    update = agg.control_step(metrics)
    if update is not None:
        for k, v in update.knob_dict().items():
            metrics[f"knob_{k}"] = v
        print("  control: " + ", ".join(
            f"{k}={v:g}" for k, v in update.knob_dict().items()
        ))
    return good


def _checkpoint_sync_round(args, agg, ckpt, streams, rnd, good) -> None:
    rs = agg.robust_state
    if rs is not None and good:
        # marked BEFORE checkpoint() so the saved manifest's last_good points
        # at THIS round — valid exactly when this checkpoint is complete. A
        # post-rollback checkpoint qualifies too: it holds the restored clean
        # state (and keeps the rollback target inside the GC's keep-last
        # window across consecutive trips)
        rs.mark_good(rnd)
    tree, agg_manifest = agg.checkpoint()
    ckpt.save_server(
        rnd, tree, extra={"args": vars(args), "aggregator": agg_manifest}
    )
    # every client's data cursor (unselected clients keep theirs unchanged;
    # saving all makes any round a complete resume point)
    for i in range(args.population):
        ckpt.save_client(rnd, i, streams[i].state_dict())


# args whose value changes the pure dispatch timeline, the data every client
# draws, or the optimizer/buffer semantics: an async resume with any of these
# altered would silently replay a DIFFERENT run ("--rounds" alone may change —
# extending the run is the point of resuming, though it re-derives the inner
# LR schedule's total_steps exactly as sync resume does)
_ASYNC_RESUME_ARGS = (
    "seed", "clients", "population", "local_steps", "batch", "buffer_size",
    "staleness_alpha", "max_staleness", "participation", "dirichlet_alpha",
    "dropout_rate", "straggler_profile", "deadline", "client_weighting",
    "uplink", "topk_fraction", "partial_progress", "fused_server",
    "arch", "reduced", "seq_len", "heterogeneous",
    "inner_lr", "outer", "outer_lr", "fedprox_mu",
    "dp_clip", "dp_noise", "pseudo_grad_dtype",
    "control", "control_target", "control_quantile", "control_gain",
    "control_window", "control_interval",
    "robust_agg", "trim_fraction", "clip_mult", "clip_norm",
    "screen", "screen_z", "screen_warmup",
    "rollback", "rollback_window", "rollback_factor", "quarantine_rounds",
    "byzantine_fraction", "byzantine_kind",
)

# flags with TRUTHY defaults that postdate older checkpoints: a checkpoint
# written before the flag existed behaved exactly like today's default, so
# only a non-default value conflicts (the falsy-default case is handled by
# the `not ours` skip below)
_RESUME_ARG_DEFAULTS = {
    "control": "static",
    "control_quantile": 0.9,
    "control_window": 4,
    "control_interval": 1,
    "robust_agg": "none",
    "trim_fraction": 0.1,
    "clip_mult": 3.0,
    "screen_z": 6.0,
    "screen_warmup": 8,
    "rollback_window": 8,
    "rollback_factor": 4.0,
    "quarantine_rounds": 4,
    "byzantine_kind": "scale",
}


def _run_worker(args, model, fed, pcfg, streams, codec=None) -> dict:
    """``--runtime sockets --role client``: one pure-compute worker process.

    It builds the SAME model/fed/participation configuration as the server (so
    both compile the same jitted client phase) but owns no federation state —
    every assignment ships the params snapshot, residual row, rng and the
    population client's data cursor (docs/runtime.md). The streams constructed
    here are cursor *receptacles*: the authoritative cursors live on the
    server and ride the wire.
    """
    if args.partial_progress:
        pcfg = dataclasses.replace(
            pcfg, partial_progress=True, local_steps=args.local_steps
        )
    tracer = _build_tracer(args, args.worker_id)
    worker = ClientWorker(
        lambda p, b: model.loss(p, b), fed, pcfg,
        streams=streams, batch_size=args.batch,
        host=args.host, port=args.port, codec=codec,
        name=args.worker_id, io_timeout=args.io_timeout,
        chaos=_chaos_from_args(args), tracer=tracer,
    )
    metrics_srv = _start_metrics(args, tracer)
    print(f"worker {args.worker_id} serving {args.host}:{args.port}")
    try:
        n = worker.run()
    finally:
        if metrics_srv is not None:
            metrics_srv.close()
        if tracer is not None:
            tracer.close()
    print(f"worker {args.worker_id} done after {n} assignments")
    return {"completed": n}


def _run_async(args, cfg, model, fed, pcfg, streams, val_stream, params, codec=None) -> dict:
    """Event-driven FedBuff-style training: K busy client slots, a server-side
    delta buffer, one outer update per ``--buffer-size`` admitted deltas.

    With ``codec``, completions upload encoded payloads (decoded at admission)
    and the driver owns one error-feedback residual row per population client.
    Every update checkpoints the aggregator's CANONICAL schema — buffer lanes,
    residual store, dispatch cursor, in-flight slot table and params snapshots
    — so ``--resume`` replays the pure-in-(cfg, seed, n) timeline from the
    checkpoint exactly: the resumed run is bitwise the uninterrupted one.
    """
    acfg = AsyncAggConfig(
        buffer_size=(
            args.buffer_size if args.buffer_size is not None
            else max(1, args.clients // 2)
        ),
        staleness_alpha=args.staleness_alpha,
        max_staleness=args.max_staleness,
    )
    if args.partial_progress:
        # the deadline becomes a per-dispatch budget: plan_round derives τ_i and
        # the aggregator admits partial deltas at the fractional τ_i/τ weight
        pcfg = dataclasses.replace(
            pcfg, partial_progress=True, local_steps=args.local_steps
        )
    controller = _build_controller(args, acfg=acfg)
    robust = _robust_from_args(args)

    def loss_fn(p, b):
        return model.loss(p, b)

    def make_batches(cid):
        b = round_batches([streams[cid]], args.local_steps, args.batch)
        return {k: jnp.asarray(v) for k, v in b.items()}

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    logger = MetricLogger(args.log) if args.log else None

    state = dispatch = None
    start_update = 0
    deltas_resumed = 0
    if args.resume:
        if ckpt is None:
            raise SystemExit("--resume with --aggregation async needs --ckpt-dir")
        latest = ckpt.latest_round()
        if latest is not None:
            manifest = ckpt.load_manifest(latest)
            extra = manifest.get("extra", {})
            dispatch = extra.get("aggregator")
            if not isinstance(dispatch, dict) or dispatch.get("kind") != "async":
                raise SystemExit(
                    f"--resume: checkpoint round {latest} carries no async "
                    f"aggregator manifest (written before the resumable schema, "
                    f"or by a sync run) — the in-flight dispatch queue cannot "
                    f"be replayed; start fresh"
                )
            ck_args = extra.get("args", {})
            for key in _ASYNC_RESUME_ARGS:
                ours = getattr(args, key)
                if key not in ck_args and (
                    not ours or ours == _RESUME_ARG_DEFAULTS.get(key)
                ):
                    # the flag postdates this checkpoint (e.g. --fused-server on
                    # a PR-4 checkpoint): the old run used today's default
                    # semantics, so only a non-default value conflicts
                    continue
                theirs = ck_args.get(key)
                if theirs is not None or ours is not None:
                    if ours != theirs:
                        raise SystemExit(
                            f"--resume: --{key.replace('_', '-')}={ours} does not "
                            f"match the checkpoint's {theirs} — the async "
                            f"timeline is pure in (config, seed), so resuming "
                            f"under a different configuration would silently "
                            f"replay a different run"
                        )
            controller = _restore_controller(controller, dispatch, latest)
            if controller is not None:
                # rebuild the async config at the controller's checkpointed
                # knob values: the buffer lanes in the npz have THAT shape,
                # and the resumed governor continues its trajectory from them
                knobs = controller.knobs()
                acfg = dataclasses.replace(
                    acfg,
                    staleness_alpha=float(knobs["staleness_alpha"]),
                    buffer_size=int(knobs["buffer_size"]),
                )
            like = AsyncBufferAggregator.checkpoint_template(
                fed, acfg, pcfg, params, codec,
                uplink_ids=dispatch.get("uplink_ids"),
            )
            state, _ = ckpt.load_server(latest, like)
            start_update = latest + 1
            deltas_resumed = int(extra.get("train", {}).get("deltas_admitted", 0))
            for i, s in enumerate(streams):
                try:
                    s.load_state_dict(ckpt.load_client(latest, i))
                except FileNotFoundError:
                    pass
            print(f"resumed async run from update {latest} "
                  f"(dispatch cursor {dispatch['cursor']}, "
                  f"sim_time {dispatch['sim_time']:.2f})")

    tracer = _build_tracer(args, "server")
    backend = None
    if args.runtime == "sockets":
        # the server owns every population client's data cursor: it ships the
        # cursor out with each assignment and commits the advanced cursor in
        # event order, so the checkpointed cursors stay consistent with the
        # dispatch manifest (any worker can then serve any client, and resume
        # recreates in-flight assignments with the cursor they shipped with)
        backend = SocketBackend(
            host=args.host, port=args.port,
            stream_states=[s.state_dict() for s in streams],
            lease_timeout=args.lease_timeout, io_timeout=args.io_timeout,
            chaos=_chaos_from_args(args), tracer=tracer,
        )
        print(f"server listening on {backend.host}:{backend.port}", flush=True)
        driver = FederationDriver(
            backend, fed, acfg, pcfg, flush_deadline=args.flush_deadline,
            seed=args.seed, params=params, rng=jax.random.PRNGKey(args.seed + 1),
            codec=codec, state=state, dispatch=dispatch, robust=robust,
            fused_server=args.fused_server, tracer=tracer, controller=controller,
        )
    else:
        driver = AsyncFederationDriver(
            loss_fn, fed, acfg, pcfg, make_batches,
            seed=args.seed, params=params, rng=jax.random.PRNGKey(args.seed + 1),
            codec=codec, state=state, dispatch=dispatch, robust=robust,
            fused_server=args.fused_server, tracer=tracer, controller=controller,
        )
        # the in-process attack simulator: deterministic Byzantine population
        # clients poison every delta they push (the robust-agg bench arms)
        driver.corrupt_fn = make_byzantine_fn(
            args.byzantine_fraction, args.byzantine_kind, args.population
        )
    metrics_srv = _start_metrics(
        args, tracer,
        # liveness + live control knobs (control_* gauges) from the backend
        extra=(backend.metrics_extras if backend is not None else None),
    )

    # reference: what the deadline-masking sync schedule pays to aggregate the
    # same number of client deltas (cached cumulative replay of plan_round)
    sync_cum = [(0.0, 0)]  # (cumulative sim time, cumulative aggregated deltas)

    def sync_equiv_time(n_deltas: int) -> float:
        while sync_cum[-1][1] < n_deltas and len(sync_cum) < 100_000:
            plan = plan_round(pcfg, args.seed, len(sync_cum) - 1)
            t, d = sync_cum[-1]
            sync_cum.append((t + plan.round_time, d + plan.effective_k))
        return sync_cum[-1][0] if sync_cum[-1][1] >= n_deltas else float("inf")

    history = []
    deltas_admitted = [deltas_resumed]
    t_wall = [time.perf_counter()]  # monotonic: row["seconds"] is a duration
    compiles = CompileCounter()
    seen_compiles = [0]

    def on_update(i, row):
        u = start_update + i  # absolute outer-update index across resumes
        # mean/max staleness + buffer occupancy come in-graph from flush_buffer;
        # the host side only adds the histogram buckets of the admitted ages
        staleness = row.pop("admitted_staleness", [])
        row.update(
            (k, v)
            for k, v in staleness_stats(staleness).items()
            if k.startswith("staleness_hist_")
        )
        deltas_admitted[0] += int(row.get("buffer_fill", 0))
        row.update(
            uplink_round_metrics(
                args.uplink, params, row.get("buffer_fill", 0.0),
                args.topk_fraction, codec=codec,
            )
        )
        row.update(
            update=u,
            round=u,  # outer-update index, the async analogue of the round
            deltas_admitted=float(deltas_admitted[0]),
            wallclock_speedup=wallclock_speedup(
                sync_equiv_time(deltas_admitted[0]), row["sim_time"]
            ),
            work_completed=driver.work_completed,
            work_wasted=driver.work_wasted,
            seconds=time.perf_counter() - t_wall[0],
            train_loss=row["train_loss_mean"],
            train_ppl=perplexity(row["train_loss_mean"]),
        )
        t_wall[0] = time.perf_counter()
        row["val_ppl"] = evaluate_perplexity(
            model, driver.state["params"], val_stream,
            batches=args.eval_batches, batch_size=args.batch,
        )
        # compilations since the previous update (first row: since start)
        row["compiles"] = compiles.count - seen_compiles[0]
        seen_compiles[0] = compiles.count
        history.append(row)
        print(
            f"update {u}: loss={row['train_loss_mean']:.4f} "
            f"val_ppl={row['val_ppl']:.2f} "
            f"pg_norm={row['pseudo_grad_norm']:.4f} "
            f"staleness={row['staleness_mean']:.2f}/{row['staleness_max']:.0f} "
            f"buf={row['buffer_fill']:.0f}/{driver.acfg.buffer_size} "
            f"t_sim={row['sim_time']:.2f} "
            f"speedup={row['wallclock_speedup']:.2f}x [{row['seconds']:.1f}s]"
        )
        knobs = {k[len("knob_"):]: v for k, v in row.items()
                 if k.startswith("knob_")}
        if knobs:
            print("  control: " + ", ".join(
                f"{k}={v:g}" for k, v in knobs.items()
            ))
        # divergence guard (async): a spiking flush norm rolls the server back
        # to the last good checkpointed update. Contributors are NOT
        # quarantined here — the flushed buffer mixes many senders and the
        # lanes are already drained; repeat offenders are the door screen's
        # job (docs/robustness.md)
        rs = driver.robust_state
        tripped = rolled_back = False
        if rs is not None and robust is not None and robust.rollback:
            row["rolled_back"] = 0.0
            tripped = rs.observe_update(row["pseudo_grad_norm"])
            if tripped:
                good = rs.last_good
                if good >= 0 and ckpt is not None:
                    like = {"params": driver.state["params"],
                            "outer": driver.state["outer"]}
                    restored, _ = ckpt.load_server(good, like)
                    driver.adopt_model(restored)
                    rs.note_rollback()
                    rolled_back = True
                    row["rolled_back"] = 1.0
                    if driver.tracer.enabled:
                        driver.tracer.point(
                            "rollback", round=u, restored_round=good,
                        )
                        driver.tracer.count("rollbacks")
                    print(
                        f"  ROLLBACK: flush norm tripped the divergence "
                        f"guard — restored update {good} (buffer drained)"
                    )
                else:
                    print(
                        "  divergence guard tripped but no good checkpoint "
                        "exists yet — continuing without rollback"
                    )
        if logger:
            logger.log(row)
        if ckpt:
            if rs is not None and (not tripped or rolled_back):
                # pre-checkpoint mark (same discipline as the sync path): the
                # manifest's last_good points at this update, valid exactly
                # when this checkpoint commits
                rs.mark_good(u)
            # the CANONICAL aggregator checkpoint: buffer lanes, the residual
            # store, the K in-flight params snapshots (state pytree) plus the
            # dispatch cursor / per-slot finish-time+version tags (manifest) —
            # everything `--resume` needs to replay the run bitwise
            tree, agg_manifest = driver.checkpoint()
            ckpt.save_server(
                u, tree,
                extra={"args": vars(args), "aggregator": agg_manifest,
                       "train": {"deltas_admitted": deltas_admitted[0]},
                       "sim_time": row["sim_time"]},
            )
            # the cursor source of truth differs by runtime: inproc mutates the
            # stream objects directly; sockets commits returned cursors into
            # the backend in event order
            cursors = (
                backend.snapshot_stream_states() if backend is not None
                else [streams[ci].state_dict() for ci in range(args.population)]
            )
            for ci, cur in enumerate(cursors):
                ckpt.save_client(u, ci, cur)

    try:
        if args.rounds > start_update:
            with compiles:
                driver.run_updates(
                    args.rounds - start_update, on_update=on_update
                )
        else:
            print(f"nothing to do: checkpoint already at update {start_update - 1} "
                  f"of {args.rounds}")
    finally:
        driver.finalize_trace()  # close in-flight dispatch spans (no-op untraced)
        if backend is not None:
            backend.close(linger=1.0)  # let workers pull the "done" answer
        if metrics_srv is not None:
            metrics_srv.close()
        if tracer is not None:
            tracer.close()
    return {"history": history, "state": driver.state, "model": model,
            "config": cfg, "driver": driver}


def main() -> None:
    enable_compile_cache()
    run(parse_args())


if __name__ == "__main__":
    main()
