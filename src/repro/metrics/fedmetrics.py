"""Federated training monitors (§6.2): per-round norm tracking (the paper's divergence
leading-indicators), perplexity evaluation, and a lightweight CSV metric logger."""
from __future__ import annotations

import csv
import io
import math
import os
from typing import Dict, Iterable, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import STALENESS_BUCKETS


def perplexity(loss_ce: float) -> float:
    return float(math.exp(min(30.0, loss_ce)))


# ---------------------------------------------------------------------------
# Elastic-participation monitors (paper §7: partial participation / stragglers)
# ---------------------------------------------------------------------------


def effective_clients(weights) -> int:
    """K_eff: clients with nonzero aggregation weight this round."""
    return int(np.count_nonzero(np.asarray(weights)))


def weight_entropy(weights) -> float:
    """Shannon entropy (nats) of the normalized aggregation weights. log(K) means a
    perfectly balanced round; falling entropy flags domination by few clients (the
    data-size-skew failure mode of FedAvg weighting)."""
    w = np.asarray(weights, np.float64)
    w = w[w > 0]
    if w.size == 0:
        return 0.0
    p = w / w.sum()
    return float(-(p * np.log(p)).sum())


def participation_metrics(plan) -> Dict[str, float]:
    """Flatten a ``ParticipationPlan`` into the per-round logging row. Deliberately
    omits a ``weight_entropy`` key: the jitted round already reports the in-round
    value under that name, and a host-side copy would silently clobber it."""
    return {
        "effective_k": float(plan.effective_k),
        "straggler_count": float(plan.n_stragglers),
        "dropout_count": float(plan.n_dropped),
        "unavailable_count": float(np.asarray(plan.unavailable).sum()),
        "round_time_sim": float(plan.round_time),
    }


def partial_progress_metrics(plan, tau: int) -> Dict[str, float]:
    """Per-round straggler partial-progress monitors (core/aggregator weight
    policy): how much of the requested τ the cohort actually realized, and how
    much compute the deadline-cut baseline would have thrown away.

    - ``partial_tau_mean``: mean realized fraction τ_i/τ over the contributors
      (1.0 = nobody was slowed).
    - ``partial_full_fraction``: fraction of contributors that finished all τ
      steps.
    - ``partial_rescued_clients`` / ``partial_rescued_work``: the clients the
      deadline cut would have dropped entirely, and the client-rounds of
      compute (Σ τ_i/τ) their partial deltas salvage instead.
    - ``partial_wasted_work``: client-rounds still burned this round — clients
      too slow for even one step hold their slot until the deadline
      (deadline·speed ≈ the fraction of a full round they computed for
      nothing), plus the plain deadline-cut waste when partial progress is off.

    Returns ``{}``-compatible zeros when the plan carries no ``local_steps``
    (partial progress disabled), so the logging row stays schema-stable.
    """
    mask = np.asarray(plan.mask)
    speeds_all = np.asarray(plan.speeds, np.float64)
    if plan.local_steps is None:
        # deadline-cut baseline: a cut straggler ran until the deadline (≈ the
        # round time) and every one of those client-rounds was discarded
        cut = np.asarray(plan.stragglers)
        return {
            "partial_tau_mean": 1.0 if mask.any() else 0.0,
            "partial_full_fraction": 1.0 if mask.any() else 0.0,
            "partial_rescued_clients": 0.0,
            "partial_rescued_work": 0.0,
            "partial_wasted_work": float(
                np.minimum(1.0, plan.round_time * speeds_all[cut]).sum()
            ),
        }
    ls = np.asarray(plan.local_steps, np.float64)
    frac = ls[mask] / float(tau)
    rescued = mask & (ls < tau)  # clients the deadline cut would have dropped
    cut = np.asarray(plan.stragglers)  # still dropped: τ_i < 1
    wasted = float(np.minimum(1.0, plan.round_time * speeds_all[cut]).sum())
    return {
        "partial_tau_mean": float(frac.mean()) if mask.any() else 0.0,
        "partial_full_fraction": float((ls[mask] >= tau).mean()) if mask.any() else 0.0,
        "partial_rescued_clients": float(rescued.sum()),
        "partial_rescued_work": float((ls[rescued] / float(tau)).sum()),
        "partial_wasted_work": wasted,
    }


# ---------------------------------------------------------------------------
# Async-aggregation monitors (FedBuff-style buffer, core/async_agg.py)
# ---------------------------------------------------------------------------

def staleness_stats(staleness: Iterable[float]) -> Dict[str, float]:
    """Per-update staleness summary + histogram of the admitted deltas' ages.

    Buckets (``staleness_hist_*``): exactly-fresh (0), one round late (1), 2–3,
    4–7, and 8+ — a long right tail means the buffer is mostly absorbing ancient
    work and ``max_staleness`` / a larger cohort should be considered.
    """
    s = np.asarray(list(staleness), np.float64)
    out = {
        "staleness_mean": float(s.mean()) if s.size else 0.0,
        "staleness_max": float(s.max()) if s.size else 0.0,
    }
    for lo, hi in STALENESS_BUCKETS:
        if hi is None:
            out[f"staleness_hist_{lo}p"] = float((s >= lo).sum())
        elif lo == hi:
            out[f"staleness_hist_{lo}"] = float(((s >= lo) & (s <= hi)).sum())
        else:
            out[f"staleness_hist_{lo}_{hi}"] = float(((s >= lo) & (s <= hi)).sum())
    return out


def staleness_hist_counts(staleness: Iterable[float]) -> np.ndarray:
    """Per-bucket counts of admitted-delta staleness, aligned with
    ``STALENESS_BUCKETS`` (the same buckets ``staleness_stats`` logs and the
    Prometheus endpoint exports) — the cumulative-histogram input the control
    layer's staleness governor reads quantiles from."""
    s = np.asarray(list(staleness), np.float64)
    counts = []
    for lo, hi in STALENESS_BUCKETS:
        if hi is None:
            counts.append(float((s >= lo).sum()))
        else:
            counts.append(float(((s >= lo) & (s <= hi)).sum()))
    return np.asarray(counts, np.float64)


def histogram_quantile(counts, q: float) -> float:
    """Conservative quantile off the cumulative staleness histogram.

    Returns the UPPER edge of the first bucket whose cumulative count reaches
    ``q * total`` (ties included: a ``q`` landing exactly on a cumulative
    boundary resolves to that bucket). The open-ended last bucket has no finite
    upper edge and reports its LOWER edge instead; an empty histogram is 0.0.
    The possible return values are therefore exactly the bucket edges
    {0, 1, 3, 7, 8} — coarse on purpose: a governor stepping on bucket edges
    cannot chase sub-bucket noise.
    """
    c = np.asarray(counts, np.float64)
    if c.shape[0] != len(STALENESS_BUCKETS):
        raise ValueError(
            f"expected {len(STALENESS_BUCKETS)} bucket counts, got {c.shape[0]}"
        )
    total = float(c.sum())
    if total <= 0.0:
        return 0.0
    rank = float(q) * total
    cum = 0.0
    for (lo, hi), n in zip(STALENESS_BUCKETS, c):
        cum += float(n)
        if cum >= rank:
            return float(hi if hi is not None else lo)
    return float(STALENESS_BUCKETS[-1][0])  # pragma: no cover — q > 1 guard


def window_mean(rows, key: str, default: float = 0.0) -> float:
    """Mean of ``row[key]`` over the rows of a metrics window that carry the
    key; ``default`` when none do (empty window, or a metric the current
    configuration never emits). Non-finite values are skipped, not averaged:
    a single NaN round metric (a poisoned cohort before the screen engages)
    must not turn every downstream window statistic — and the control loop
    decisions made from them — into NaN forever."""
    vals = [
        float(r[key]) for r in rows
        if r.get(key) is not None and math.isfinite(float(r[key]))
    ]
    if not vals:
        return float(default)
    return float(sum(vals) / len(vals))


def window_concat(rows, key: str) -> List[float]:
    """Concatenate per-row LIST metrics (e.g. ``admitted_staleness``) across a
    metrics window; rows without the key contribute nothing, and non-finite
    elements are dropped (same NaN-propagation discipline as
    :func:`window_mean`)."""
    out: List[float] = []
    for r in rows:
        v = r.get(key)
        if v:
            out.extend(float(x) for x in v if math.isfinite(float(x)))
    return out


def wallclock_speedup(sync_time: float, async_time: float) -> float:
    """Simulated wall-clock speedup of reaching the same point: how much longer
    the deadline-masking sync schedule would have taken than the async buffered
    schedule (> 1.0 means async wins)."""
    return float(sync_time) / max(float(async_time), 1e-12)


# ---------------------------------------------------------------------------
# Compressed-uplink monitors (core/compression.py codecs)
# ---------------------------------------------------------------------------


def uplink_round_metrics(
    scheme: str, params_like, n_uploads: float, topk_fraction: float = 0.05,
    codec=None,
) -> Dict[str, float]:
    """Per-round uplink cost row: bytes one client sends under ``scheme``, bytes
    the whole round's ``n_uploads`` uploads cost, and the compression ratio vs
    the uncompressed float32 uplink. Uses the analytic accounting from
    ``uplink_bytes``, which the tier-1 tests pin to real encoded payload sizes.

    Pass the run's live ``codec`` when one exists: a codec may override its
    wire accounting (the fused flat top-k prices ONE global kept-entry budget,
    not per-leaf budgets), and the logged bytes must match what that codec
    actually ships — not what the scheme name alone would suggest."""
    from repro.core.compression import uplink_bytes

    per_client = (
        float(codec.nbytes(params_like)) if codec is not None
        else uplink_bytes(params_like, scheme, topk_fraction)
    )
    f32 = uplink_bytes(params_like, "float32")
    return {
        "uplink_bytes_per_client": float(per_client),
        "uplink_bytes_round": float(per_client) * float(n_uploads),
        "uplink_compression_ratio": float(f32) / max(float(per_client), 1e-12),
    }


def evaluate_perplexity(model, params, stream, batches: int = 4, batch_size: int = 4) -> float:
    """Held-out perplexity on a validation stream (server-side evaluation, §4.2)."""
    total, n = 0.0, 0
    for _ in range(batches):
        tokens = jnp.asarray(stream.next_batch(batch_size))
        total += float(model.eval_ce(params, {"tokens": tokens}))
        n += 1
    return perplexity(total / n)


def activation_l2_probe(model, params, batch) -> float:
    """L2 norm of output logits activations — the divergence leading indicator the
    paper tracks (Fig 5)."""
    logits, _, _ = model.jit_forward(params, batch)
    return float(jnp.sqrt(jnp.mean(jnp.square(logits.astype(jnp.float32)))))


class MetricLogger:
    """Append-only CSV logger, one row per round/step.

    Schema growth is handled, not swallowed: the first ``log`` fixes the
    header, and a later row introducing NEW keys (e.g. ``val_ppl`` appearing
    only on eval rounds) atomically rewrites the file with the widened header
    — earlier rows pad the new columns with ``""``. The old behaviour
    (``extrasaction="ignore"``) silently discarded such keys forever;
    ``extrasaction="raise"`` now backstops the union logic so a dropped field
    can only ever be a loud error, never lost data.
    """

    def __init__(self, path: str, fieldnames: Optional[List[str]] = None):
        self.path = path
        self.fieldnames = list(fieldnames) if fieldnames else None
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._initialized = os.path.exists(path)
        if self._initialized:
            # resuming into an existing file: adopt (and union with) its header
            with open(self.path, newline="") as f:
                existing = next(csv.reader(f), None)
            if existing:
                merged = list(existing)
                merged += [c for c in (self.fieldnames or []) if c not in merged]
                self.fieldnames = merged

    def _grow_schema(self, new_keys: List[str]) -> None:
        """Widen the header in place: atomic whole-file rewrite (checkpoint
        module's tmp+fsync+replace pattern), old rows padded with ''."""
        from repro.checkpoint.checkpoint import _atomic_write

        old_rows = self.read() if self._initialized else []
        self.fieldnames = list(self.fieldnames or []) + list(new_keys)

        buf = io.StringIO(newline="")
        w = csv.DictWriter(
            buf, fieldnames=self.fieldnames, extrasaction="raise", restval=""
        )
        w.writeheader()
        for r in old_rows:
            w.writerow(r)
        _atomic_write(self.path, lambda f: f.write(buf.getvalue().encode("utf-8")))
        self._initialized = True

    def log(self, row: Dict) -> None:
        row = {k: (float(v) if hasattr(v, "item") or isinstance(v, (int, float)) else v)
               for k, v in row.items()}
        if self.fieldnames is None:
            self.fieldnames = list(row.keys())
        new_keys = [k for k in row if k not in self.fieldnames]
        if new_keys and self._initialized:
            self._grow_schema(new_keys)
        elif new_keys:
            self.fieldnames += new_keys
        write_header = not self._initialized
        with open(self.path, "a", newline="") as f:
            w = csv.DictWriter(
                f, fieldnames=self.fieldnames, extrasaction="raise", restval=""
            )
            if write_header:
                w.writeheader()
            w.writerow(row)
        self._initialized = True

    def read(self) -> List[Dict]:
        with open(self.path) as f:
            return list(csv.DictReader(f))
