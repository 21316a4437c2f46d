"""Correctness of synchronous FedAvg rounds against the plain float32 reference.

The program's own aggregator object runs the set-up rounds and then the
window; nothing is built twice. While the set-up rounds are logged this check
reads from that object:

- each set-up round's mean training loss (the history row's ``train_loss_mean``);
- after round 0, the norm of each weight leaf's pseudo-gradient as the outer
  optimizer took it, worked out from the weights: ``‖θ0 − θ1‖ / η_s``;
- after the last set-up round n, each leaf's change ``‖θn − θ0‖``.

It also keeps the round's inputs: each set-up round's token batches and
aggregation weights. Once the window has closed and the program's state is
freed, ``reference/`` replays the same rounds from weights it draws itself
from the seed. Three numbers can be compared, each steady from seed to seed:

- ``first_loss_gap``: |program − reference| on the first round's mean loss;
- ``pg_gap``: over the weight leaves, the largest gap between the program's
  pseudo-gradient norm and the reference's, over the larger of the
  reference's norm of that leaf and its median leaf norm;
- ``median_change_gap``: the same gap for each leaf's change after the last
  set-up round, its median over the leaves. A leaf whose reference
  pseudo-gradient is under a thousandth of the median leaf's is left out
  (round-off alone moves such a leaf under Adam).

The later rounds' losses and the worst leaf's change are not compared: from
the second round on, bfloat16 and float32 runs of the same rounds drift apart
by an amount that swings from seed to seed (the reference computed in
bfloat16 drifts as far), so no limit separates a sound run from the control
there. The cell's workload file names the numbers compared and their limits.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from reference import fedavg as ref_fedavg  # noqa: E402
from reference import model as ref_model  # noqa: E402

NEAR_ZERO = 1e-3  # a leaf whose pseudo-gradient is under this share of the median's


@jax.jit
def _leaf_gaps(a, b):
    """Per-leaf ‖a − b‖ in float32, keyed by the leaf's path."""
    flat = jax.tree_util.tree_flatten_with_path(a)[0]
    other = jax.tree_util.tree_leaves(b)
    return {
        jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32))))
        for (p, x), y in zip(flat, other)
    }


def _floats(tree) -> dict:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def leaf_gaps(prog: dict, ref: dict, leaves=None) -> list:
    """Per leaf, |‖prog‖ − ‖ref‖| / max(‖ref‖, median leaf ‖ref‖)."""
    if set(prog) != set(ref):
        raise ValueError(f"weight leaves differ: {sorted(set(prog) ^ set(ref))}")
    median = float(np.median(list(ref.values())))
    keys = sorted(ref) if leaves is None else leaves
    return [abs(prog[k] - ref[k]) / max(ref[k], median) for k in keys]


class Readings:
    """What one side (program, reference, control or a fault) gives."""

    def __init__(self, losses, pg, change):
        self.losses = list(losses)
        self.pg = dict(pg)
        self.change = dict(change)


class Check:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.seed = int(seed)
        self.rounds = cell.setup_rounds
        cfg = cell.config
        self.ref_cfg = {k: cfg[k] for k in (
            "n_layers", "d_model", "n_heads", "d_ff", "vocab_size", "padded_vocab",
            "layernorm_eps")}
        self.ref_cfg["z_loss"] = cfg["model"]["z_loss"]
        flags = cell.flags()
        tau, job_rounds = int(flags["--local-steps"]), int(flags["--rounds"])
        inner = dict(cfg["inner_optimizer"])
        inner["total_steps"] = job_rounds * tau
        inner["warmup_steps"] = max(1, job_rounds * tau // inner.pop("warmup_divisor"))
        self.recipe = {"inner": inner, "outer": cfg["outer_optimizer"]}
        self.limits = cell.workload["limits"]
        self.agg = None
        self.initial = None
        self.inputs = []  # per set-up round: (tokens (τ, C, B, S) int32, weights (C,))
        self.losses, self.pg, self.change = [], None, None
        self.reference = None  # the float32 reference's Readings, once verified

    # --- while the program runs ------------------------------------------
    def install(self, stack, train) -> None:
        from harness import patched

        check = self
        base = train.SyncAggregator

        class Followed(base):
            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                check.agg = self
                check.initial = kwargs["params"]

            def run_round(self, batches, plan):
                if len(check.inputs) < check.rounds:
                    check.inputs.append((
                        np.asarray(batches["tokens"]),
                        np.asarray(self.round_weights(plan), np.float64),
                    ))
                return super().run_round(batches, plan)

        stack.enter_context(patched(train, "SyncAggregator", Followed))

    def on_setup_row(self, i: int, row: dict) -> None:
        self.losses.append(float(row["train_loss_mean"]))
        params = self.agg.state["params"]
        if i == 0:
            lr = float(self.recipe["outer"]["lr"])
            self.pg = {k: v / lr for k, v in _floats(_leaf_gaps(self.initial, params)).items()}
        if i == self.rounds - 1:
            self.change = _floats(_leaf_gaps(params, self.initial))

    def release_program(self) -> None:
        """Drop every reference to the program's arrays before the reference runs."""
        self.agg = None
        self.initial = None

    # --- after the window --------------------------------------------------
    def program_readings(self) -> Readings:
        return Readings(self.losses, self.pg, self.change)

    def reference_readings(self, precision: str = "f32", fault=None) -> Readings:
        """The reference over the set-up rounds' own inputs; with
        ``precision="fp8"`` the control, with ``"bf16"`` the same rounds in
        the configuration's own matmul precision, with a ``fault`` a planted
        fault."""
        w0 = ref_model.init_weights(self.ref_cfg, self.seed)
        w, losses, pg = w0, [], None
        lr = float(self.recipe["outer"]["lr"])
        with jax.default_matmul_precision("highest"):
            for r, (tokens, weights) in enumerate(self.inputs):
                w, loss = ref_fedavg.run_round(
                    self.ref_cfg, self.recipe, w, r, tokens, weights,
                    precision=precision, fault=fault,
                )
                losses.append(loss)
                if r == 0:
                    pg = {k: v / lr for k, v in _floats(_leaf_gaps(w0, w)).items()}
            change = _floats(_leaf_gaps(w, w0))
        return Readings(losses, pg, change)

    @staticmethod
    def compare(got: Readings, ref: Readings) -> dict:
        median = float(np.median(list(ref.pg.values())))
        moving = [k for k in sorted(ref.pg) if ref.pg[k] >= NEAR_ZERO * median]
        return {
            "first_loss_gap": abs(got.losses[0] - ref.losses[0]),
            "pg_gap": max(leaf_gaps(got.pg, ref.pg)),
            "median_change_gap": float(np.median(leaf_gaps(got.change, ref.change, moving))),
        }

    def verify(self):
        if len(self.inputs) != self.rounds or self.change is None:
            raise RuntimeError("the set-up rounds were not all observed")
        self.reference = self.reference_readings()
        gaps = self.compare(self.program_readings(), self.reference)
        checks = {k: {"value": gaps[k], "limit": limit} for k, limit in self.limits.items()}
        correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                      for c in checks.values())
        return correct, checks
