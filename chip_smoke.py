#!/usr/bin/env python3
"""On-chip smoke test: the federated trainer at photon-125m's full width.

Drives the trainer's own entry point (``repro.launch.train.run``) on one TPU,
in this one process, at photon-125m's published width (12 layers, d_model
768, 12 heads, vocab 50368, S = 2048) with random weights from ``--seed``:

  a. device line: platform, device kind, device count, jax version. Anything
     but a TPU exits non-zero before any work.
  b. sync main path: 2 rounds of tau = 2 with 2 of 4 clients, checkpointed,
     then ``--resume`` for one more round. Every loss and perplexity must be
     finite, the resumed run must start from the saved round, and no round
     after a run's first may compile anything.
  c. fused server: one ``--fused-server`` round, then the fedcore Pallas
     ``server_apply`` and ``topk_mask_ef`` kernels, compiled (never
     interpreted), against the same functions with ``use_pallas=False`` on
     the same state and deltas.
  d. async path: ``--aggregation async`` for 4 admitted updates.

Each phase prints its wall time, compile count and seconds, and the device's
peak bytes in use and reserved. The last line of stdout is one JSON object naming the
device; it is printed only when every phase passed.

    python chip_smoke.py
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.fedcore import FusedTopKCodec, fused_apply_aggregate  # noqa: E402
from repro.launch.compile_env import CompileCounter, enable_compile_cache  # noqa: E402
from repro.launch.train import parse_args, run  # noqa: E402

# per-client batch 1: the compiler puts the sync round at 11.3 GB of
# temporaries (14.7 GB at batch 2, of 15.75 GB usable, beside the trainer's
# other live arrays) for one v5e chip
BATCH = 1
SMOKE_DIR = ROOT / ".smoke"


def _common(seed: int):
    return [
        "--arch", "photon-125m", "--seq-len", "2048", "--batch", str(BATCH),
        "--clients", "2", "--population", "4", "--local-steps", "2",
        "--eval-batches", "1", "--seed", str(seed),
    ]


def _peak_memory() -> str:
    stats = jax.devices()[0].memory_stats()
    return " ".join(f"{k}={stats.get(k)}"
                    for k in ("peak_bytes_in_use", "peak_bytes_reserved"))


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _finite_rows(history, keys=("train_loss", "val_ppl")) -> None:
    for row in history:
        for k in keys:
            _check(math.isfinite(row[k]), f"round {row['round']}: {k}={row[k]}")


@contextlib.contextmanager
def phase(name: str):
    """Times one phase and counts the compilations inside it."""
    print(f"--- phase {name}", flush=True)
    t0 = time.perf_counter()
    with CompileCounter() as compiles:
        yield
    print(
        f"phase {name}: wall_s={time.perf_counter() - t0:.3f} "
        f"compiles={compiles.count} compile_s={compiles.seconds:.3f} "
        f"{_peak_memory()}",
        flush=True,
    )


def phase_sync(seed: int) -> None:
    ckpt = SMOKE_DIR / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    base = _common(seed) + ["--ckpt-dir", str(ckpt)]
    first = run(parse_args(base + ["--rounds", "2"]))
    hist = first["history"]
    _check([r["round"] for r in hist] == [0, 1], f"rounds {hist}")
    _finite_rows(hist)
    saved_round = int(first["state"]["round"])
    del first  # free its device state before the resumed run
    resumed = run(parse_args(base + ["--rounds", "3", "--resume"]))
    rhist = resumed["history"]
    _check([r["round"] for r in rhist] == [2], "resume did not start at round 2")
    _check(saved_round == 2 and int(resumed["state"]["round"]) == 3,
           "resumed run did not continue from the saved round")
    _finite_rows(rhist)
    for r in hist + rhist:
        print(f"  round {r['round']}: train_loss={r['train_loss']!r} "
              f"val_ppl={r['val_ppl']!r} compiles={r['compiles']} "
              f"seconds={r['seconds']!r}")
    _check(hist[1]["compiles"] == 0,
           f"round 1 compiled {hist[1]['compiles']} programs")
    shutil.rmtree(ckpt, ignore_errors=True)


def phase_fused(seed: int) -> None:
    res = run(parse_args(_common(seed) + [
        "--rounds", "1", "--fused-server", "--outer", "fedadam",
        "--outer-lr", "0.05",
    ]))
    _finite_rows(res["history"])
    fed = res["aggregator"].fed
    state = res["state"]
    del res

    # same state, same deltas: compiled Pallas kernel vs the flat jnp chain
    # two clients' deltas sharing a common direction, so the consensus metric
    # (a difference of two sums over all 123.7M entries) is well conditioned
    leaves, treedef = jax.tree_util.tree_flatten(state["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), 2 * len(leaves))
    deltas = jax.tree_util.tree_unflatten(treedef, [
        1e-3 * (jax.random.normal(k0, x.shape, jnp.float32)[None]
                + jax.random.normal(k1, (2,) + x.shape, jnp.float32))
        for k0, k1, x in zip(keys[0::2], keys[1::2], leaves)
    ])
    weights = jnp.asarray([1.0, 2.0], jnp.float32)
    kernel = jax.jit(functools.partial(
        fused_apply_aggregate, fed, use_pallas=True, interpret=False))
    ref = jax.jit(functools.partial(fused_apply_aggregate, fed, use_pallas=False))
    lowered = kernel.lower(state, deltas, weights)
    _check("tpu_custom_call" in lowered.as_text(), "server_apply is not a TPU kernel")
    got_state, got_m = lowered.compile()(state, deltas, weights)
    want_state, want_m = ref(state, deltas, weights)
    worst = 0.0
    for name in ("params", "outer"):
        for g, w in zip(jax.tree_util.tree_leaves(got_state[name]),
                        jax.tree_util.tree_leaves(want_state[name])):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)
            worst = max(worst, float(np.max(np.abs(np.asarray(g) - np.asarray(w)))))
    for k in want_m:
        np.testing.assert_allclose(np.asarray(got_m[k]), np.asarray(want_m[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)
    print(f"  server_apply fedadam: max_abs_diff_state={worst!r} "
          f"pseudo_grad_norm={float(got_m['pseudo_grad_norm'])!r} "
          f"ref={float(want_m['pseudo_grad_norm'])!r}")
    del got_state, want_state

    delta0 = jax.tree_util.tree_map(lambda d: d[0], deltas)
    resid0 = jax.tree_util.tree_map(lambda d: 0.1 * d[1], deltas)
    del deltas
    codec = FusedTopKCodec(k_fraction=0.01, use_pallas=True, interpret=False)
    ref_codec = FusedTopKCodec(k_fraction=0.01, use_pallas=False)
    enc = jax.jit(codec.encode)
    lowered = enc.lower(delta0, resid0)
    _check("tpu_custom_call" in lowered.as_text(), "topk_mask_ef is not a TPU kernel")
    got = lowered.compile()(delta0, resid0)
    want = jax.jit(ref_codec.encode)(delta0, resid0)
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=0)
        worst = max(worst, float(np.max(np.abs(np.asarray(g) - np.asarray(w)))))
    print(f"  topk_mask_ef: max_abs_diff={worst!r}")


def phase_async(seed: int) -> None:
    res = run(parse_args(_common(seed) + [
        "--rounds", "4", "--aggregation", "async", "--buffer-size", "1",
    ]))
    hist = res["history"]
    _check(len(hist) == 4, f"{len(hist)} async updates, expected 4")
    _finite_rows(hist)
    for r in hist:
        print(f"  update {r['update']}: train_loss={r['train_loss']!r} "
              f"val_ppl={r['val_ppl']!r} compiles={r['compiles']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and deltas")
    args = ap.parse_args()

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{dev.platform}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    with phase("b_sync"):
        phase_sync(args.seed)
    with phase("c_fused"):
        phase_fused(args.seed)
    with phase("d_async"):
        phase_async(args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
