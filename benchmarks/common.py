"""Shared helpers for the paper-table benchmarks."""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import jax

from repro.configs import get_config
from repro.launch.train import parse_args, run

BASE_ARGS = [
    "--arch", "photon-75m", "--reduced", "--seq-len", "64", "--batch", "2",
    "--eval-batches", "2",
]


def tiny_cfg(d_model: int = 128, n_layers: int = 2, vocab: int = 512):
    cfg = get_config("photon-75m").reduced()
    return dataclasses.replace(
        cfg,
        name=f"photon-tiny-{d_model}",
        d_model=d_model,
        n_layers=n_layers,
        n_heads=max(2, d_model // 64),
        n_kv_heads=max(2, d_model // 64),
        d_ff=4 * d_model,
        vocab_size=vocab,
    )


def run_fed(
    *,
    cfg=None,
    rounds: int = 6,
    tau: int = 8,
    clients: int = 4,
    population: Optional[int] = None,
    heterogeneous: bool = False,
    outer: str = "fedavg",
    outer_lr: float = 1.0,
    keep_opt: bool = False,
    inner_lr: float = 1e-3,
    seed: int = 0,
    extra: Optional[List[str]] = None,
):
    argv = BASE_ARGS + [
        "--rounds", str(rounds), "--local-steps", str(tau), "--clients", str(clients),
        "--population", str(population or clients), "--outer", outer,
        "--outer-lr", str(outer_lr), "--inner-lr", str(inner_lr), "--seed", str(seed),
    ]
    if heterogeneous:
        argv.append("--heterogeneous")
    if keep_opt:
        argv.append("--keep-opt")
    argv += extra or []
    t0 = time.time()
    out = run(parse_args(argv), cfg=cfg)
    out["seconds"] = time.time() - t0
    return out


def run_centralized(*, cfg=None, steps: int = 48, batch: int = 8, inner_lr: float = 1e-3,
                    seed: int = 0, seq_len: int = 64):
    """Centralized baseline: same total tokens as a federated run with the same
    steps x batch, synchronizing every step."""
    import jax
    import jax.numpy as jnp

    from repro.core import InnerOptConfig, centralized_step, init_centralized_state
    from repro.data import build_client_streams, validation_stream
    from repro.metrics import evaluate_perplexity
    from repro.models import build_model

    cfg = cfg or tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    inner = InnerOptConfig(lr_max=inner_lr, warmup_steps=max(1, steps // 20),
                           total_steps=steps)
    state = init_centralized_state(inner, params)
    stream = build_client_streams(1, seq_len, cfg.vocab_size, heterogeneous=False)[0]
    loss_fn = lambda p, b: model.loss(p, b)
    step_fn = jax.jit(lambda s, b: centralized_step(loss_fn, inner, s, b))
    losses = []
    for _ in range(steps):
        batch_np = stream.next_batch(batch)
        state, m = step_fn(state, {"tokens": jnp.asarray(batch_np)})
        losses.append(float(m["ce"]))
    val = validation_stream(seq_len, cfg.vocab_size, False)
    ppl = evaluate_perplexity(model, state["params"], val, batches=2, batch_size=batch)
    return {"losses": losses, "val_ppl": ppl, "state": state, "model": model}


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


# --- memory measurement (used by bench_population_scale; available to all) ---


def rss_bytes() -> int:
    """CURRENT resident set size of this process in bytes (``VmRSS``).

    Unlike ``ru_maxrss`` (a monotonic high-water mark — useless for comparing
    phases within one process), VmRSS can go down, so sampling it around a
    phase measures THAT phase. Falls back to ru_maxrss where /proc is absent.
    """
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    import resource

    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024


def live_device_bytes() -> int:
    """Bytes held by live JAX device arrays (on the CPU backend this is the
    host-side arena the federation state actually occupies)."""
    import numpy as np

    total = 0
    for a in jax.live_arrays():
        try:
            total += int(np.prod(a.shape)) * a.dtype.itemsize
        except Exception:
            pass
    return total


def tree_nbytes(tree) -> int:
    """Exact bytes of a pytree of arrays/ShapeDtypeStructs (no allocation)."""
    import numpy as np

    return sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
    )


class PeakRss:
    """Context manager sampling VmRSS on a background thread; ``.peak`` is the
    max observed during the ``with`` block (bytes). Sampling at ~50 Hz catches
    transient buffers a before/after pair would miss."""

    def __init__(self, interval_s: float = 0.02):
        self.interval_s = interval_s
        self.peak = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            self.peak = max(self.peak, rss_bytes())
            self._stop.wait(self.interval_s)

    def __enter__(self) -> "PeakRss":
        self.peak = rss_bytes()
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
        self.peak = max(self.peak, rss_bytes())
