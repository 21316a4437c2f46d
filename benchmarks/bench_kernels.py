"""Kernel microbenchmarks: wall time of the jnp reference paths on CPU (the Pallas
kernels target TPU; interpret-mode timing is not meaningful, so the reference path —
and for fedcore, the identical-math flat-buffer XLA chain — is what gets timed) +
analytic FLOP/byte intensity per kernel.

The ``fedcore`` arm additionally writes ``BENCH_fedkernels.json``: server-apply and
codec-encode wall times at 0.25–8M-param scale for C∈{4,16}, plus the analytic
bytes-moved roofline comparison (the fused single-pass layout must move ≥2x fewer
HBM bytes than the per-leaf multi-pass reference chain — the asserted acceptance;
CPU wall time is recorded honestly but only guarded against pathological
regression, since at these sizes the flat pack's concatenate puts the two paths
at parity-within-noise on a compute-cache-bound CPU)."""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.ref import decode_attention_ref
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.ref import ssd_ref
from benchmarks.common import emit

FEDKERNELS_JSON = "BENCH_fedkernels.json"


def _time(fn, *args, iters=3, warmup=1):
    """Mean wall µs per call. The warmup iterations run (and block) BEFORE the
    clock starts, so first-call jit compilation and lazy allocation can never
    pollute the reported time; the timed loop blocks once on the final value
    (async dispatch amortizes across iterations, as in production)."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _xla_bytes_accessed(jitted, *args):
    """XLA's measured 'bytes accessed' for the compiled computation on this
    host — implementation-sensitive (it reflects what the lowering actually
    materializes), unlike the analytic roofline model. None if unavailable."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        b = cost.get("bytes accessed")
        return float(b) if b is not None else None
    except Exception:
        return None


def _fed_tree(n: int, n_leaves: int, key) -> dict:
    """A synthetic params-shaped pytree of ~n total elements across n_leaves
    tensors (uneven sizes, so the per-leaf ref chain pays its real traversal
    cost)."""
    sizes = [max(1, n // n_leaves + (i % 3 - 1) * (n // (8 * n_leaves))) for i in range(n_leaves)]
    sizes[-1] = max(1, n - sum(sizes[:-1]))
    keys = jax.random.split(key, n_leaves)
    return {f"p{i}": jax.random.normal(k, (s,), jnp.float32) for i, (k, s) in enumerate(zip(keys, sizes))}


def _bench_fedcore(quick: bool) -> None:
    """Server-apply + codec-encode: the per-leaf jnp reference chain vs the
    flat-buffer fused layout (on CPU the fused math runs as one XLA-fused flat
    chain — the Pallas kernel computes the same formulas per block on TPU).

    Scales are capped for CI wall time: 0.25M (quick) / 1M and 8M (full)
    params; the layout is size-independent, so the bytes-moved ratios asserted
    here hold identically at the 100M+ TPU scale the kernel targets.
    """
    import functools

    from repro.core import (
        FederatedConfig,
        OuterOptConfig,
        TopKCodec,
        apply_aggregate,
        init_federated_state,
        uplink_bytes,
    )
    from repro.kernels.fedcore import (
        FusedTopKCodec,
        fused_apply_aggregate,
        server_apply_bytes,
        topk_encode_bytes,
    )

    cases = (
        [(1 << 18, 4)] if quick else [(1 << 20, 4), (1 << 20, 16), (1 << 23, 4)]
    )
    n_leaves = 24
    rows: dict = {"server_apply": [], "codec_encode": []}
    for n, c in cases:
        params = _fed_tree(n, n_leaves, jax.random.PRNGKey(0))
        n_real = sum(x.size for x in jax.tree_util.tree_leaves(params))
        fed = FederatedConfig(
            clients_per_round=c, local_steps=1,
            outer=OuterOptConfig(name="fedadam", lr=0.1),
        )
        state = init_federated_state(fed, params, jax.random.PRNGKey(1))
        deltas = jax.tree_util.tree_map(
            lambda p: jax.random.normal(jax.random.PRNGKey(2), (c,) + p.shape), params
        )
        w = jnp.linspace(0.5, 2.0, c)
        ref_fn = jax.jit(lambda s, d, ww: apply_aggregate(fed, s, d, client_weights=ww))
        fus_fn = jax.jit(
            lambda s, d, ww: fused_apply_aggregate(
                fed, s, d, client_weights=ww, use_pallas=False
            )
        )
        # min over repeats: robust to CI-runner load spikes, which would
        # otherwise make the no-slower assertion below flaky
        ref_us = min(_time(ref_fn, state, deltas, w, iters=5, warmup=2) for _ in range(3))
        fus_us = min(_time(fus_fn, state, deltas, w, iters=5, warmup=2) for _ in range(3))
        ref_b = server_apply_bytes(n_real, c, "fedadam")
        fus_b = server_apply_bytes(n_real, c, "fedadam", fused=True)
        rows["server_apply"].append({
            "n_params": n_real, "clients": c, "outer": "fedadam",
            "ref_us": ref_us, "fused_us": fus_us,
            # analytic roofline of the KERNEL SWEEP vs the per-leaf chain —
            # the single-pass property of the (C, N) layout
            "ref_bytes_moved": ref_b, "fused_bytes_moved": fus_b,
            "bytes_ratio": ref_b / fus_b,
            # XLA-measured bytes of this host's CPU lowering. The fused number
            # INCLUDES the per-call flat pack/unpack layout conversion (~CN of
            # extra traffic the resident-flat TPU layout amortizes), so it is
            # expected to exceed the ref here — recorded so the trade-off is
            # visible, never asserted as a win
            "ref_xla_cpu_bytes_accessed": _xla_bytes_accessed(ref_fn, state, deltas, w),
            "fused_xla_cpu_bytes_accessed": _xla_bytes_accessed(fus_fn, state, deltas, w),
        })
        emit(
            f"fedcore/server_apply_n{n_real}_c{c}", fus_us,
            f"ref={ref_us:.0f}us speedup={ref_us / max(fus_us, 1e-9):.2f}x "
            f"bytes {ref_b:.3e}->{fus_b:.3e} ({ref_b / fus_b:.2f}x fewer)",
        )

        delta1 = jax.tree_util.tree_map(lambda d: d[0], deltas)
        ref_c = TopKCodec(k_fraction=0.05)
        fus_c = FusedTopKCodec(k_fraction=0.05)
        res = ref_c.init_residual(delta1)
        ref_enc = jax.jit(lambda d, e: ref_c.encode(d, e))
        fus_enc = jax.jit(lambda d, e: fus_c.encode(d, e))
        ref_eus = _time(ref_enc, delta1, res, iters=5, warmup=2)
        fus_eus = _time(fus_enc, delta1, res, iters=5, warmup=2)
        rows["codec_encode"].append({
            "n_params": n_real, "codec": "topk@5%",
            "ref_us": ref_eus, "fused_us": fus_eus,
            "ref_bytes_moved": topk_encode_bytes(n_real),
            "fused_bytes_moved": topk_encode_bytes(n_real, fused=True),
            "wire_bytes_ref": uplink_bytes(params, "topk", 0.05),
            "wire_bytes_fused": fus_c.nbytes(params),
        })
        emit(
            f"fedcore/topk_encode_n{n_real}", fus_eus,
            f"ref={ref_eus:.0f}us speedup={ref_eus / max(fus_eus, 1e-9):.2f}x "
            f"wire={fus_c.nbytes(params):.3e}B",
        )

    # acceptance: the fused layout must move >=2x fewer bytes per round than
    # the ref multi-pass chain, and must not be slower where both are timeable
    speedup_min = min(
        r["ref_us"] / max(r["fused_us"], 1e-9) for r in rows["server_apply"]
    )
    rows["summary"] = {
        "server_apply_bytes_ratio_min": min(
            r["bytes_ratio"] for r in rows["server_apply"]
        ),
        "server_apply_speedup_min": speedup_min,
    }
    with open(FEDKERNELS_JSON, "w") as f:
        json.dump(rows, f, indent=2)
    # CPU wall time at quick sizes is parity-within-noise (the flat pack's
    # concatenate offsets the fusion win that HBM-bound TPU execution banks),
    # so the timing assertion is only a pathology guard; the stable, layout-
    # intrinsic acceptance is the bytes-moved roofline.
    for r in rows["server_apply"]:
        assert r["bytes_ratio"] >= 2.0, r
        assert r["fused_us"] <= r["ref_us"] * 2.0, (
            f"fused server apply pathologically slower than ref: {r}"
        )
    emit(
        "fedcore/acceptance", 0.0,
        f"bytes_ratio_min={rows['summary']['server_apply_bytes_ratio_min']:.2f}>=2 "
        f"server_apply_speedup_min={speedup_min:.2f}x",
    )


def main(quick: bool = False) -> None:
    _bench_fedcore(quick)
    B, H, S, hd = 1, 4, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, hd), jnp.float32)

    f = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    us = _time(f, q, k, v)
    flops = 4 * B * H * S * S * hd
    emit("kernels/flash_attention_ref", us, f"flops={flops:.2e} achieved={flops/us*1e6/1e9:.1f}GFLOP/s")

    qd = q[:, :, :1].reshape(B, H, hd)
    fd = jax.jit(lambda q, k, v: decode_attention_ref(q, k, v, jnp.int32(S)))
    us = _time(fd, qd, k, v)
    byts = 2 * B * H * S * hd * 4
    emit("kernels/flash_decode_ref", us, f"kv_bytes={byts:.2e} bw={byts/us*1e6/1e9:.1f}GB/s")

    nh, ds, chunk = 4, 32, 64
    x = jax.random.normal(ks[3], (B, S, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[1], (nh,)) * 0.5)
    Bm = jax.random.normal(ks[2], (B, S, 1, ds), jnp.float32)
    Cm = jax.random.normal(ks[3], (B, S, 1, ds), jnp.float32)
    fs = jax.jit(lambda *a: ssd_ref(*a, chunk)[0])
    us = _time(fs, x, dt, A, Bm, Cm)
    ssd_flops = 2 * B * S * nh * hd * (chunk + 2 * ds)
    emit("kernels/ssd_scan_ref", us, f"flops~{ssd_flops:.2e} chunk={chunk}")

    xr = jax.random.normal(ks[0], (4096, 1024), jnp.float32)
    sc = jnp.ones((1024,))
    fr = jax.jit(rmsnorm_ref)
    us = _time(fr, xr, sc)
    rb = 2 * xr.size * 4
    emit("kernels/rmsnorm_ref", us, f"bytes={rb:.2e} bw={rb/us*1e6/1e9:.1f}GB/s")


if __name__ == "__main__":
    main()
