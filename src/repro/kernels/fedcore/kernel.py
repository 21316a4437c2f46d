"""Fused Pallas TPU kernels for the federation hot loop (server side + uplink codecs).

The federated round is dominated by params-sized elementwise passes: the server's
weighted-mean-over-clients → DP noise → outer-optimizer chain re-reads the (C, N)
delta buffer and the params-sized optimizer lanes once per op when written as
per-leaf jnp (Photon, arXiv 2411.02908, names aggregation throughput as the
billion-parameter scaling bottleneck). These kernels operate on the *flat-buffer*
layout built by ``ops.pack_leaves``: every pytree leaf of one dtype concatenated
into a single contiguous 1D view, so one grid sweep touches each byte exactly once.

  - :func:`server_apply` — weighted mean over the client axis + optional DP noise
    + FedAvg/FedMom(Nesterov)/FedAdam outer update, fused into ONE pass: per grid
    block it reads the (C, bn) delta tile, the params tile and the optimizer-lane
    tiles, and writes the updated params/lanes. The aggregation metrics the jnp
    path derives from extra passes (per-client delta norms, pseudo-gradient norm,
    new model norm) are accumulated IN-KERNEL into tiny revisited output blocks —
    the grid dimension is declared "arbitrary" (sequential), which is what makes
    the accumulator pattern race-free on TPU.
  - :func:`topk_mask_ef` — the top-k codec's mask + select + error-feedback
    residual update in one pass (the threshold itself comes from ``lax.top_k``,
    the one genuinely non-streaming step).
  - :func:`sr_bf16` — bit-level stochastic-round-to-bf16 given pre-drawn uint32
    noise (bitwise-identical to ``compression.cast_compress``'s rounding).
  - :func:`int8_quant` / :func:`int8_dequant` — per-tensor symmetric int8.

All kernels run under ``interpret=True`` on CPU hosts — that is how the tier-1
parity tests execute them; the compiled path targets TPU. The jnp reference
semantics live in ``core/federated.apply_aggregate`` / ``core/compression`` (see
``ref.py``).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compiler_params(interpret: bool, semantics: Tuple[str, ...]):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics)


# ---------------------------------------------------------------------------
# Fused server apply: weighted mean + DP noise + outer update, one (C, N) pass
# ---------------------------------------------------------------------------


def _server_apply_kernel(
    *refs,
    opt: str,
    lr: float,
    momentum: float,
    nesterov: bool,
    beta2: float,
    eps: float,
    n_lanes: int,
    has_noise: bool,
    has_bias_corr: bool,
):
    """One grid block: refs are
    [wn (C,1), (b1c (1,1), b2c (1,1))?, deltas (C,bn), params (bn,), lanes*,
     noise (bn,)?] then outputs
    [new_params (bn,), new_lanes*, pg_sq (1,1), newp_sq (1,1), delta_sq (C,1)].
    """
    it = iter(refs)
    wn_ref = next(it)
    if has_bias_corr:
        b1c_ref, b2c_ref = next(it), next(it)
    d_ref = next(it)
    p_ref = next(it)
    lane_refs = [next(it) for _ in range(n_lanes)]
    noise_ref = next(it) if has_noise else None
    o_p_ref = next(it)
    o_lane_refs = [next(it) for _ in range(n_lanes)]
    pg_sq_ref = next(it)
    np_sq_ref = next(it)
    dsq_ref = next(it)

    i = pl.program_id(0)
    d = d_ref[...].astype(jnp.float32)  # (C, bn)
    wn = wn_ref[...].astype(jnp.float32)  # (C, 1), already w/Σw
    pg = jnp.sum(d * wn, axis=0)  # the ONE client-axis reduction
    if has_noise:
        pg = pg + noise_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)

    if opt == "fedavg":
        new_p = p - lr * pg
        new_lanes = []
    elif opt == "fedmom":
        m = lane_refs[0][...].astype(jnp.float32)
        new_m = momentum * m + pg
        upd = momentum * new_m + pg if nesterov else new_m
        new_p = p - lr * upd
        new_lanes = [new_m]
    elif opt == "fedadam":
        m = lane_refs[0][...].astype(jnp.float32)
        v = lane_refs[1][...].astype(jnp.float32)
        b1c = b1c_ref[0, 0]
        b2c = b2c_ref[0, 0]
        new_m = momentum * m + (1.0 - momentum) * pg
        new_v = beta2 * v + (1.0 - beta2) * jnp.square(pg)
        new_p = p - lr * (new_m / b1c) / (jnp.sqrt(new_v / b2c) + eps)
        new_lanes = [new_m, new_v]
    else:  # pragma: no cover — builder validates
        raise ValueError(opt)

    new_p_cast = new_p.astype(o_p_ref.dtype)
    o_p_ref[...] = new_p_cast
    for lane, o_ref in zip(new_lanes, o_lane_refs):
        o_ref[...] = lane.astype(o_ref.dtype)

    # the accumulators are stored as whole (1,1)/(C,1) blocks: the TPU compiler
    # refuses scalar stores into VMEM
    @pl.when(i == 0)
    def _():
        pg_sq_ref[...] = jnp.zeros_like(pg_sq_ref)
        np_sq_ref[...] = jnp.zeros_like(np_sq_ref)
        dsq_ref[...] = jnp.zeros_like(dsq_ref)

    pg_sq_ref[...] += jnp.sum(jnp.square(pg)).reshape(1, 1)
    # norm of the params as STORED (post-cast), matching the ref's global_norm
    np_sq_ref[...] += jnp.sum(jnp.square(new_p_cast.astype(jnp.float32))).reshape(1, 1)
    dsq_ref[...] += jnp.sum(jnp.square(d), axis=1, keepdims=True)


def server_apply(
    deltas2d: jax.Array,  # (C, Np) float32 — packed client deltas (padded)
    wn: jax.Array,  # (C,) float32 — weights pre-divided by Σw
    params_flat: jax.Array,  # (Np,) — packed params (any float dtype)
    lanes: Sequence[jax.Array],  # packed outer-opt lanes, each (Np,), params dtype
    *,
    opt: str,  # 'fedavg' | 'fedmom' | 'fedadam'
    lr: float,
    momentum: float = 0.9,
    nesterov: bool = True,
    beta2: float = 0.99,
    eps: float = 1e-8,
    bias_corr: Optional[Tuple[jax.Array, jax.Array]] = None,  # (b1c, b2c) fedadam
    noise: Optional[jax.Array] = None,  # (Np,) float32 pre-scaled DP noise
    block: int = 8192,
    interpret: bool = False,
):
    """One fused pass over the flat buffers. Returns
    ``(new_params (Np,), new_lanes, pg_sq (1,1), newp_sq (1,1), delta_sq (C,1))``.

    Reads each input byte exactly once and writes each output byte exactly once;
    the three metric outputs are revisited (1,1)/(C,1) accumulator blocks.
    """
    C, Np = deltas2d.shape
    assert Np % block == 0, (Np, block)
    n_lanes = len(lanes)
    has_noise = noise is not None
    has_bias_corr = bias_corr is not None
    kernel = functools.partial(
        _server_apply_kernel,
        opt=opt, lr=lr, momentum=momentum, nesterov=nesterov, beta2=beta2,
        eps=eps, n_lanes=n_lanes, has_noise=has_noise, has_bias_corr=has_bias_corr,
    )
    args = [wn.reshape(C, 1).astype(jnp.float32)]
    in_specs = [pl.BlockSpec((C, 1), lambda i: (0, 0))]
    if has_bias_corr:
        for b in bias_corr:
            args.append(jnp.asarray(b, jnp.float32).reshape(1, 1))
            in_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0)))
    args.append(deltas2d)
    in_specs.append(pl.BlockSpec((C, block), lambda i: (0, i)))
    args.append(params_flat)
    in_specs.append(pl.BlockSpec((block,), lambda i: (i,)))
    for lane in lanes:
        args.append(lane)
        in_specs.append(pl.BlockSpec((block,), lambda i: (i,)))
    if has_noise:
        args.append(noise)
        in_specs.append(pl.BlockSpec((block,), lambda i: (i,)))

    out_shape = [jax.ShapeDtypeStruct((Np,), params_flat.dtype)]
    out_specs = [pl.BlockSpec((block,), lambda i: (i,))]
    for lane in lanes:
        out_shape.append(jax.ShapeDtypeStruct((Np,), lane.dtype))
        out_specs.append(pl.BlockSpec((block,), lambda i: (i,)))
    out_shape += [
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
        jax.ShapeDtypeStruct((C, 1), jnp.float32),
    ]
    out_specs += [
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
        pl.BlockSpec((C, 1), lambda i: (0, 0)),
    ]

    outs = pl.pallas_call(
        kernel,
        grid=(Np // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        # the metric outputs accumulate across grid steps -> sequential grid
        compiler_params=_compiler_params(interpret, ("arbitrary",)),
        interpret=interpret,
    )(*args)
    new_p = outs[0]
    new_lanes = list(outs[1 : 1 + n_lanes])
    pg_sq, np_sq, dsq = outs[1 + n_lanes :]
    return new_p, new_lanes, pg_sq, np_sq, dsq


# ---------------------------------------------------------------------------
# Fused codec kernels (flat-buffer uplink)
# ---------------------------------------------------------------------------


def _topk_mask_ef_kernel(t_ref, xf_ref, kept_ref, resid_ref):
    xf = xf_ref[...].astype(jnp.float32)
    thresh = t_ref[0, 0]
    kept = jnp.where(jnp.abs(xf) >= thresh, xf, 0.0)
    kept_ref[...] = kept
    resid_ref[...] = xf - kept


def topk_mask_ef(
    xf: jax.Array,  # (Np,) float32 — delta + error-feedback residual, packed
    thresh: jax.Array,  # () float32 — the k-th magnitude (from lax.top_k)
    *,
    block: int = 8192,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Mask + select + residual update in ONE pass: reads xf once, writes the
    kept payload and the new residual once. (The ref chain re-reads xf for the
    abs, the mask, the select and the subtraction.)"""
    (Np,) = xf.shape
    assert Np % block == 0, (Np, block)
    return pl.pallas_call(
        _topk_mask_ef_kernel,
        grid=(Np // block,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[jax.ShapeDtypeStruct((Np,), jnp.float32)] * 2,
        compiler_params=_compiler_params(interpret, ("parallel",)),
        interpret=interpret,
    )(jnp.asarray(thresh, jnp.float32).reshape(1, 1), xf)


def _sr_bf16_kernel(x_ref, noise_ref, o_ref):
    bits = jax.lax.bitcast_convert_type(x_ref[...].astype(jnp.float32), jnp.uint32)
    rounded = (bits + noise_ref[...]) & jnp.uint32(0xFFFF0000)
    o_ref[...] = jax.lax.bitcast_convert_type(rounded, jnp.float32).astype(jnp.bfloat16)


def sr_bf16(
    x: jax.Array,  # (Np,) float32
    noise: jax.Array,  # (Np,) uint32 in [0, 2^16) — pre-drawn rounding noise
    *,
    block: int = 8192,
    interpret: bool = False,
) -> jax.Array:
    """Bit-level stochastic round to bf16 in one pass — the identical arithmetic
    to ``compression.cast_compress`` (add 16-bit noise to the f32 pattern,
    truncate), so given the same noise the payload is bitwise the ref's."""
    (Np,) = x.shape
    assert Np % block == 0, (Np, block)
    return pl.pallas_call(
        _sr_bf16_kernel,
        grid=(Np // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Np,), jnp.bfloat16),
        compiler_params=_compiler_params(interpret, ("parallel",)),
        interpret=interpret,
    )(x, noise)


def _int8_quant_kernel(s_ref, x_ref, q_ref):
    scale = s_ref[0, 0]
    q = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)


def int8_quant(
    x: jax.Array,  # (Np,) float32
    scale: jax.Array,  # () float32 — per-tensor absmax/127
    *,
    block: int = 8192,
    interpret: bool = False,
) -> jax.Array:
    (Np,) = x.shape
    assert Np % block == 0, (Np, block)
    return pl.pallas_call(
        _int8_quant_kernel,
        grid=(Np // block,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Np,), jnp.int8),
        compiler_params=_compiler_params(interpret, ("parallel",)),
        interpret=interpret,
    )(jnp.asarray(scale, jnp.float32).reshape(1, 1), x)


def _int8_dequant_kernel(s_ref, q_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0, 0]


def int8_dequant(
    q: jax.Array,  # (Np,) int8
    scale: jax.Array,  # () float32
    *,
    block: int = 8192,
    interpret: bool = False,
) -> jax.Array:
    (Np,) = q.shape
    assert Np % block == 0, (Np, block)
    return pl.pallas_call(
        _int8_dequant_kernel,
        grid=(Np // block,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Np,), jnp.float32),
        compiler_params=_compiler_params(interpret, ("parallel",)),
        interpret=interpret,
    )(jnp.asarray(scale, jnp.float32).reshape(1, 1), q)
