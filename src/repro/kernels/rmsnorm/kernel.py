"""Fused RMSNorm Pallas TPU kernel: one pass over rows, fp32 accumulation in VMEM.

Grid: (n_row_blocks,) with block (br, D) — D stays whole (norms reduce over it), rows
tile. A pure VPU kernel; its value on TPU is fusing the square-mean + rsqrt + scale
into one VMEM-resident pass instead of three HBM round-trips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (br, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_fwd(
    x2d: jax.Array,  # (R, D)
    scale: jax.Array,  # (D,)
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    R, D = x2d.shape
    assert R % block_rows == 0, (R, block_rows)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(dimension_semantics=("parallel",))
    return pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(x2d, scale)
