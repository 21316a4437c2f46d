"""SSD (Mamba2) chunk-scan Pallas TPU kernel.

Grid: (B, n_heads, n_chunks) — chunks are sequential ('arbitrary'), carrying the
(hd, ds) recurrent state in VMEM scratch across chunk steps. Each chunk step does the
intra-chunk quadratic term (two MXU matmuls of shape (chunk, ds)x(ds, chunk) and
(chunk, chunk)x(chunk, hd)) plus the inter-chunk state propagation — the TPU-native
realisation of state-space duality: all FLOPs live in MXU-aligned matmuls, the
recurrence touches VMEM only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,  # (1, 1, cl, hd)
    dt_ref,  # (1, 1, cl)
    a_ref,  # (1,)
    b_ref,  # (1, 1, cl, ds)
    c_ref,  # (1, 1, cl, ds)
    init_ref,  # (1, 1, hd, ds)
    y_ref,  # (1, 1, cl, hd) out
    final_ref,  # (1, 1, hd, ds) out
    state_scr,  # (hd, ds) f32 scratch
    *,
    chunk: int,
    n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)  # (cl, hd)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (cl,)
    A = a_ref[0].astype(jnp.float32)  # scalar
    Bm = b_ref[0, 0].astype(jnp.float32)  # (cl, ds)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (cl, ds)

    dA = dt * A  # (cl,) negative
    dA_cum = jnp.cumsum(dA)  # inclusive
    dA_total = dA_cum[-1]
    dx = x * dt[:, None]  # (cl, hd)

    # intra-chunk: causal decay-weighted "attention"
    decay = dA_cum[:, None] - dA_cum[None, :]  # (cl_i, cl_j)
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (chunk, chunk), 1
    )
    L = jnp.where(causal, jnp.exp(decay), 0.0)
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (cl, cl)
    y_intra = jax.lax.dot_general(
        scores * L, dx, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (cl, hd)

    # inter-chunk: contribution of carried state
    state = state_scr[...]  # (hd, ds)
    y_inter = jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(dA_cum)[:, None]  # (cl, hd)

    y_ref[0, 0, ...] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S' = exp(dA_total) S + sum_j exp(dA_total - dA_cum_j) dx_j B_j^T
    w = jnp.exp(dA_total - dA_cum)  # (cl,)
    new_state = jnp.exp(dA_total) * state + jax.lax.dot_general(
        dx * w[:, None], Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (hd, ds)
    state_scr[...] = new_state

    @pl.when(ic == n_chunks - 1)
    def _finalize():
        final_ref[0, 0, ...] = new_state


def ssd_scan_fwd(
    x: jax.Array,  # (B, nh, S, hd)
    dt: jax.Array,  # (B, nh, S)
    A: jax.Array,  # (nh,)
    Bm: jax.Array,  # (B, G, S, ds)
    Cm: jax.Array,  # (B, G, S, ds)
    init_state: jax.Array,  # (B, nh, hd, ds)
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    B, nh, S, hd = x.shape
    G, ds = Bm.shape[1], Bm.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = nh // G

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    grid = (B, nh, nc)

    x_spec = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0))
    dt_spec = pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c))
    a_spec = pl.BlockSpec((1,), lambda b, h, c: (h,))
    bc_spec = pl.BlockSpec((1, 1, chunk, ds), lambda b, h, c: (b, h // rep, c, 0))
    init_spec = pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0))
    y_spec = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0))
    fin_spec = pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0))

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    y, final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, dt_spec, a_spec, bc_spec, bc_spec, init_spec],
        out_specs=[y_spec, fin_spec],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((B, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        compiler_params=compiler_params,
        interpret=interpret,
    )(x, dt, A, Bm, Cm, init_state)
    return y, final
