"""Flash-decode Pallas TPU kernel: one query token vs. a long KV cache.

Grid: (B, Hq, n_kv_blocks); the kv dimension is sequential, carrying the online-softmax
(m, l, acc) in VMEM scratch. Variable cache length enters as a scalar-prefetch style
operand (a (B,) int32 array in SMEM-like placement) so a single compiled kernel serves
every decode position. This is the memory-bound hot loop of decode_32k/long_500k: each
KV byte is touched exactly once.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,  # (1,) int32 — valid kv length for this batch row
    q_ref,  # (1, 1, hd)
    k_ref,  # (1, 1, bk, hd)
    v_ref,  # (1, 1, bk, hd)
    o_ref,  # (1, 1, hd)
    m_scr,  # (1,) f32
    l_scr,  # (1,) f32
    acc_scr,  # (hd,) f32 — wait, use (1, hd)
    *,
    sm_scale: float,
    block_k: int,
    n_kv_blocks: int,
    window: Optional[int],
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[0]
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (hd,)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    s = jnp.sum(k * q[None, :], axis=1)  # (bk,)

    pos = ik * block_k + jax.lax.iota(jnp.int32, block_k)
    mask = pos < kv_len
    if window is not None:
        mask &= pos > (kv_len - 1 - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # (bk,)
    l_scr[0] = l_scr[0] * alpha + jnp.sum(p)
    acc_scr[...] = acc_scr[...] * alpha + jnp.sum(
        p[:, None] * v_ref[0, 0].astype(jnp.float32), axis=0, keepdims=True
    )
    m_scr[0] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0, :] = (acc_scr[0] / jnp.maximum(l_scr[0], 1e-30)).astype(o_ref.dtype)


def flash_decode_fwd(
    q: jax.Array,  # (B, Hq, hd)
    k: jax.Array,  # (B, Hkv, S, hd)
    v: jax.Array,
    kv_len: jax.Array,  # (B,) int32
    *,
    window: Optional[int] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    assert S % block_k == 0, (S, block_k)
    grp = Hq // Hkv
    n_kv = S // block_k
    sm_scale = 1.0 / (hd**0.5)

    kernel = functools.partial(
        _decode_kernel,
        sm_scale=sm_scale,
        block_k=block_k,
        n_kv_blocks=n_kv,
        window=window,
    )

    grid = (B, Hq, n_kv)
    len_spec = pl.BlockSpec((1,), lambda b, h, j: (b,))
    q_spec = pl.BlockSpec((1, 1, hd), lambda b, h, j: (b, h, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h // grp, j, 0))
    o_spec = pl.BlockSpec((1, 1, hd), lambda b, h, j: (b, h, 0))

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[len_spec, q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(kv_len, q, k, v)
