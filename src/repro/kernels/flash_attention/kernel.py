"""Flash attention Pallas TPU kernel: online-softmax tiling with explicit BlockSpec
VMEM placement. GQA-aware (KV blocks indexed by query-head → kv-head mapping), causal
and sliding-window masking.

Grid: (B, Hq, n_q_blocks, n_kv_blocks) — the last (kv) dimension is sequential
('arbitrary'), carrying the running max/denominator/accumulator in VMEM scratch across
kv steps, the canonical TPU flash-attention schedule. Block shapes are chosen by the
ops.py wrapper to be MXU-aligned (multiples of 128 where the problem allows).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, 1, bq, hd) VMEM
    k_ref,  # (1, 1, bk, hd)
    v_ref,  # (1, 1, bk, hd)
    o_ref,  # (1, 1, bq, hd)
    m_scr,  # (bq,) f32 scratch
    l_scr,  # (bq,) f32
    acc_scr,  # (bq, hd) f32
    *,
    causal: bool,
    window: Optional[int],
    sm_scale: float,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
    q_offset: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    # fully-masked rows: keep numerics clean
    p = jnp.where(mask, p, 0.0)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0, ...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, Hq, Sq, hd)
    k: jax.Array,  # (B, Hkv, Sk, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    grp = Hq // Hkv
    n_q, n_kv = Sq // block_q, Sk // block_k
    sm_scale = 1.0 / (hd**0.5)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        window=window,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_kv,
        q_offset=q_offset,
    )

    grid = (B, Hq, n_q, n_kv)
    q_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // grp, j, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0))

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v)
