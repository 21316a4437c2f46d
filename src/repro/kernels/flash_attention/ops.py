"""Differentiable public wrapper for the flash attention kernels.

Handles layout (model code uses (B, S, H, hd); the kernels take (B, H, S, hd) and
give back transposed outputs), block-size selection from the shapes, the
backward pass through ``jax.custom_vjp``, and the CPU/TPU dispatch (interpret
mode on CPU hosts so the same code path is testable everywhere).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_bwd, flash_fwd

# Largest dQᵀ accumulator the backward keeps in VMEM: (head_dim, Sq) float32.
_MAX_DQ_BYTES = 4 << 20


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _largest_divisor(s: int, candidates) -> Optional[int]:
    return next((b for b in candidates if s % b == 0), None)


def _block(s: int) -> Optional[int]:
    if s <= 256:
        return s
    return _largest_divisor(s, tuple(b for b in (1024, 512, 256, 128) if 2 * b <= s))


def pick_blocks(sq: int, sk: int, hd: int) -> Optional[Tuple[int, int]]:
    """``(block_q, block_k)`` for the compiled TPU kernels, or None where the
    shapes do not tile. Square tiles of up to 1024, at most half the sequence
    so that causal tiles are skipped: a grid step costs as much as ~0.1M
    scores, so large tiles win, and the diagonal tiles' column strips cut
    their waste (measured at S = 2048, head_dim 64 and 128: PERF.md). Blocks lie on the 128-wide lanes: multiples of 128 or the whole
    sequence. The backward keeps a (head_dim, Sq) float32 dQ in VMEM."""
    bq, bk = _block(sq), _block(sk)
    if bq is None or bk is None or hd * sq * 4 > _MAX_DQ_BYTES:
        return None
    return bq, bk


def _interpret_blocks(sq: int, sk: int) -> Tuple[int, int]:
    """Any divisor will do in interpret mode: small blocks for small tests."""
    cands = (64, 32, 16, 8)
    return _largest_divisor(sq, cands) or sq, _largest_divisor(sk, cands) or sk


def _t(x):  # (B, S, H, hd) <-> (B, H, S, hd)
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attend(q, k, v, slopes, causal, window, q_offset, interpret):
    return _attend_fwd(q, k, v, slopes, causal, window, q_offset, interpret)[0]


def _static(q, k, causal, window, q_offset, interpret):
    sq, sk, hd = q.shape[1], k.shape[1], q.shape[3]
    bq, bk = _interpret_blocks(sq, sk) if interpret else pick_blocks(sq, sk, hd)
    return dict(causal=causal, window=window, q_offset=q_offset,
                block_q=bq, block_k=bk, interpret=interpret)


def _attend_fwd(q, k, v, slopes, causal, window, q_offset, interpret):
    kw = _static(q, k, causal, window, q_offset, interpret)
    qt, kt, vt = _t(q), _t(k), _t(v)
    o_t, lse = flash_fwd(qt, kt, vt, slopes, **kw)
    o = jnp.transpose(o_t, (0, 3, 1, 2))  # (B, Hq, hd, Sq) -> (B, Sq, Hq, hd)
    return o, (q, k, v, slopes, o, lse)


def _attend_bwd(causal, window, q_offset, interpret, res, do):
    q, k, v, slopes, o, lse = res
    qt, kt, vt = _t(q), _t(k), _t(v)
    kw = _static(q, k, causal, window, q_offset, interpret)
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # (B, Sq, Hq)
    di = jnp.swapaxes(di, 1, 2)[:, :, None, :]  # rows, as lse
    dq_t, dk, dv = flash_bwd(qt, kt, vt, slopes, _t(do), lse, di, **kw)
    B, Hkv, Sk, hd = kt.shape
    Hq = qt.shape[1]
    if Hq != Hkv:  # per-query-head float32 partials -> sum each group
        dk = dk.reshape(B, Hkv, Hq // Hkv, Sk, hd).sum(2)
        dv = dv.reshape(B, Hkv, Hq // Hkv, Sk, hd).sum(2)
    dq = jnp.transpose(dq_t, (0, 3, 1, 2))
    dslopes = None if slopes is None else jnp.zeros_like(slopes)
    return dq, _t(dk).astype(kt.dtype), _t(dv).astype(vt.dtype), dslopes


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, hd) — model layout
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,
    slopes: Optional[jax.Array] = None,  # (Hq,) ALiBi slopes, or None
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Scaled-dot-product attention of (B, Sq, Hq, hd) queries over (B, Sk, Hkv,
    hd) keys and values, differentiable in q, k and v."""
    if interpret is None:
        interpret = _on_cpu()
    if window is not None and not isinstance(window, int):
        raise TypeError("kernel path needs a static window")
    if not interpret and pick_blocks(q.shape[1], k.shape[1], q.shape[3]) is None:
        raise ValueError(f"no kernel tiling for Sq={q.shape[1]}, Sk={k.shape[1]}")
    return _attend(q, k, v, slopes, causal, window, q_offset, interpret)
