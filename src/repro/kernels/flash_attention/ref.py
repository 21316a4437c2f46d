"""Pure-jnp float32 oracle for the flash attention kernels (GQA, causal, sliding
window, ALiBi)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(
    q: jax.Array,  # (B, Hq, Sq, hd)
    k: jax.Array,  # (B, Hkv, Sk, hd)
    v: jax.Array,  # (B, Hkv, Sk, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,  # absolute position of q[0] (decode: Sk - Sq)
    slopes: Optional[jax.Array] = None,  # (Hq,) ALiBi slopes, or None
) -> jax.Array:
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    grp = Hq // Hkv
    qr = q.reshape(B, Hkv, grp, Sq, hd).astype(jnp.float32)
    scores = jnp.einsum("bhgqd,bhsd->bhgqs", qr, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    if slopes is not None:  # -slope_h * (q_pos - k_pos), clipped at 0 as in sdpa_chunked
        dist = jnp.maximum((q_pos[:, None] - k_pos[None, :]).astype(jnp.float32), 0.0)
        scores = scores - slopes.reshape(Hkv, grp, 1, 1).astype(jnp.float32) * dist
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqs,bhsd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, hd).astype(q.dtype)
