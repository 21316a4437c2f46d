"""Grouped-query attention with qk-norm, RoPE/ALiBi/learned positions, sliding windows,
cross-attention, and KV-cache decode. The scaled-dot-product core runs in the Pallas
flash kernels (forward and backward) on TPU wherever the call allows it
(:func:`flash_eligible`), and in the pure-jnp paths otherwise.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamDesc, alibi_slopes, apply_rope, rmsnorm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter descriptions
# ---------------------------------------------------------------------------


def attn_desc(cfg, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    scale = 0.02
    p = {
        "wq": ParamDesc((d, hq, hd), (None, "heads", "head_dim"), "normal", scale),
        "wk": ParamDesc((d, hkv, hd), (None, "kv_heads", "head_dim"), "normal", scale),
        "wv": ParamDesc((d, hkv, hd), (None, "kv_heads", "head_dim"), "normal", scale),
        "wo": ParamDesc((hq, hd, d), ("heads", "head_dim", None), "normal", scale / max(1, 2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = ParamDesc((hd,), (None,), "ones")
        p["k_norm"] = ParamDesc((hd,), (None,), "ones")
    return p


# ---------------------------------------------------------------------------
# Core scaled-dot-product (reference path; Pallas kernels in repro.kernels)
# ---------------------------------------------------------------------------


def sdpa(
    q: jax.Array,  # (B, Sq, Hq, hd)
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,  # (B, Sk, Hkv, hd)
    mask: Optional[jax.Array],  # broadcastable to (B, 1, 1, Sq, Sk) or None
    bias: Optional[jax.Array] = None,  # additive, broadcastable to (B, Hq, Sq, Sk)
) -> jax.Array:
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    grp = Hq // Hkv
    qr = q.reshape(B, Sq, Hkv, grp, hd)
    scores = jnp.einsum("bqhgd,bshd->bhgqs", qr, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if bias is not None:  # (b|1, Hq, Sq, Sk) -> (b|1, Hkv, grp, Sq, Sk), broadcast over B
        scores = scores + bias.reshape(bias.shape[0], Hkv, grp, *bias.shape[-2:])
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p, v)
    return out.reshape(B, Sq, Hq, hd)


def make_mask(
    q_pos: jax.Array,  # (Sq,) or (B, Sq)
    k_pos: jax.Array,  # (Sk,) or (B, Sk)
    causal: bool,
    window,  # None, python int, or traced scalar (scanned per-layer window)
    k_len: Optional[jax.Array] = None,  # valid KV length for decode (scalar)
) -> jax.Array:
    """Boolean mask broadcastable to (B, 1, 1, Sq, Sk)."""
    if q_pos.ndim == 1:
        q_pos = q_pos[None]
    if k_pos.ndim == 1:
        k_pos = k_pos[None]
    qp = q_pos[:, None, None, :, None]
    kp = k_pos[:, None, None, None, :]
    mask = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        mask = mask & (kp <= qp)
    if window is not None:
        mask = mask & (qp - kp < window)
    if k_len is not None:
        mask = mask & (kp < k_len)
    return mask


def _pick_chunk(s: int, preferred: int = 256) -> int:
    for c in (preferred, 128, 512, 64, 250, 375, 32):
        if s % c == 0:
            return c
    return s


import os

# §Perf experiment toggle: keep masked score blocks in bf16 through the softmax
# (halves the dominant HBM traffic of the jnp attention path; the Pallas kernel keeps
# scores in VMEM entirely). Enabled per-run: REPRO_BF16_SCORES=1.
_BF16_SCORES = os.environ.get("REPRO_BF16_SCORES", "0") == "1"


def sdpa_chunked(
    q: jax.Array,  # (B, Sq, Hq, hd)
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,
    *,
    q_pos: jax.Array,  # (Sq,)
    k_pos: jax.Array,  # (Sk,)
    causal: bool,
    window,
    k_len: Optional[jax.Array],
    slopes: Optional[jax.Array],  # ALiBi (Hq,) or None
    chunk: int = 256,
) -> jax.Array:
    """Flash-structured attention in pure jnp: lax.scan over query chunks keeps the
    materialized score block at (B, H, chunk, Sk) — this is the graph the dry-run
    lowers, bounding HBM temps the same way the Pallas kernel bounds VMEM."""
    B, Sq, Hq, hd = q.shape
    Hkv, Sk = k.shape[2], k.shape[1]
    grp = Hq // Hkv
    chunk = _pick_chunk(Sq, chunk)
    nq = Sq // chunk
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    qc = jnp.moveaxis(q.reshape(B, nq, chunk, Hq, hd), 1, 0)  # (nq, B, cq, Hq, hd)
    qpos_c = q_pos.reshape(nq, chunk)

    def body(_, inp):
        qb, qp = inp  # (B, cq, Hq, hd), (cq,)
        qr = qb.reshape(B, chunk, Hkv, grp, hd)
        s = jnp.einsum("bqhgd,bshd->bhgqs", qr, k).astype(jnp.float32) * scale
        qpc = qp[:, None]
        kpc = k_pos[None, :]
        m = jnp.ones((chunk, Sk), bool)
        if causal:
            m &= kpc <= qpc
        if window is not None:
            m &= (qpc - kpc) < window
        if k_len is not None:
            m &= kpc < k_len
        if slopes is not None:
            dist = jnp.maximum((qpc - kpc).astype(jnp.float32), 0.0)
            s = s - slopes.reshape(1, Hkv, grp, 1, 1) * dist[None, None, None]
        s = jnp.where(m[None, None, None], s, NEG_INF)
        if _BF16_SCORES:
            # bf16 shares f32's exponent range, so NEG_INF masking survives; the
            # max-subtraction inside softmax bounds the mantissa error.
            s = s.astype(jnp.bfloat16)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhgqs,bshd->bqhgd", p, v).reshape(B, chunk, Hq, hd)
        return None, o

    # checkpoint: backward recomputes the per-chunk score block instead of saving all
    # (B, H, chunk, Sk) softmax residuals — the jnp analogue of flash attention's
    # recompute-in-backward.
    body = jax.checkpoint(body, prevent_cse=False)
    _, out = jax.lax.scan(body, None, (qc, qpos_c))  # (nq, B, cq, Hq, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, Hq, hd)


def flash_eligible(
    platform: str,
    *,
    self_attention: bool,
    decode: bool,
    window,
    sq: int,
    sk: int,
    hd: int,
) -> bool:
    """Whether the scaled-dot-product runs in the Pallas flash kernels: on the
    TPU, for self-attention outside decode (no cache length to mask), with no
    window or a static one, at shapes the kernels tile. ALiBi and RoPE both
    qualify; everything else keeps the jnp paths."""
    from repro.kernels.flash_attention import ops as fa_ops

    return (
        platform == "tpu"
        and self_attention
        and not decode
        and (window is None or isinstance(window, int))
        and fa_ops.pick_blocks(sq, sk, hd) is not None
    )


# ---------------------------------------------------------------------------
# Full attention layer
# ---------------------------------------------------------------------------


def attention(
    cfg,
    p: dict,
    x: jax.Array,  # (B, S, D)
    *,
    positions: jax.Array,  # (S,) token positions (absolute)
    causal: bool = True,
    window=None,
    cache: Optional[dict] = None,  # {'k': (B, Smax, Hkv, hd), 'v': ..., } decode/prefill
    cache_index: Optional[jax.Array] = None,  # scalar write offset for decode
    kv_source: Optional[jax.Array] = None,  # cross-attention memory (B, Skv, D)
) -> Tuple[jax.Array, Optional[dict]]:
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    kv_in = kv_source if kv_source is not None else x
    k = jnp.einsum("bsd,dhk->bshk", kv_in, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_in, p["wv"].astype(x.dtype))

    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])

    bias = None
    if kv_source is None:  # self-attention: positional treatment
        if cfg.pos_embedding == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        if kv_source is not None and cache_index is None:
            # cross-attention cache is written once at prefill: entire k/v
            new_cache = {"k": k, "v": v}
        elif cache_index is not None and "k" in cache and cache["k"].shape[1] > S:
            # decode: write S (=1) new entries at cache_index, attend over full cache
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1
            )
            new_cache = {"k": ck, "v": cv}
            k, v = ck.astype(q.dtype), cv.astype(q.dtype)
        else:
            # prefill: cache is exactly the computed k/v
            new_cache = {"k": k, "v": v}

    Sk = k.shape[1]
    k_positions = jnp.arange(Sk)
    slopes = None
    if kv_source is not None:
        eff_causal, eff_window, k_len = False, None, None
    else:
        eff_causal, eff_window = causal, window
        k_len = None
        if cache is not None and cache_index is not None and Sk > S:
            k_len = cache_index + S
        if cfg.pos_embedding == "alibi":
            slopes = alibi_slopes(cfg.n_heads)  # (Hq,)

    with jax.named_scope("attn"):  # the core, for the trace (bench/attn_parts.py)
        if flash_eligible(
            jax.default_backend(), self_attention=kv_source is None,
            decode=cache_index is not None, window=eff_window, sq=S, sk=Sk, hd=q.shape[-1],
        ):
            from repro.kernels.flash_attention import ops as fa_ops

            out = fa_ops.flash_attention(q, k, v, slopes, causal=eff_causal, window=eff_window)
        elif S >= 512:
            out = sdpa_chunked(
                q, k, v, q_pos=positions, k_pos=k_positions, causal=eff_causal,
                window=eff_window, k_len=k_len, slopes=slopes,
            )
        else:
            mask = (
                None
                if kv_source is not None
                else make_mask(positions, k_positions, eff_causal, eff_window, k_len)
            )
            bias = None
            if slopes is not None:
                dist = (positions[:, None] - k_positions[None, :]).astype(jnp.float32)
                bias = (-slopes[:, None, None] * jnp.maximum(dist, 0.0))[None]
            out = sdpa(q, k, v, mask, bias)

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, new_cache


def empty_cache_desc(cfg, batch: int, max_len: int, dtype) -> dict:
    """ShapeDtypeStruct-compatible zero cache for one attention layer."""
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (batch, max_len, hkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
