"""Pattern-aware transformer engine.

Layers are grouped into *segments*: a short unrolled prefix plus a periodic body that is
``lax.scan``-ned over its repeats (params stacked on a leading dim). This keeps the HLO
small for 40-62 layer models while supporting heterogeneous layer patterns:

  granite / qwen3 / coder / chameleon / llama4 : period 1 (uniform)
  gemma3        : period 1 — local/global differ only in *window*, passed as scanned data
  deepseek-moe  : prefix 1 (dense-FFN layer 0) + period 1 (MoE layers)
  jamba         : period 8 (MMMMAMMM with alternating dense/MoE FFN)
  mamba2        : period 1 (pure SSD blocks)
  whisper       : encoder stack (non-causal) + decoder stack with cross-attention

Modes: 'train' (no cache), 'prefill' (returns cache), 'decode' (1 token, updates cache).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerKind, ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (
    ParamDesc,
    apply_norm,
    norm_desc,
    stack_descs,
)

WINDOW_SENTINEL = 1 << 30  # "no window": mask (qpos - kpos < sentinel) is always true


# ---------------------------------------------------------------------------
# Segment planning
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SegmentPlan:
    kinds: Tuple[LayerKind, ...]  # one per position within the body
    n_repeat: int  # scan length (1 = executed inline)
    first_layer: int  # absolute index of this segment's first layer

    @property
    def period(self) -> int:
        return len(self.kinds)

    @property
    def n_layers(self) -> int:
        return self.period * self.n_repeat

    def window_array(self, all_kinds: List[LayerKind]):
        """(n_repeat, period) int32 window per layer (sentinel = full attention)."""
        import numpy as np

        w = np.full((self.n_repeat, self.period), WINDOW_SENTINEL, dtype=np.int64)
        for r in range(self.n_repeat):
            for p in range(self.period):
                k = all_kinds[self.first_layer + r * self.period + p]
                if k.window is not None:
                    w[r, p] = k.window
        return jnp.asarray(np.minimum(w, WINDOW_SENTINEL), dtype=jnp.int32)


def plan_segments(kinds: List[LayerKind], max_period: int = 12) -> List[SegmentPlan]:
    n = len(kinds)
    sigs = [k.signature for k in kinds]
    for r in range(0, min(3, n) + 1):
        m = n - r
        if m == 0:
            break
        for p in range(1, max_period + 1):
            if m % p:
                continue
            if all(sigs[r + i] == sigs[r + (i % p)] for i in range(m)):
                segs = [
                    SegmentPlan(kinds=(kinds[i],), n_repeat=1, first_layer=i)
                    for i in range(r)
                ]
                segs.append(
                    SegmentPlan(
                        kinds=tuple(kinds[r : r + p]), n_repeat=m // p, first_layer=r
                    )
                )
                return segs
    # fallback: fully unrolled
    return [SegmentPlan(kinds=(k,), n_repeat=1, first_layer=i) for i, k in enumerate(kinds)]


# ---------------------------------------------------------------------------
# Parameter description
# ---------------------------------------------------------------------------


def _layer_desc(cfg: ModelConfig, kind: LayerKind) -> dict:
    d = {"norm1": norm_desc(cfg)}
    if kind.mixer == "attn":
        d["mixer"] = attn_mod.attn_desc(cfg)
    else:
        d["mixer"] = ssm_mod.ssm_desc(cfg)
    if kind.cross_attn:
        d["norm_cross"] = norm_desc(cfg)
        d["cross_attn"] = attn_mod.attn_desc(cfg, cross=True)
    if kind.ffn == "dense":
        d["norm2"] = norm_desc(cfg)
        d["ffn"] = moe_mod.dense_ffn_desc(cfg, cfg.d_ff)
    elif kind.ffn == "moe":
        d["norm2"] = norm_desc(cfg)
        d["ffn"] = moe_mod.moe_ffn_desc(cfg)
    return d


def _segment_desc(cfg: ModelConfig, seg: SegmentPlan) -> dict:
    body = {f"pos{p}": _layer_desc(cfg, k) for p, k in enumerate(seg.kinds)}
    if seg.n_repeat > 1:
        body = stack_descs(body, seg.n_repeat, stack_axis_name="layers")
    return body


def model_desc(cfg: ModelConfig) -> dict:
    d: Dict[str, Any] = {
        "embed": ParamDesc((cfg.padded_vocab, cfg.d_model), ("vocab", None), "embed"),
    }
    if cfg.pos_embedding == "learned":
        d["pos_embed"] = ParamDesc((cfg.max_seq_len, cfg.d_model), (None, None), "embed")
    segs = plan_segments(cfg.layer_kinds())
    d["segments"] = [_segment_desc(cfg, s) for s in segs]
    d["final_norm"] = norm_desc(cfg)
    if not cfg.tie_embeddings:
        d["lm_head"] = ParamDesc((cfg.d_model, cfg.padded_vocab), (None, "vocab"), "normal")
    if cfg.enc_dec:
        enc_segs = plan_segments(cfg.encoder_layer_kinds())
        d["encoder"] = {
            "audio_pos": ParamDesc((cfg.n_audio_frames, cfg.d_model), (None, None), "embed"),
            "segments": [_segment_desc(cfg, s) for s in enc_segs],
            "final_norm": norm_desc(cfg),
        }
    return d


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def _layer_cache(cfg: ModelConfig, kind: LayerKind, batch: int, max_len: int, dtype):
    c: Dict[str, Any] = {}
    if kind.mixer == "attn":
        hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        c["mixer"] = {
            "k": jnp.zeros((batch, max_len, hkv, hd), dtype),
            "v": jnp.zeros((batch, max_len, hkv, hd), dtype),
        }
    else:
        c["mixer"] = ssm_mod.empty_ssm_cache(cfg, batch)
    if kind.cross_attn:
        hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        c["cross"] = {
            "k": jnp.zeros((batch, cfg.n_audio_frames, hkv, hd), dtype),
            "v": jnp.zeros((batch, cfg.n_audio_frames, hkv, hd), dtype),
        }
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    segs = plan_segments(cfg.layer_kinds())
    out = []
    for seg in segs:
        body = {
            f"pos{p}": _layer_cache(cfg, k, batch, max_len, dtype)
            for p, k in enumerate(seg.kinds)
        }
        if seg.n_repeat > 1:
            body = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (seg.n_repeat,) + x.shape), body
            )
        out.append(body)
    return out


# ---------------------------------------------------------------------------
# Layer / segment application
# ---------------------------------------------------------------------------


def _apply_layer(
    cfg: ModelConfig,
    kind: LayerKind,
    p: dict,
    h: jax.Array,
    *,
    window,
    positions: jax.Array,
    cache: Optional[dict],
    cache_index: Optional[jax.Array],
    enc_out: Optional[jax.Array],
    decode: bool,
    use_pallas: bool,
) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}

    x = apply_norm(cfg, p["norm1"], h)
    if kind.mixer == "attn":
        a, mc = attn_mod.attention(
            cfg,
            p["mixer"],
            x,
            positions=positions,
            causal=True,
            window=window,
            cache=cache.get("mixer") if cache else None,
            cache_index=cache_index,
        )
    else:
        a, mc = ssm_mod.ssm_block(
            cfg,
            p["mixer"],
            x,
            cache=cache.get("mixer") if cache else None,
            decode=decode,
            use_pallas=use_pallas,
        )
    if mc is not None:
        new_cache["mixer"] = mc
    h = h + a

    if kind.cross_attn:
        xc = apply_norm(cfg, p["norm_cross"], h)
        if decode:
            # static memory KV, computed at prefill
            cc = cache["cross"]
            ca, _ = _cross_attend_cached(cfg, p["cross_attn"], xc, cc)
            new_cache["cross"] = cc
        else:
            ca, cc = attn_mod.attention(
                cfg, p["cross_attn"], xc, positions=positions, causal=False,
                cache={} if cache is not None else None, kv_source=enc_out,
            )
            if cc is not None:
                new_cache["cross"] = cc
        h = h + ca

    if kind.ffn != "none":
        x2 = apply_norm(cfg, p["norm2"], h)
        if kind.ffn == "dense":
            f = moe_mod.dense_ffn(cfg, p["ffn"], x2)
        else:
            f, aux = moe_mod.moe_ffn(cfg, p["ffn"], x2)
        h = h + f

    return h, (new_cache if (cache is not None or decode) else None), aux


def _cross_attend_cached(cfg, p, x, cross_cache):
    """Decode-time cross attention against prefill-cached encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k, v = cross_cache["k"].astype(x.dtype), cross_cache["v"].astype(x.dtype)
    out = attn_mod.sdpa(q, k, v, mask=None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), cross_cache


def _apply_segment(
    cfg: ModelConfig,
    seg: SegmentPlan,
    seg_params: dict,
    h: jax.Array,
    *,
    all_kinds: List[LayerKind],
    positions: jax.Array,
    seg_cache,
    cache_index,
    enc_out,
    decode: bool,
    use_pallas: bool,
    remat: bool = False,
):
    windows = seg.window_array(all_kinds)  # (n_repeat, period)

    def make_layer_fn(pidx, kind):
        # a window every repeat shares is passed as a python value (None or int),
        # which the attention kernels take; only windows that differ between
        # repeats are the scanned data
        shared = {all_kinds[seg.first_layer + r * seg.period + pidx].window
                  for r in range(seg.n_repeat)}

        def layer_fn(h, params_l, window_l, cache_l):
            return _apply_layer(
                cfg,
                kind,
                params_l,
                h,
                window=next(iter(shared)) if len(shared) == 1 else window_l,
                positions=positions,
                cache=cache_l,
                cache_index=cache_index,
                enc_out=enc_out,
                decode=decode,
                use_pallas=use_pallas,
            )

        if remat and not decode:
            # per-LAYER checkpointing: the backward pass holds one layer's internals
            # at a time even when the scan body spans a multi-layer hybrid period
            return jax.checkpoint(layer_fn, prevent_cse=False)
        return layer_fn

    layer_fns = [make_layer_fn(p, k) for p, k in enumerate(seg.kinds)]

    def run_body(h, params_r, windows_r, cache_r):
        aux_total = jnp.zeros((), jnp.float32)
        new_cache_r = {}
        for pidx, kind in enumerate(seg.kinds):
            key = f"pos{pidx}"
            h, nc, aux = layer_fns[pidx](
                h,
                params_r[key],
                windows_r[pidx],
                cache_r.get(key) if cache_r else None,
            )
            if nc is not None:
                new_cache_r[key] = nc
            aux_total = aux_total + aux
        return h, new_cache_r, aux_total

    if seg.n_repeat == 1:
        params_r = seg_params
        cache_r = seg_cache
        h, new_cache_r, aux = run_body(h, params_r, windows[0], cache_r)
        return h, (new_cache_r or None), aux

    body = run_body

    def scan_fn(carry, xs):
        h, aux_acc = carry
        params_r, windows_r, cache_r = xs
        h, new_cache_r, aux = body(h, params_r, windows_r, cache_r)
        return (h, aux_acc + aux), new_cache_r

    xs = (seg_params, windows, seg_cache)
    if seg_cache is None:
        xs = (seg_params, windows, jax.tree_util.tree_map(lambda _: None, jnp.zeros(seg.n_repeat)))
        # scan requires a pytree; use a dummy per-repeat placeholder
        xs = (seg_params, windows, jnp.zeros((seg.n_repeat,), jnp.int32))

        def scan_fn(carry, xs):  # noqa: F811
            h, aux_acc = carry
            params_r, windows_r, _ = xs
            h, new_cache_r, aux = body(h, params_r, windows_r, None)
            return (h, aux_acc + aux), new_cache_r

    (h, aux), new_cache = jax.lax.scan(scan_fn, (h, jnp.zeros((), jnp.float32)), xs)
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# Encoder (audio, non-causal)
# ---------------------------------------------------------------------------


def _encode(cfg: ModelConfig, enc_params: dict, audio_embed: jax.Array):
    h = audio_embed + enc_params["audio_pos"][None, : audio_embed.shape[1]].astype(audio_embed.dtype)
    kinds = cfg.encoder_layer_kinds()
    segs = plan_segments(kinds)
    positions = jnp.arange(audio_embed.shape[1])

    for seg, seg_params in zip(segs, enc_params["segments"]):
        windows = seg.window_array(kinds)

        def enc_layer(h, params_r):
            x = apply_norm(cfg, params_r["pos0"]["norm1"], h)
            a, _ = attn_mod.attention(
                cfg, params_r["pos0"]["mixer"], x, positions=positions, causal=False,
            )
            h = h + a
            x2 = apply_norm(cfg, params_r["pos0"]["norm2"], h)
            return h + moe_mod.dense_ffn(cfg, params_r["pos0"]["ffn"], x2)

        if seg.n_repeat == 1:
            h = enc_layer(h, seg_params)
        else:
            def scan_fn(carry, params_r):
                return enc_layer(carry, params_r), None

            h, _ = jax.lax.scan(scan_fn, h, seg_params)
    return apply_norm(cfg, enc_params["final_norm"], h)


# ---------------------------------------------------------------------------
# Public forward
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S) int32
    *,
    audio_embed: Optional[jax.Array] = None,  # (B, F, D) for enc-dec (stub frontend)
    mode: str = "train",  # 'train' | 'prefill' | 'decode'
    cache=None,
    cache_index: Optional[jax.Array] = None,
    remat: bool = False,
    use_pallas: bool = False,
    logits_mode: str = "full",  # 'full' | 'last' | 'hidden' (return pre-head h)
):
    """Returns (logits (B,S,V) | hidden (B,S,D), aux_loss scalar, new_cache)."""
    assert mode in ("train", "prefill", "decode")
    decode = mode == "decode"
    B, S = tokens.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    embed = params["embed"]
    h = jnp.take(embed, tokens, axis=0).astype(compute_dtype)

    if decode:
        assert cache_index is not None
        positions = cache_index + jnp.arange(S)
    else:
        positions = jnp.arange(S)

    if cfg.pos_embedding == "learned":
        pe = jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], positions[0] if decode else 0, S, axis=0
        )
        h = h + pe.astype(compute_dtype)

    enc_out = None
    if cfg.enc_dec and not decode:
        assert audio_embed is not None, "enc-dec model requires audio_embed"
        enc_out = _encode(cfg, params["encoder"], audio_embed.astype(compute_dtype))

    all_kinds = cfg.layer_kinds()
    segs = plan_segments(all_kinds)
    if mode == "prefill" and cache is None:
        cache = _prefill_placeholder_cache(cfg, segs)

    aux_total = jnp.zeros((), jnp.float32)
    new_cache = [] if (cache is not None or decode) else None
    for seg, seg_params, seg_cache in zip(
        segs, params["segments"], cache if cache is not None else [None] * len(segs)
    ):
        h, seg_new_cache, aux = _apply_segment(
            cfg,
            seg,
            seg_params,
            h,
            all_kinds=all_kinds,
            positions=positions,
            seg_cache=seg_cache,
            cache_index=cache_index,
            enc_out=enc_out,
            decode=decode,
            use_pallas=use_pallas,
            remat=remat,
        )
        aux_total = aux_total + aux
        if new_cache is not None:
            new_cache.append(seg_new_cache)

    h = apply_norm(cfg, params["final_norm"], h)
    if logits_mode == "hidden":
        return h, aux_total, new_cache
    if logits_mode == "last":
        h = h[:, -1:]
    logits = project_logits(cfg, params, h)
    return logits, aux_total, new_cache


def project_logits(cfg: ModelConfig, params: dict, h: jax.Array) -> jax.Array:
    compute_dtype = h.dtype
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(compute_dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(compute_dtype))
    if cfg.padded_vocab != cfg.vocab_size:
        logits = logits[..., : cfg.vocab_size]
    return logits


def _prefill_placeholder_cache(cfg, segs):
    """Prefill computes the cache from scratch; placeholder triggers cache outputs."""
    out = []
    for seg in segs:
        body = {f"pos{p}": {"mixer": {}} for p in range(seg.period)}
        out.append(body)
    return out
