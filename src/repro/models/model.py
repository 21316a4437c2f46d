"""Public model API: a lightweight functional facade over the transformer engine.

    model = Model(cfg)
    params = model.init(rng)
    logits, aux, _ = model.forward(params, batch)
    loss, metrics = model.loss(params, batch)
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.models.common import init_params, param_axes, param_shapes
from repro.obs import programs


HEAD_ROWS = 512  # sequence positions whose full-vocabulary logits one head tile may hold
HEAD_TILE_BYTES = 1 << 30  # and at most this many bytes of f32 logits


def head_tiles(seq_len: int, tokens: int, vocab: int) -> Tuple[Tuple[int, int], ...]:
    """Static vocabulary tiles ``[v0, v1)`` of the LM head for ``tokens`` positions of
    ``seq_len``-long sequences: as few as keep the f32 ``(tokens, v1 - v0)`` logits
    tile within ``HEAD_ROWS`` positions' worth of full-vocabulary logits and
    ``HEAD_TILE_BYTES``, each as wide rounded up to 128 rows (the lanes); the last
    tile takes what is left."""
    n = max(1, -(-seq_len // HEAD_ROWS), -(-tokens * vocab * 4 // HEAD_TILE_BYTES))
    width = 128 * -(-vocab // (128 * n))
    return tuple((v0, min(v0 + width, vocab)) for v0 in range(0, vocab, width))


def _tile(tied: bool, table, v0: int, v1: int, dtype):
    """Rows ``[v0, v1)`` of the head's table in ``dtype``, and their einsum
    subscripts: the embedding ``(V_pad, d)`` when ``tied``, else ``(d, V_pad)``."""
    if tied:
        return table[v0:v1].astype(dtype), "vd"
    return table[:, v0:v1].astype(dtype), "dv"


def _tile_logits(w, ws: str, h):
    """One tile's logits as ``transformer.project_logits`` gives them (operands and
    product in ``h``'s dtype, bf16 in training), in f32."""
    return jnp.einsum(f"td,{ws}->tv", h, w).astype(jnp.float32)


def _after(x, *done):
    """``x`` and ``done`` back, with ``x`` usable only once ``done`` is computed:
    the next tile's logits wait for this tile's results, so that one tile's
    logits are alive at a time (XLA would otherwise compute several first)."""
    return jax.lax.optimization_barrier((x,) + done)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _head_stats(tiles, tied, h, table, labels):
    """Per position: the logsumexp over the vocabulary, the label's logit (0 where
    the label is -1) and the argmax (ties to the lowest index). Each tile is
    reduced on its own (its max, its exp-sum below that max, the label's logit,
    its argmax), so that its logits are freed before the next tile's exist; the
    ``(T,)`` results are then combined."""
    stats = []  # per tile: max, exp-sum below it, label logit, argmax
    for v0, v1 in tiles:
        logits = _tile_logits(*_tile(tied, table, v0, v1, h.dtype), h)
        m_t = logits.max(-1)
        hit = jnp.arange(v0, v1)[None, :] == labels[:, None]
        stats.append((m_t, jnp.exp(logits - m_t[:, None]).sum(-1),
                      jnp.where(hit, logits, 0.0).sum(-1),
                      jnp.argmax(logits, -1).astype(jnp.int32) + v0))
        h, *_ = _after(h, *stats[-1])
    m, _, ll, arg = stats[0]
    for m_t, _, ll_t, arg_t in stats[1:]:
        arg = jnp.where(m_t > m, arg_t, arg)  # strict: ties keep the lower index
        m, ll = jnp.maximum(m, m_t), ll + ll_t
    s = sum(s_t * jnp.exp(m_t - m) for m_t, s_t, _, _ in stats)
    return m + jnp.log(s), ll, arg


def _head_stats_fwd(tiles, tied, h, table, labels):
    lse, ll, arg = _head_stats(tiles, tied, h, table, labels)
    return (lse, ll, arg), (h, table, labels, lse)


def _head_stats_bwd(tiles, tied, res, cts):
    """Recomputes each tile's logits; its ``dl = g_lse·softmax + g_ll·onehot`` (bf16)
    gives that tile's rows of the table's gradient in one f32 product, written
    in place once, and adds ``dl @ W_t`` into an f32 ``(T, d)`` carry for ``dh``.
    Rows past the last tile (the padded vocabulary) get exactly 0."""
    g_lse, g_ll, _ = cts
    # the barrier keeps XLA from merging the recompute with the forward's logits,
    # which would hold every tile's logits from the forward to here
    h, table, labels, lse = jax.lax.optimization_barrier(res)
    axis = 0 if tied else 1
    with jax.named_scope("head"):
        dh = jnp.zeros(h.shape, jnp.float32)
        dw = jax.lax.empty(table.shape, jnp.float32)  # every row is written below
        for v0, v1 in tiles:
            w, ws = _tile(tied, table, v0, v1, h.dtype)
            logits = _tile_logits(w, ws, h)
            hit = jnp.arange(v0, v1)[None, :] == labels[:, None]
            dl = (g_lse[:, None] * jnp.exp(logits - lse[:, None])
                  + jnp.where(hit, g_ll[:, None], 0.0)).astype(h.dtype)
            dw_t = jnp.einsum(f"tv,td->{ws}", dl, h, preferred_element_type=jnp.float32)
            dw = jax.lax.dynamic_update_slice_in_dim(dw, dw_t, v0, axis)
            dh = dh + jnp.einsum(f"tv,{ws}->td", dl, w, preferred_element_type=jnp.float32)
            h, dh, dw = _after(h, dh, dw)
        pad = table.shape[axis] - tiles[-1][1]
        if pad:
            zeros = jnp.zeros(table.shape[:axis] + (pad,) + table.shape[axis + 1:], jnp.float32)
            dw = jax.lax.dynamic_update_slice_in_dim(dw, zeros, tiles[-1][1], axis)
    return dh.astype(h.dtype), dw.astype(table.dtype), None


_head_stats.defvjp(_head_stats_fwd, _head_stats_bwd)


def tiled_cross_entropy(
    cfg,
    params,
    h: jax.Array,  # (B, S, D) pre-head hidden states
    labels: jax.Array,  # (B, S) int32; -1 = ignore
    z_loss: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """LM head + softmax cross-entropy, tiled over the vocabulary (``head_tiles``):
    one tile's ``(B·S, V_t)`` logits block exists at a time, reduced to its
    max, exp-sum, label logit and argmax, which combine into the logsumexp, the
    label's logit and the argmax; only those ``(B·S,)`` vectors are kept. The
    backward pass (a ``jax.custom_vjp``) recomputes each tile's logits and
    writes that tile's rows of the table's f32 gradient once, with ``dh``
    carried in f32. Padded vocabulary rows are never scored and get a zero
    gradient. ``ce``, ``z_loss`` (z·lse²) and ``accuracy`` are those of
    :func:`cross_entropy` on the full logits."""
    B, S, D = h.shape
    with jax.named_scope("head"):
        tiles = head_tiles(S, B * S, cfg.vocab_size)
        table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        lab = labels.reshape(B * S)
        lse, ll, arg = _head_stats(tiles, cfg.tie_embeddings, h.reshape(B * S, D), table, lab)
        valid = lab >= 0
        n_valid = jnp.maximum(valid.sum(), 1).astype(jnp.float32)
        ce = jnp.where(valid, lse - ll, 0.0).sum() / n_valid
        acc = jnp.where(valid, arg == lab, False).sum().astype(jnp.float32) / n_valid
        metrics = {"ce": ce, "n_tokens": n_valid, "accuracy": acc}
        loss = ce
        if z_loss:
            zl = z_loss * jnp.where(valid, jnp.square(lse), 0.0).sum() / n_valid
            loss = loss + zl
            metrics["z_loss"] = zl
    return loss, metrics


def cross_entropy(
    logits: jax.Array,  # (B, S, V)
    labels: jax.Array,  # (B, S) int32; -1 = ignore
    z_loss: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    valid = labels >= 0
    safe_labels = jnp.where(valid, labels, 0)
    label_logits = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = lse - label_logits
    n_valid = jnp.maximum(valid.sum(), 1)
    ce = jnp.where(valid, nll, 0.0).sum() / n_valid
    metrics = {"ce": ce, "n_tokens": n_valid.astype(jnp.float32)}
    loss = ce
    if z_loss:
        zl = z_loss * jnp.where(valid, jnp.square(lse), 0.0).sum() / n_valid
        loss = loss + zl
        metrics["z_loss"] = zl
    acc = jnp.where(valid, jnp.argmax(logits, -1) == safe_labels, False).sum() / n_valid
    metrics["accuracy"] = acc.astype(jnp.float32)
    return loss, metrics


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._desc = transformer.model_desc(cfg)

    # -- parameters -----------------------------------------------------
    def desc(self):
        return self._desc

    def init(self, rng: jax.Array, dtype=None):
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return init_params(rng, self._desc, dtype)

    def axes(self):
        return param_axes(self._desc)

    def shapes(self):
        return param_shapes(self._desc)

    def abstract_params(self, dtype=None):
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        from repro.models.common import is_desc

        return jax.tree_util.tree_map(
            lambda d: jax.ShapeDtypeStruct(d.shape, dtype), self._desc, is_leaf=is_desc
        )

    # -- forward / loss ---------------------------------------------------
    def forward(
        self,
        params,
        batch: Dict[str, jax.Array],
        *,
        mode: str = "train",
        cache=None,
        cache_index=None,
        remat: bool = False,
        use_pallas: bool = False,
    ):
        """Returns ``(logits, aux_loss, new_cache)``."""
        logits, aux, new_cache = transformer.forward(
            self.cfg,
            params,
            batch["tokens"],
            audio_embed=batch.get("audio_embed"),
            mode=mode,
            cache=cache,
            cache_index=cache_index,
            remat=remat,
            use_pallas=use_pallas,
        )
        return logits, aux["loss"], new_cache

    def loss(
        self, params, batch: Dict[str, jax.Array], *, remat: bool = False,
        use_pallas: bool = False,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Next-token LM loss. batch['tokens'] (B,S); optional batch['loss_mask']."""
        tokens = batch["tokens"]
        h, aux, _ = transformer.forward(
            self.cfg,
            params,
            tokens,
            audio_embed=batch.get("audio_embed"),
            mode="train",
            remat=remat,
            use_pallas=use_pallas,
            logits_mode="hidden",
        )
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.full((tokens.shape[0], 1), -1, tokens.dtype)], axis=1
        )
        if "loss_mask" in batch:
            labels = jnp.where(batch["loss_mask"] > 0, labels, -1)
        loss, metrics = tiled_cross_entropy(self.cfg, params, h, labels, self.cfg.z_loss)
        if self.cfg.is_moe:
            loss = loss + self.cfg.router_aux_coef * aux["loss"]
            metrics["moe_aux"] = aux["loss"]
        if aux["router_load"]:  # a step's slots per expert, for the load rule and counters
            metrics[moe_mod.ROUTER_LOAD] = aux["router_load"]
            metrics[moe_mod.HELD_LOAD] = moe_mod.held_loads(self.cfg, aux["router_load"])
        metrics["loss"] = loss
        return loss, metrics

    # -- jitted evaluation, built once per model -------------------------
    @functools.cached_property
    def eval_ce(self):
        """Jitted ``(params, batch) -> mean cross-entropy``. One function per
        model, so repeated evaluations reuse its compiled executables."""
        def eval_ce(p, b):
            return self.loss(p, b)[1]["ce"]

        return programs.register(jax.jit(eval_ce))

    @functools.cached_property
    def jit_forward(self):
        """Jitted ``forward(params, batch)``, one per model."""
        return jax.jit(lambda p, b: self.forward(p, b))

    # -- serving ----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return transformer.init_cache(self.cfg, batch, max_len, dtype)

    def prefill(self, params, batch, *, use_pallas: bool = False):
        """Fills the cache; returns next-token logits (last position only — the full
        (B, S, V) logits tensor is never materialized)."""
        logits, _, cache = transformer.forward(
            self.cfg,
            params,
            batch["tokens"],
            audio_embed=batch.get("audio_embed"),
            mode="prefill",
            use_pallas=use_pallas,
            logits_mode="last",
        )
        return logits, cache

    def decode_step(self, params, cache, tokens, cache_index, *, use_pallas: bool = False):
        """tokens: (B, 1) — one new token per sequence; cache_index: scalar position."""
        logits, _, new_cache = self.forward(
            params,
            {"tokens": tokens},
            mode="decode",
            cache=cache,
            cache_index=cache_index,
            use_pallas=use_pallas,
        )
        return logits, new_cache


def build_model(name_or_cfg) -> Model:
    if isinstance(name_or_cfg, str):
        from repro.configs import get_config

        return Model(get_config(name_or_cfg))
    return Model(name_or_cfg)
