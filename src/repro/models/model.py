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
from repro.models import transformer
from repro.models.common import init_params, param_axes, param_shapes
from repro.obs import programs


def chunked_cross_entropy(
    cfg,
    params,
    h: jax.Array,  # (B, S, D) pre-head hidden states
    labels: jax.Array,  # (B, S) int32; -1 = ignore
    z_loss: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """LM-head + softmax-CE fused over sequence chunks: the (B, chunk, V) logits block
    is the only vocab-sized temp ever materialized (~1 GB cap), instead of (B, S, V).
    The backward pass recomputes per-chunk logits (checkpointed scan)."""
    from repro.models.transformer import project_logits

    B, S, D = h.shape
    V = cfg.vocab_size
    # chunk size: largest power-of-two divisor of S with B*chunk*V*4B <= ~1 GB
    budget = max(1, (1 << 30) // max(1, B * V * 4))
    chunk = 1
    while chunk * 2 <= min(budget, 512) and S % (chunk * 2) == 0:
        chunk *= 2
    if S % chunk:
        chunk = 1
    n = S // chunk

    hc = jnp.moveaxis(h.reshape(B, n, chunk, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    def body(carry, inp):
        nll_sum, zsq_sum, acc_sum, n_valid = carry
        h_b, lab = inp
        logits = project_logits(cfg, params, h_b).astype(jnp.float32)  # (B, chunk, V)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        valid = lab >= 0
        safe = jnp.where(valid, lab, 0)
        ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, lse - ll, 0.0)
        zsq = jnp.where(valid, jnp.square(lse), 0.0)
        acc = jnp.where(valid, jnp.argmax(logits, -1) == safe, False)
        return (
            nll_sum + nll.sum(),
            zsq_sum + zsq.sum(),
            acc_sum + acc.sum().astype(jnp.float32),
            n_valid + valid.sum(),
        ), None

    body = jax.checkpoint(body, prevent_cse=False)
    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    (nll_sum, zsq_sum, acc_sum, n_valid), _ = jax.lax.scan(body, init, (hc, lc))

    n_valid_f = jnp.maximum(n_valid, 1).astype(jnp.float32)
    ce = nll_sum / n_valid_f
    metrics = {"ce": ce, "n_tokens": n_valid_f, "accuracy": acc_sum / n_valid_f}
    loss = ce
    if z_loss:
        zl = z_loss * zsq_sum / n_valid_f
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
        return transformer.forward(
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
        loss, metrics = chunked_cross_entropy(self.cfg, params, h, labels, self.cfg.z_loss)
        if self.cfg.is_moe:
            loss = loss + self.cfg.router_aux_coef * aux
            metrics["moe_aux"] = aux
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
