"""Plain float32 reference of the MPT-style ALiBi decoder (Photon's model family).

Written from the published descriptions, not from the program under test:
MPT (MosaicML, "Introducing MPT-7B", and its `llm-foundry` model card: pre-norm
decoder blocks, no positional embedding, ALiBi attention bias, GELU MLP of
width 4·d_model, tied input and output embedding) and ALiBi (Press et al.,
"Train Short, Test Long", ICLR 2022: head slopes 2^(-8i/n), with the
interleaving rule for a head count that is not a power of two).

Departures from the published MPT description, each because the configuration
as run states it (see the configuration files under ``bench/configs``):

- LayerNorm keeps its additive bias (MPT's ``no_bias`` drops it);
- the MLP uses GELU's tanh approximation (MPT uses the exact erf form);
- the loss adds a z-loss term, ``z_loss · mean(logsumexp²)``;
- the embedding table holds ``padded_vocab`` rows (the vocabulary rounded up
  to 256); rows past ``vocab_size`` are never looked up and never scored;
- the ALiBi bias is written as ``-slope·(q - k)``; MPT writes
  ``-slope·(S-1-k)``, which differs by a per-query constant that softmax
  cancels.

Everything is float32. Every contraction goes through one ``einsum`` hook, so
the same model runs in a lower precision for the control (``precision="fp8"``:
forward operands quantised to float8_e4m3 and the incoming gradient to
float8_e5m2, each scaled per tensor by its absolute maximum, with float32
accumulation), and in the configuration's own bfloat16 operands with float32
accumulation (``precision="bf16"``), which shows how far rounding alone
carries a run from the float32 one. Layers are rematerialised one at a time so the reference fits
beside nothing else on one chip at the cells' sizes.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Weights: the configuration's seeded initialisation
# ---------------------------------------------------------------------------


def weight_layout(cfg: dict) -> dict:
    """Tree of ``(shape, init, scale)`` per weight, named as the deployment
    names its weights: the layers are stacked on a leading axis of length
    ``n_layers`` under ``segments[0].pos0``."""
    L, d, h, f = cfg["n_layers"], cfg["d_model"], cfg["n_heads"], cfg["d_ff"]
    hd = d // h
    out_scale = 0.02 / max(1, 2 * L) ** 0.5
    norm = lambda: {"scale": ((L, d), "ones", 0.0), "bias": ((L, d), "zeros", 0.0)}
    layer = {
        "norm1": norm(),
        "mixer": {
            "wq": ((L, d, h, hd), "normal", 0.02),
            "wk": ((L, d, h, hd), "normal", 0.02),
            "wv": ((L, d, h, hd), "normal", 0.02),
            "wo": ((L, h, hd, d), "normal", out_scale),
        },
        "norm2": norm(),
        "ffn": {
            "w_in": ((L, d, f), "normal", 0.02),
            "w_out": ((L, f, d), "normal", out_scale),
        },
    }
    return {
        "embed": ((cfg["padded_vocab"], d), "normal", 0.02),
        "segments": [{"pos0": layer}],
        "final_norm": {"scale": ((d,), "ones", 0.0), "bias": ((d,), "zeros", 0.0)},
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init_weights(cfg: dict, seed: int) -> dict:
    """Weights from the seed: each leaf is drawn from its own key,
    ``fold_in(PRNGKey(seed), crc32(path))``, as N(0, 1)·scale in float32
    (ones and zeros for the norms)."""
    root = jax.random.PRNGKey(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_layout(cfg), is_leaf=_is_spec
    )
    leaves = []
    for path, (shape, kind, scale) in flat:
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        if kind == "ones":
            leaves.append(jnp.ones(shape, jnp.float32))
        elif kind == "zeros":
            leaves.append(jnp.zeros(shape, jnp.float32))
        else:
            leaves.append((scale * jax.random.normal(key, shape)).astype(jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Contractions: float32 or the fp8 control
# ---------------------------------------------------------------------------


def _quantise(x, dtype):
    """Per-tensor scaled cast to a float8 type and back to float32."""
    fmax = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, fmax / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, _quantise(a, jnp.float8_e4m3fn),
                      _quantise(b, jnp.float8_e4m3fn), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    aq, bq = _quantise(a, jnp.float8_e4m3fn), _quantise(b, jnp.float8_e4m3fn)
    return jnp.einsum(spec, aq, bq, precision=HIGHEST), (aq, bq)


def _fp8_bwd(spec, res, g):
    aq, bq = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST), aq, bq)
    return vjp(_quantise(g, jnp.float8_e5m2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def einsum_for(precision: str):
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "bf16":
        return lambda spec, a, b: jnp.einsum(
            spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)
    if precision == "fp8":
        return _fp8_einsum
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# The decoder
# ---------------------------------------------------------------------------


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Press et al.: 2^(-8i/n) for n a power of two; otherwise the slopes of
    the nearest lower power of two followed by every other slope of twice it."""

    def pow2(n):
        return [2.0 ** (-8.0 * (i + 1) / n) for i in range(n)]

    if (n_heads & (n_heads - 1)) == 0:
        return np.asarray(pow2(n_heads), np.float32)
    low = 1 << (n_heads.bit_length() - 1)
    return np.asarray(pow2(low) + pow2(2 * low)[0::2][: n_heads - low], np.float32)


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(cfg, ein, bias, x, lp):
    eps = cfg["layernorm_eps"]
    hd = cfg["d_model"] // cfg["n_heads"]
    a = lp["mixer"]
    h = layer_norm(x, lp["norm1"], eps)
    q = ein("bsd,dhk->bshk", h, a["wq"])
    k = ein("bsd,dhk->bshk", h, a["wk"])
    v = ein("bsd,dhk->bshk", h, a["wv"])
    s = ein("bqhk,bshk->bhqs", q, k) / np.sqrt(hd) + bias
    p = jax.nn.softmax(s, axis=-1)
    o = ein("bhqs,bshk->bqhk", p, v)
    x = x + ein("bqhk,hkd->bqd", o, a["wo"])
    h = layer_norm(x, lp["norm2"], eps)
    f = ein("bsd,df->bsf", h, lp["ffn"]["w_in"])
    return x + ein("bsf,fd->bsd", gelu_tanh(f), lp["ffn"]["w_out"])


def attention_bias(n_heads: int, seq_len: int):
    """(1, H, S, S): -slope·(q - k) on and below the diagonal, -inf above.
    Built from iotas inside the trace, so no (S, S) constant enters the program."""
    pos = jnp.arange(seq_len)
    dist = (pos[:, None] - pos[None, :]).astype(jnp.float32)
    bias = -jnp.asarray(alibi_slopes(n_heads))[:, None, None] * dist[None]
    return jnp.where(dist[None] >= 0, bias, NEG_INF)[None]


def loss(cfg: dict, w: dict, tokens, loss_mask=None, precision: str = "f32"):
    """Mean next-token cross-entropy plus z-loss over the positions that have a
    next token (and, with ``loss_mask``, whose mask is set)."""
    ein = einsum_for(precision)
    B, S = tokens.shape
    x = w["embed"][tokens]
    bias = attention_bias(cfg["n_heads"], S)
    block = jax.checkpoint(lambda x, lp: (_block(cfg, ein, bias, x, lp), None))
    x, _ = jax.lax.scan(block, x, w["segments"][0]["pos0"])
    x = layer_norm(x, w["final_norm"], cfg["layernorm_eps"])
    logits = ein("bsd,vd->bsv", x, w["embed"][: cfg["vocab_size"]])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    labels = jnp.concatenate([tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    valid = jnp.broadcast_to(jnp.arange(S)[None, :] < S - 1, (B, S))
    if loss_mask is not None:
        valid = valid & loss_mask
    label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    n = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)
    ce = jnp.sum(jnp.where(valid, lse - label_logit, 0.0)) / n
    z = cfg["z_loss"] * jnp.sum(jnp.where(valid, jnp.square(lse), 0.0)) / n
    return ce + z
