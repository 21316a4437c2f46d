"""Mixture-of-Experts FFNs.

:func:`moe_ffn`: shared + routed experts, top-k routing with capacity, scatter-based
dispatch (memory-safe for fine-grained 64-expert configs), load-balance auxiliary
loss. Experts shard over the 'experts' logical axis (expert parallelism).

:func:`moe_share_ffn`: DeepSeek-V3's layer over an expert share, with no capacity
and no dropped token. The layer holds experts ``[first, first + held)`` of
``n_experts`` (one chip's share under expert parallelism), routes every token over
all of them, and computes its own experts' part of the result for the slots
routed to them, through a grouped matrix product over the held experts (the
megablox ``gmm`` kernel), plus the shared experts. That partial sum goes on to the
next layer; the absent experts' part is another chip's, and nothing here stands
in for it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamDesc, activation_fn

HIGHEST = jax.lax.Precision.HIGHEST

# the loss's metrics that carry a step's slots per expert of the expert-share
# layers; they add up over a step's micro-batches (core/federated), and the
# client step takes them out before the metrics are reduced
ROUTER_LOAD = "router_load"  # a tree at the router biases' paths, (…, n_experts)
HELD_LOAD = "router_held_load"  # (MoE layers, held experts)
ROUTED_ROWS = "router_routed_rows"  # (MoE layers,) rows the routed parts were sized for


def dense_ffn_desc(cfg, d_ff: int, n_copies: int = 1) -> dict:
    d = cfg.d_model
    dff = d_ff * n_copies
    if cfg.activation == "silu":  # SwiGLU
        return {
            "w_in": ParamDesc((d, dff), (None, "ffn"), "normal"),
            "w_gate": ParamDesc((d, dff), (None, "ffn"), "normal"),
            "w_out": ParamDesc((dff, d), ("ffn", None), "normal", 0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
        }
    return {
        "w_in": ParamDesc((d, dff), (None, "ffn"), "normal"),
        "w_out": ParamDesc((dff, d), ("ffn", None), "normal", 0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }


def dense_ffn(cfg, p: dict, x: jax.Array) -> jax.Array:
    act = activation_fn(cfg.activation)
    h = jnp.einsum("...d,df->...f", x, p["w_in"].astype(x.dtype))
    if "w_gate" in p:
        g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
        h = act(g) * h
    else:
        h = act(h)
    return jnp.einsum("...f,fd->...d", h, p["w_out"].astype(x.dtype))


def moe_ffn_desc(cfg) -> dict:
    d = cfg.d_model
    e = cfg.n_experts
    dff = cfg.moe_d_ff or cfg.d_ff
    p = {
        "router": ParamDesc((d, e), (None, None), "normal"),
        "w_in": ParamDesc((e, d, dff), ("experts", None, None), "normal"),
        "w_gate": ParamDesc((e, d, dff), ("experts", None, None), "normal"),
        "w_out": ParamDesc((e, dff, d), ("experts", None, None), "normal", 0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }
    if cfg.n_shared_experts:
        p["shared"] = dense_ffn_desc(cfg, dff, cfg.n_shared_experts)
    return p


def moe_ffn(
    cfg, p: dict, x: jax.Array, capacity_factor: float = None
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux load-balance loss scalar)."""
    from repro.models.common import shard_hint

    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    T = B * S
    xt = shard_hint(x.reshape(T, D), "model", None)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)  # renorm (deepseek)

    # Load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    onehot_k = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (T, K, E)
    tokens_per_expert = onehot_k.sum((0, 1)) / (T * K)  # f_e
    router_prob = probs.mean(0)  # P_e
    aux = E * jnp.sum(tokens_per_expert * router_prob)

    # Capacity-based dispatch via cumsum position-in-expert + scatter.
    if capacity_factor is None:
        capacity_factor = getattr(cfg, "moe_capacity_factor", 1.25)
    capacity = max(1, int(T * K * capacity_factor / E))
    flat_idx = expert_idx.reshape(T * K)  # route slots, ordered by token then k
    flat_gate = gate_vals.reshape(T * K)
    oh = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)  # (T*K, E)
    pos_in_expert = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1  # (T*K,)
    keep = pos_in_expert < capacity
    safe_pos = jnp.where(keep, pos_in_expert, 0)

    token_of_slot = jnp.repeat(jnp.arange(T), K)
    contrib = jnp.where(keep, flat_gate, 0.0)

    # Scatter tokens into (E, capacity, D) expert buffers. Slot arrays shard over the
    # within-client TP ('model') axis and expert buffers shard over experts ('model'):
    # the slot->expert scatter and expert->slot gather become the canonical MoE
    # all-to-all instead of replicating token-slot tensors.
    buf = jnp.zeros((E, capacity, D), x.dtype)
    src = shard_hint(
        xt[token_of_slot] * keep[:, None].astype(x.dtype), "model", None
    )
    buf = shard_hint(buf.at[flat_idx, safe_pos].add(src), "model", None, None)

    act = activation_fn(cfg.activation)

    @jax.checkpoint
    def expert_ffn(buf_, w_in, w_gate, w_out):
        # checkpointed: the (E, cap, d_ff) hiddens are recomputed in the backward
        # pass instead of living as residuals — they are the widest buffers of
        # fine-grained MoE layers.
        h_in = jnp.einsum("ecd,edf->ecf", buf_, w_in)
        h_gate = jnp.einsum("ecd,edf->ecf", buf_, w_gate)
        h = shard_hint(act(h_gate) * h_in, "model", None, None)
        return jnp.einsum("ecf,efd->ecd", h, w_out)

    out_buf = shard_hint(
        expert_ffn(
            buf,
            p["w_in"].astype(x.dtype),
            p["w_gate"].astype(x.dtype),
            p["w_out"].astype(x.dtype),
        ),
        "model", None, None,
    )

    # Combine: gather each slot's expert output, weight by gate, sum over K.
    slot_out = shard_hint(
        out_buf[flat_idx, safe_pos] * contrib[:, None].astype(x.dtype), "model", None
    )  # (T*K, D)
    yt = slot_out.reshape(T, K, D).sum(1)

    if cfg.n_shared_experts:
        yt = yt + dense_ffn(cfg, p["shared"], xt)

    return yt.reshape(B, S, D), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# DeepSeek-V3 routing over an expert share, dropless
# ---------------------------------------------------------------------------


def moe_share_desc(cfg) -> dict:
    d, held = cfg.d_model, cfg.moe_experts_held
    dff = cfg.moe_d_ff or cfg.d_ff
    out_scale = 0.02 / max(1, 2 * cfg.n_layers) ** 0.5
    p = {
        "router": ParamDesc((d, cfg.n_experts), (None, None), "normal"),
        # moved by the load rule, not by the gradient (core/inner_opt.inner_update)
        "router_bias": ParamDesc((cfg.n_experts,), (None,), "zeros"),
        "w_in": ParamDesc((held, d, dff), ("experts", None, None), "normal"),
        "w_gate": ParamDesc((held, d, dff), ("experts", None, None), "normal"),
        "w_out": ParamDesc((held, dff, d), ("experts", None, None), "normal", out_scale),
    }
    if cfg.n_shared_experts:
        p["shared"] = dense_ffn_desc(cfg, dff, cfg.n_shared_experts)
    return p


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _tile(n: int, candidates) -> int:
    return next((c for c in candidates if n % c == 0), n)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array, first: int,
                   interpret=None) -> jax.Array:
    """``x`` (m, k) rows sorted by group, ``group_sizes`` (G,) int32, ``w`` (g, k, n)
    the weights of groups ``[first, first + g)``: each of those groups' rows
    times its own weights. The kernel skips every other row's tiles. Where
    ``g < G`` the other groups' rows are zeroed; where ``g == G`` the rows past
    ``Σ group_sizes`` are never written and hold whatever the buffer held, so a
    caller masks them (``jnp.where``, as a product with 0 keeps a NaN). The
    megablox ``gmm`` kernel with its backward (interpret mode on a CPU host)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    if interpret is None:
        interpret = _on_cpu()
    (m, k), n = x.shape, w.shape[2]
    tiling = (_tile(m, (128, 64, 32, 16, 8)), _tile(k, (512, 384, 256, 128)),
              _tile(n, (512, 384, 256, 128)))
    return megablox.gmm(x, w, group_sizes, x.dtype, tiling,
                        jnp.asarray(first, jnp.int32), None, False, interpret)


def trip_rows(tokens: int, top_k: int, held: int, n_experts: int) -> int:
    """R, the rows of one trip of the routed part: twice the held experts' even
    share of the ``tokens · top_k`` slots, rounded up to 512, and at most all
    of them (one trip where every expert is held)."""
    slots = tokens * top_k
    return min(slots, -(-2 * slots * held // (n_experts * 512)) * 512)


def _trip(c, rows: int, tok, gate, sizes):
    """Rows ``[c·R, c·R + R)`` of the live run: each row's token and gate, whether
    it is live, and the held experts' group sizes within the trip. Rows past
    the run keep a real token and take a zero gate."""
    with jax.named_scope("dispatch"):
        start = c * rows
        ends = jnp.clip(jnp.cumsum(sizes) - start, 0, rows)
        live = jnp.arange(rows) < ends[-1]
        gate = jnp.where(live, jax.lax.dynamic_slice_in_dim(gate, start, rows), 0.0)
        return (jax.lax.dynamic_slice_in_dim(tok, start, rows), gate, live,
                jnp.diff(ends, prepend=0).astype(jnp.int32))


def _trip_experts(act, xs, g, live, w_in, w_gate, w_out, sizes):
    """A trip's rows through their held experts' SwiGLU, times their gates, in
    float32; 0 on rows that are not live, whatever the kernel left there."""
    with jax.named_scope("experts"):
        mm = lambda a, w: grouped_matmul(a, w, sizes, 0)  # noqa: E731
        ys = mm(act(mm(xs, w_gate)) * mm(xs, w_in), w_out)
        ys = jnp.where(live[:, None], ys, 0).astype(jnp.float32)
    with jax.named_scope("combine"):
        return ys * g.astype(xs.dtype).astype(jnp.float32)[:, None]


def _trips(rows: int, sizes):
    """The trips the live run of ``Σ sizes`` rows takes, at least one."""
    return jnp.maximum(1, -(-jnp.sum(sizes) // rows))


def _over_trips(rows: int, sizes, trip, init):
    """``trip(c, carry)`` over the live run's trips, at least one. The first runs
    outside the loop, so that XLA can fold its sums into ``init``'s zeros."""
    return jax.lax.fori_loop(1, _trips(rows, sizes), trip, trip(0, init))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed(rows, act, xt, gate, w_in, w_gate, w_out, tok, sizes):
    """The held experts' part of the layer, (T, D): the live run's rows (``tok``
    and ``gate`` in sorted order, ``sizes`` the held experts' groups at its
    front), ``rows`` at a time, each trip gathered, computed and added into a
    float32 ``(T, D)`` sum by token."""
    with jax.named_scope("experts"):
        w = [v.astype(xt.dtype) for v in (w_in, w_gate, w_out)]

    def trip(c, acc):
        t, g, live, gs = _trip(c, rows, tok, gate, sizes)
        with jax.named_scope("dispatch"):
            xs = xt[t]
        ys = _trip_experts(act, xs, g, live, *w, gs)
        with jax.named_scope("combine"):
            return acc.at[t].add(ys)

    return _over_trips(rows, sizes, trip, jnp.zeros(xt.shape, jnp.float32)).astype(xt.dtype)


def _routed_fwd(rows, act, xt, gate, w_in, w_gate, w_out, tok, sizes):
    res = (xt, gate, w_in, w_gate, w_out, tok, sizes)
    return _routed(rows, act, *res), res


def _routed_bwd(rows, act, res, dy):
    """The same trips again: each gathers ``dy``'s and ``x``'s rows, recomputes
    its products and takes their VJP, adding ``dx`` rows into a float32 ``(T, D)``
    sum, the weights' gradients into float32 sums, and writing the live rows'
    gate gradients."""
    xt, gate, w_in, w_gate, w_out, tok, sizes = res
    ws = (w_in, w_gate, w_out)
    with jax.named_scope("experts"):
        w = [v.astype(xt.dtype) for v in ws]

    def trip(c, carry):
        dx, dgate, dw = carry
        t, g, live, gs = _trip(c, rows, tok, gate, sizes)
        with jax.named_scope("combine"):
            dys = dy[t].astype(jnp.float32)
        with jax.named_scope("dispatch"):
            xs = xt[t]
        _, vjp = jax.vjp(lambda xs, g, *w: _trip_experts(act, xs, g, live, *w, gs), xs, g, *w)
        dxs, dg, *dw_t = vjp(dys)
        with jax.named_scope("dispatch"):
            dx = dx.at[t].add(jnp.where(live[:, None], dxs, 0).astype(jnp.float32))
        with jax.named_scope("combine"):  # 0 on rows that are not live: ys is 0 there
            dgate = jax.lax.dynamic_update_slice_in_dim(dgate, dg, c * rows, 0)
        with jax.named_scope("experts"):
            return dx, dgate, [a + b.astype(jnp.float32) for a, b in zip(dw, dw_t)]

    dx, dgate, dw = _over_trips(rows, sizes, trip, (
        jnp.zeros(xt.shape, jnp.float32), jnp.zeros_like(gate),
        [jnp.zeros(v.shape, jnp.float32) for v in ws]))
    return (dx.astype(xt.dtype), dgate, *[d.astype(v.dtype) for d, v in zip(dw, ws)],
            None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


def moe_share_ffn(cfg, p: dict, x: jax.Array):
    """Returns ``(y, balance, load, rows)``: the layer's output (B, S, D) from the
    held experts and the shared ones; the sequence-wise balance loss, the mean
    over sequences of ``Σ_i f_i·P_i`` with ``f_i = E/(K·S)·#{t : i chosen for
    t}`` and ``P_i`` the mean over t of ``s_i,t / Σ_j s_j,t``; the step's slots
    routed to each of the ``n_experts`` experts, float32 (E,); and the rows the
    routed part was sized for, float32.

    Routing, per token, in float32: ``s = sigmoid(h·W_r)``, the top-K of
    ``s + b`` chosen (``b`` the correction bias), gates ``g_i = scale · s_i /
    Σ_chosen s_j``. Every slot whose expert is held goes to it. Those live
    slots are sorted to the front, grouped by held expert, and only they are
    gathered, computed and added back, ``trip_rows`` at a time (``_routed``):
    no tensor of all T·K slots' rows is built."""
    B, S, D = x.shape
    E, K, first, held = cfg.n_experts, cfg.moe_top_k, cfg.moe_first_expert, cfg.moe_experts_held
    T = B * S
    xt = x.reshape(T, D)
    act = activation_fn(cfg.activation)
    rows = trip_rows(T, K, held, E)
    pad = -(-T * K // rows) * rows - T * K  # the last trip's rows past the slots
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            s = jax.nn.sigmoid(jnp.einsum(
                "td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                precision=HIGHEST))
            _, idx = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), K)  # (T, K)
            chosen = jnp.take_along_axis(s, idx, axis=-1)
            gate = cfg.moe_routed_scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
            hits = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1)  # (T, E)
            f = jnp.sum(hits.reshape(B, S, E), axis=1) * (E / (K * S))
            share = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(B, S, E), axis=1)
            balance = jnp.mean(jnp.sum(f * share, axis=-1))
        with jax.named_scope("dispatch"):
            slots = idx.reshape(T * K)
            # the held experts' slots first, grouped by expert, in slot order
            order = jnp.argsort((slots - first) % E, stable=True)
            group_sizes = jnp.bincount(slots, length=E).astype(jnp.int32)
            sizes = group_sizes[first:first + held]
            tok = jnp.pad(order // K, (0, pad))
            gate_sorted = jnp.pad(gate.reshape(T * K)[order], (0, pad))
        y = _routed(rows, act, xt, gate_sorted, p["w_in"], p["w_gate"], p["w_out"], tok, sizes)
        if cfg.n_shared_experts:
            with jax.named_scope("shared"):
                y = y + dense_ffn(cfg, p["shared"], xt)
    n_rows = (_trips(rows, sizes) * rows).astype(jnp.float32)
    return y.reshape(B, S, D), balance, group_sizes.astype(jnp.float32), n_rows


def held_loads(cfg, loads) -> jax.Array:
    """The held experts' slots, (MoE layers, held), from ``loads``: a tree at
    the params' own paths of each expert-share layer's slots per expert,
    ``(…, n_experts)`` under the router bias's path."""
    first, held = cfg.moe_first_expert, cfg.moe_experts_held
    return jnp.concatenate([l.reshape(-1, l.shape[-1])[:, first:first + held]
                            for l in jax.tree_util.tree_leaves(loads)])
