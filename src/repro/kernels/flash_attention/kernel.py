"""Flash attention Pallas TPU kernels, forward and backward: online-softmax tiling
with explicit BlockSpec VMEM placement, GQA (KV blocks indexed by query head →
kv head), causal and sliding-window masking, and ALiBi biases built in the kernel.

Scores are computed transposed, keys on sublanes and queries on lanes: a tile is
``s[k, q] = K_blk · Q_blkᵀ`` of shape ``(block_k, block_q)``. The per-query
quantities (running max, denominator, logsumexp, ``rowsum(dO·O)``) are then
lane-dense ``(1, block_q)`` rows that broadcast over sublanes, and the softmax
reductions run over sublanes. Each tile is scaled (exactly, on the query
block, where the scale is a power of two), then the ALiBi bias
``-slope_h · (q_pos − k_pos)`` is subtracted, then it is masked: the order of
``models.attention.sdpa_chunked``. The bias comes from one ``(block_k,
block_q)`` tile of ``col − row``, fetched once a call, and a per-tile offset
folded into the per-query rows; no ``(S, S)`` bias exists in HBM.

Blocks that no query of the tile may see (above the causal diagonal, or out of
the window) do no work under ``pl.when``, and their index maps are clamped to
the nearest needed block, so Pallas issues no copy for them. Only tiles that
cross a mask boundary build a mask, and square diagonal tiles go in column
strips that skip the keys above the diagonal.

Forward grid ``(B, Hq, n_q, n_k)``; the key axis is sequential and carries the
running max, denominator and ``(head_dim, block_q)`` accumulator in VMEM. It
returns ``Oᵀ`` and the logsumexp of every query row.

Backward grid ``(B, Hq, n_k, n_q)``: one kernel recomputes each tile's
probabilities once and accumulates dK and dV for its key block over the query
blocks, and dQᵀ for the whole ``(b, h)`` sequence in a ``(head_dim, Sq)`` VMEM
accumulator written out after the last tile.

Matmul operands keep their input dtype (bf16 in the model) and accumulate in
float32; the softmax, its statistics and every accumulator are float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
VMEM_LIMIT = 64 << 20  # of the v5e's 128 MiB; the largest tiles' float32 temporaries need ~24

# Column strips of a diagonal tile (see _Tiling.run), measured on one v5e at
# S = 2048 with 1024 tiles: the forward is fastest at 2, the backward at 4.
FWD_STRIPS, BWD_STRIPS = 2, 4

_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b
_NN = (((1,), (0,)), ((), ()))  # a · b


@dataclasses.dataclass(frozen=True)
class _Tiling:
    """Static geometry of a call: which tiles are needed and which need a mask."""

    causal: bool
    window: Optional[int]
    q_offset: int
    block_q: int
    block_k: int
    n_q: int
    n_k: int
    sm_scale: float
    alibi: bool
    strips: int  # column strips of a diagonal tile (see run)

    def _bounds(self, iq, ik):
        q0 = self.q_offset + iq * self.block_q
        k0 = ik * self.block_k
        return q0, q0 + self.block_q - 1, k0, k0 + self.block_k - 1

    def visible(self, iq, ik):
        """Some query of tile (iq, ik) may see some key of it."""
        q0, q1, k0, k1 = self._bounds(iq, ik)
        ok = True
        if self.causal:
            ok = k0 <= q1
        if self.window is not None:
            ok = jnp.logical_and(ok, q0 - k1 < self.window)
        return ok

    def unmasked(self, iq, ik):
        """Every query of tile (iq, ik) sees every key of it."""
        q0, q1, k0, k1 = self._bounds(iq, ik)
        ok = True
        if self.causal:
            ok = k1 <= q0
        if self.window is not None:
            ok = jnp.logical_and(ok, q1 - k0 < self.window)
        return ok

    @property
    def may_mask(self) -> bool:
        return self.causal or self.window is not None

    def k_needed(self, iq, ik):
        """Key block to fetch at step ik of query block iq: ik clamped to the
        blocks the query block needs, so a skipped step re-uses a fetched block."""
        q0, q1, _, _ = self._bounds(iq, 0)
        if self.causal:
            ik = jnp.minimum(ik, jnp.minimum(q1 // self.block_k, self.n_k - 1))
        if self.window is not None:
            ik = jnp.maximum(ik, jnp.maximum(q0 - self.window + 1, 0) // self.block_k)
        return ik

    def q_needed(self, ik, iq):
        """Query block to fetch at step iq of key block ik (the backward's order)."""
        _, _, k0, k1 = self._bounds(0, ik)
        if self.causal:
            lo = jnp.maximum(k0 - self.q_offset, 0) // self.block_q
            iq = jnp.maximum(iq, jnp.minimum(lo, self.n_q - 1))
        if self.window is not None:
            hi = jnp.maximum(k1 + self.window - 1 - self.q_offset, 0) // self.block_q
            iq = jnp.minimum(iq, jnp.minimum(hi, self.n_q - 1))
        return iq

    @property
    def uses_rel(self) -> bool:
        """Whether tiles read the ``(block_k, block_q)`` float32 tile of
        ``col - row`` (made by :func:`_lead_args`), for ALiBi or masks."""
        return self.alibi or self.may_mask

    @property
    def q_scaled(self) -> bool:
        """The scale is a power of two (head_dim 4ⁿ): folded exactly into the
        query block instead of scaling each score."""
        return self.sm_scale == 2.0 ** round(math.log2(self.sm_scale))

    def query(self, q):
        return q * jnp.asarray(self.sm_scale, q.dtype) if self.q_scaled else q

    def scores(self, k, q, slope, rel, iq, ik, masked: bool):
        """``(s, shift)``: the scaled, biased, masked ``(block_k, block_q)``
        score tile is ``s - shift``, with ``shift`` a per-tile constant
        (ALiBi's ``slope·(q0 − k0)`` under causality, else 0) that the caller
        folds into its per-query rows. ``q`` comes from :meth:`query`, and
        ``rel`` is the matching part of the ``col - row`` tile."""
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        if not self.q_scaled:
            s = s * self.sm_scale
        if not (self.alibi or masked):
            return s, 0.0
        q0, _, k0, _ = self._bounds(iq, ik)
        off = (q0 - k0).astype(jnp.float32)  # q_pos - k_pos = rel + off
        shift = 0.0
        if self.alibi and self.causal:  # masked entries aside, q_pos >= k_pos
            s = s - slope * rel
            shift = slope * off
        elif self.alibi:
            s = s - slope * jnp.maximum(rel + off, 0.0)
        if masked:
            keep = True
            if self.causal:
                keep = rel >= -off
            if self.window is not None:
                keep = jnp.logical_and(keep, rel < self.window - off)
            s = jnp.where(keep, s, NEG_INF)
        return s, shift

    def run(self, body, iq, ik):
        """``body(rows, cols, masked)`` on the tiles that need it, over slices of
        the tile's keys and queries, with a mask only where one bites. With
        square causal tiles the masked tiles are the diagonal ones, and these
        go in ``strips`` column strips of width w, strip j over the keys it
        sees (rows ``[0, (j + 1)·w)``): a quarter of the tile is skipped at 2."""
        rows, cols = slice(0, self.block_k), slice(0, self.block_q)
        if not self.may_mask:
            body(rows, cols, False)
            return

        def partial():
            w = self.block_q // self.strips
            for j in range(self.strips):
                body(slice(0, (j + 1) * w) if self.strips > 1 else rows,
                     slice(j * w, (j + 1) * w), True)

        full = self.unmasked(iq, ik)
        pl.when(full)(lambda: body(rows, cols, False))
        pl.when(jnp.logical_and(self.visible(iq, ik), jnp.logical_not(full)))(partial)


def _slope(slopes_ref, h):
    return None if slopes_ref is None else slopes_ref[0, h]


def _split(refs, t: _Tiling):
    """``(slopes_ref, rel_ref, refs)``: a kernel's optional leading refs."""
    slopes_ref = refs[0] if t.alibi else None
    refs = refs[1:] if t.alibi else refs
    rel_ref = refs[0] if t.uses_rel else None
    return slopes_ref, rel_ref, refs[1:] if t.uses_rel else refs


def _fwd_kernel(*refs, t: _Tiling):
    slopes_ref, rel_ref, refs = _split(refs, t)
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    h, iq, ik = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    slope = _slope(slopes_ref, h)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(rows, cols, masked):
        v = v_ref[0, 0, rows]
        rel = None if rel_ref is None else rel_ref[rows, cols]
        s, shift = t.scores(k_ref[0, 0, rows], t.query(q_ref[0, 0, cols]), slope, rel,
                            iq, ik, masked)
        m_prev = m_scr[:, cols]  # (1, w)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True) - shift)
        alpha = jnp.exp(m_prev - m_new)
        # masked entries are exp(NEG_INF - m) = 0 once a query has seen a key; any
        # weight taken before that is scaled away by alpha = 0 at its first key
        p = jnp.exp(s - (m_new + shift))
        l_scr[:, cols] = alpha * l_scr[:, cols] + jnp.sum(p, axis=0, keepdims=True)
        pv = jax.lax.dot_general(v, p.astype(v.dtype), _TN,
                                 preferred_element_type=jnp.float32)  # (hd, w)
        acc_scr[:, cols] = alpha * acc_scr[:, cols] + pv
        m_scr[:, cols] = m_new

    t.run(body, iq, ik)

    @pl.when(ik == t.n_k - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _bwd_kernel(*refs, t: _Tiling):
    slopes_ref, rel_ref, refs = _split(refs, t)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
     dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = refs
    h, ik, iq = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    slope = _slope(slopes_ref, h)

    @pl.when(jnp.logical_and(ik == 0, iq == 0))
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(iq == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(rows, cols, masked):
        q, do = t.query(q_ref[0, 0, cols]), do_ref[0, 0, cols]
        k, v = k_ref[0, 0, rows], v_ref[0, 0, rows]
        rel = None if rel_ref is None else rel_ref[rows, cols]
        s, shift = t.scores(k, q, slope, rel, iq, ik, masked)
        p = jnp.exp(s - (lse_ref[0, 0, :, cols] + shift))  # masked entries are 0
        dv_scr[rows] += jax.lax.dot_general(p.astype(do.dtype), do, _NN,
                                            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - di_ref[0, 0, :, cols])).astype(q.dtype)
        dk_scr[rows] += jax.lax.dot_general(ds, q, _NN, preferred_element_type=jnp.float32)
        w = cols.stop - cols.start
        at = pl.ds(pl.multiple_of(iq * t.block_q + cols.start, w), w)
        dq_scr[:, at] += jax.lax.dot_general(k, ds, _TN, preferred_element_type=jnp.float32)

    t.run(body, iq, ik)

    @pl.when(iq == t.n_q - 1)
    def _store_dkv():
        dk = dk_scr[...] if t.q_scaled else dk_scr[...] * t.sm_scale  # q came scaled
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ik == t.n_k - 1, iq == t.n_q - 1))
    def _store_dq():
        dq_ref[0, 0] = (dq_scr[...] * t.sm_scale).astype(dq_ref.dtype)


def _tiling(q, k, slopes, causal, window, q_offset, block_q, block_k, interpret,
            strips: int) -> _Tiling:
    Sq, hd = q.shape[2], q.shape[3]
    Sk = k.shape[2]
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    # diagonal tiles in strips where they are square and aligned, as many as
    # keep the strips on whole lane tiles (any width serves in interpret mode)
    square = causal and window is None and q_offset == 0 and block_q == block_k
    lane = 8 if interpret else 128
    while strips > 1 and not (square and (block_q // strips) % lane == 0):
        strips //= 2
    return _Tiling(
        causal=causal, window=window, q_offset=q_offset, block_q=block_q,
        block_k=block_k, n_q=Sq // block_q, n_k=Sk // block_k,
        sm_scale=1.0 / (hd ** 0.5), alibi=slopes is not None, strips=strips,
    )


def _lead_args(t: _Tiling, slopes, Hq):
    """Specs and arrays of the optional leading inputs: the slopes, in SMEM,
    and the ``col - row`` tile, fetched once (its block never changes)."""
    specs, args = [], []
    if t.alibi:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(slopes, jnp.float32).reshape(1, Hq))
    if t.uses_rel:
        shape = (t.block_k, t.block_q)
        specs.append(pl.BlockSpec(shape, lambda *_: (0, 0)))
        args.append((jnp.arange(t.block_q)[None, :] - jnp.arange(t.block_k)[:, None])
                    .astype(jnp.float32))
    return specs, args


def _params(interpret: bool, semantics):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def flash_fwd(
    q: jax.Array,  # (B, Hq, Sq, hd)
    k: jax.Array,  # (B, Hkv, Sk, hd)
    v: jax.Array,
    slopes: Optional[jax.Array] = None,  # (Hq,) ALiBi slopes, or None
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Returns ``(Oᵀ, lse)``: ``(B, Hq, hd, Sq)`` in q's dtype and the float32
    logsumexp of each query row's scaled, biased scores, ``(B, Hq, 1, Sq)``."""
    B, Hq, Sq, hd = q.shape
    grp = Hq // k.shape[1]
    t = _tiling(q, k, slopes, causal, window, q_offset, block_q, block_k, interpret,
                strips=FWD_STRIPS)

    def kv_map(b, h, i, j):
        return (b, h // grp, t.k_needed(i, j), 0)

    s_specs, s_args = _lead_args(t, slopes, Hq)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, t=t),
        grid=(B, Hq, t.n_q, t.n_k),
        in_specs=s_specs + [
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hd, block_q), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, hd, Sq), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((hd, block_q), jnp.float32),
        ],
        compiler_params=_params(interpret, ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*s_args, q, k, v)


def flash_bwd(
    q: jax.Array,  # (B, Hq, Sq, hd)
    k: jax.Array,  # (B, Hkv, Sk, hd)
    v: jax.Array,
    slopes: Optional[jax.Array],
    do: jax.Array,  # (B, Hq, Sq, hd)
    lse: jax.Array,  # (B, Hq, 1, Sq) float32, from flash_fwd
    di: jax.Array,  # (B, Hq, 1, Sq) float32: rowsum(dO · O)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Returns ``(dQᵀ, dK, dV)``: ``(B, Hq, hd, Sq)`` in q's dtype, and dK, dV per
    query head, ``(B, Hq, Sk, hd)``: in k's dtype where each kv head serves one
    query head, else float32 for the caller to sum over each group."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    grp = Hq // Hkv
    t = _tiling(q, k, slopes, causal, window, q_offset, block_q, block_k, interpret,
                strips=BWD_STRIPS)
    dkv_dtype = k.dtype if grp == 1 else jnp.float32

    def q_map(b, h, j, i):
        return (b, h, t.q_needed(j, i), 0)

    def row_map(b, h, j, i):
        return (b, h, 0, t.q_needed(j, i))

    def kv_map(b, h, j, i):
        return (b, h // grp, j, 0)

    s_specs, s_args = _lead_args(t, slopes, Hq)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, t=t),
        grid=(B, Hq, t.n_k, t.n_q),
        in_specs=s_specs + [
            pl.BlockSpec((1, 1, block_q, hd), q_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_q, hd), q_map),
            pl.BlockSpec((1, 1, 1, block_q), row_map),
            pl.BlockSpec((1, 1, 1, block_q), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hd, Sq), lambda b, h, j, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, hd, Sq), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sk, hd), dkv_dtype),
            jax.ShapeDtypeStruct((B, Hq, Sk, hd), dkv_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((hd, Sq), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=_params(interpret, ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd",
    )(*s_args, q, k, v, do, lse, di)
