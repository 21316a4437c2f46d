"""Logical-axis → mesh-axis sharding rules for every (architecture × shape × mesh).

Parameters carry logical axes from their ParamDesc declarations; this module resolves
them to PartitionSpecs against the production mesh with divisibility-aware fallbacks:

  dim % axis == 0  -> shard
  dim >= axis      -> shard (GSPMD pads; waste < 2x — e.g. coder's 56 heads over 16)
  dim <  axis      -> replicate (e.g. 8 KV heads over model=16; tensors are small)

Training/prefill shard batch/client over ('pod','data') and tensor dims over 'model'.
Decode shards the KV cache *sequence* over 'model' (flash-decode style partial-softmax
combine); long_500k (B=1) shards the sequence over every mesh axis.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axis (training / generic tensors)
AXIS_RULES = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "experts": "model",
    "ssm_heads": "model",
    "head_dim": None,  # fallback target when the head axis cannot shard (see below)
    "layers": None,  # scan-stacked layer dim: never sharded
}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def _resolve_dim(mesh: Mesh, logical: Optional[str], dim: int) -> Optional[str]:
    if logical is None:
        return None
    target = AXIS_RULES.get(logical)
    if target is None or target not in mesh.axis_names:
        return None
    n = _axis_size(mesh, target)
    # exact divisibility only: jit *input* shardings reject GSPMD padding, so uneven
    # head counts (coder 56, llama4 40, whisper 20) go through the head_dim fallback.
    if dim % n == 0:
        return target
    return None


def choose_client_mapping(mesh: Mesh, param_count: int, hbm_bytes: float = 16 * 1024**3):
    """Photon client → mesh mapping (§5.1 / Algorithm 1 L.15-24).

    Every federated client holds a full model replica + AdamW state (~16 B/param in
    fp32). Small models: one client per ('pod','data') slice (max parallel clients,
    single-GPU-node analogue). Models too large for one model-parallel slice fall back
    to the paper's hierarchical mode: fewer clients, with the leftover data axis used
    INSIDE each client for FSDP + data parallelism (the Photon LLM Node's multi-machine
    FSDP pipeline).

    Returns (client_axes, fsdp_axes, n_clients).
    """
    candidates = []
    all_client = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    candidates.append((all_client, ()))
    if "pod" in mesh.axis_names:
        candidates.append((("pod",), ("data",)))
    candidates.append(((), all_client))
    state_bytes = param_count * 16.0  # fp32 params + m + v + pseudo-grad
    for client_axes_, fsdp_axes_ in candidates:
        n_c = int(np.prod([mesh.shape[a] for a in client_axes_])) if client_axes_ else 1
        chips_per_client = mesh.size // n_c
        budget = chips_per_client * hbm_bytes * 0.55  # rest for activations/temps
        if state_bytes <= budget:
            return client_axes_, fsdp_axes_, n_c
    return candidates[-1][0], candidates[-1][1], 1


def add_fsdp_axes(
    spec: P,
    shape: Tuple[int, ...],
    mesh: Mesh,
    fsdp_axes: Tuple[str, ...],
    logical_axes: Tuple[Optional[str], ...] = (),
) -> P:
    """ZeRO-style sharding: place the fsdp axes on the first unsharded NON-STACK dim
    whose size divides them (params are gathered per-layer at use; GSPMD inserts the
    all-gather after the scan's per-layer slice). The 'layers' scan dim must never be
    fsdp-sharded — that would broadcast a different shard every scan step."""
    if not fsdp_axes:
        return spec
    n = int(np.prod([mesh.shape[a] for a in fsdp_axes]))
    entries = list(spec) + [None] * (len(shape) - len(spec))
    logical = list(logical_axes) + [None] * (len(shape) - len(logical_axes))
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if logical[i] == "layers":
            continue
        if e is None and dim % n == 0 and dim >= n:
            entries[i] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
            return P(*entries)
    return spec  # nothing divisible: replicate (tiny tensors only)


def param_pspec(mesh: Mesh, axes: Tuple[Optional[str], ...], shape: Tuple[int, ...]) -> P:
    resolved = []
    used = set()
    for logical, dim in zip(axes, shape):
        ax = _resolve_dim(mesh, logical, dim)
        if ax in used:  # an axis can appear at most once in a PartitionSpec
            ax = None
        if ax is not None:
            used.add(ax)
        resolved.append(ax)
    # head-count too small to shard (e.g. gemma3's 8 heads over model=16): fall back
    # to sharding head_dim — RoPE then pays a halo exchange, but the attention
    # parameter mass stays distributed.
    if "model" not in used and "model" in mesh.axis_names:
        n = _axis_size(mesh, "model")
        wants_model = any(a in ("heads", "kv_heads") for a in axes)
        if wants_model:
            for i, (logical, dim) in enumerate(zip(axes, shape)):
                if logical == "head_dim" and dim % n == 0:
                    resolved[i] = "model"
                    break
    return P(*resolved)


def client_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes that the federated client dimension shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_clients(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in client_axes(mesh)]))


# ---------------------------------------------------------------------------
# Pytree spec builders
# ---------------------------------------------------------------------------


def params_pspecs(mesh: Mesh, axes_tree, shapes_tree, fsdp_axes: Tuple[str, ...] = ()):
    """Parameter PartitionSpecs: sharded over 'model' per the logical axes, plus
    optional ZeRO/FSDP sharding over the given leftover axes."""
    return jax.tree_util.tree_map(
        lambda a, s: add_fsdp_axes(param_pspec(mesh, a, s), s, mesh, fsdp_axes, a),
        axes_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x
        ),
    )


def params_shardings(mesh: Mesh, axes_tree, shapes_tree):
    specs = params_pspecs(mesh, axes_tree, shapes_tree)
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p), specs, is_leaf=lambda x: isinstance(x, P)
    )


def clientize_pspec(mesh: Mesh, spec: P, client_axes_: Optional[Tuple[str, ...]] = None) -> P:
    """Prepend the client axis to a parameter spec (client-stacked params/opt state)."""
    ca = client_axes(mesh) if client_axes_ is None else client_axes_
    return P(ca if ca else None, *spec)


def clientize_tree(mesh: Mesh, spec_tree, client_axes_: Optional[Tuple[str, ...]] = None):
    return jax.tree_util.tree_map(
        lambda p: clientize_pspec(mesh, p, client_axes_), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Activations / inputs
# ---------------------------------------------------------------------------


def train_batch_pspec(mesh: Mesh, ndim: int) -> P:
    """Round batches (τ, C, B, ...): client dim over ('pod','data')."""
    return P(None, client_axes(mesh), *([None] * (ndim - 2)))


def central_batch_pspec(mesh: Mesh, ndim: int) -> P:
    """Centralized baseline batches (B, ...): batch over ('pod','data')."""
    return P(client_axes(mesh), *([None] * (ndim - 1)))


def decode_cache_pspec(mesh: Mesh, shape: Tuple[int, ...], kind: str, long_context: bool) -> P:
    """KV cache (B, S, Hkv, hd) / SSM state shardings for serving.

    kind: 'kv' (B,S,Hkv,hd) | 'conv' (B,W,C) | 'ssd' (B,nh,hd,ds) | 'cross' (B,F,H,hd)
    Caches inside scan-stacked segments carry a leading layer dim; callers prepend None.
    """
    ca = client_axes(mesh)
    if kind == "kv":
        B, S = shape[0], shape[1]
        if long_context or B < max(1, np.prod([mesh.shape[a] for a in ca])):
            # batch too small to shard: shard sequence over everything
            return P(None, ca + ("model",), None, None)
        return P(ca, "model", None, None)
    if kind == "cross":
        B = shape[0]
        return P(ca, None, None, None) if B >= n_clients(mesh) else P(*([None] * len(shape)))
    if kind == "conv":
        B = shape[0]
        lead = ca if B >= n_clients(mesh) else None
        return P(lead, None, "model" if shape[-1] % mesh.shape["model"] == 0 else None)
    if kind == "ssd":
        B = shape[0]
        lead = ca if B >= n_clients(mesh) else None
        nh = shape[1]
        return P(lead, "model" if nh % mesh.shape["model"] == 0 or nh >= mesh.shape["model"] else None, None, None)
    raise ValueError(kind)
