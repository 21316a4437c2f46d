"""Step builders shared by the dry-run and the real launcher.

For every (architecture × input shape × mesh) this module produces:
  - the step function (federated round / centralized step / prefill / decode),
  - abstract inputs (`jax.ShapeDtypeStruct` with NamedSharding attached — no
    allocation), the `input_specs()` contract of deliverable (e).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.core import (
    FederatedConfig,
    InnerOptConfig,
    OuterOptConfig,
    centralized_step,
    get_codec,
    init_uplink_residuals,
)
from repro.core.outer_opt import init_outer_state
from repro.core.round_program import build_round_programs
from repro.models import build_model
from repro.sharding import specs as sh


def _sds(shape, dtype, mesh: Mesh, pspec: P):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))


def _tree_sds(shape_tree, sharding_tree, mesh):
    return jax.tree_util.tree_map(
        lambda s, p: _sds(s.shape, s.dtype, mesh, p),
        shape_tree,
        sharding_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


# ---------------------------------------------------------------------------
# Abstract parameter / state trees
# ---------------------------------------------------------------------------


def abstract_params(model, mesh: Mesh, fsdp_axes: Tuple[str, ...] = (), dtype=None):
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dtype=dtype))
    pspecs = sh.params_pspecs(mesh, model.axes(), model.shapes(), fsdp_axes)
    return _tree_sds(shapes, pspecs, mesh), pspecs


def _serve_fsdp_axes(cfg: ModelConfig, mesh: Mesh) -> Tuple[str, ...]:
    """Weight-gathered serving for models whose bf16 weights overflow one
    model-parallel slice (>20B params): shard params over the batch axes too."""
    return sh.client_axes(mesh) if cfg.param_count() > 20e9 else ()


def abstract_fed_state(model, mesh: Mesh, fed: FederatedConfig, fsdp_axes: Tuple[str, ...] = ()):
    params_sds, pspecs = abstract_params(model, mesh, fsdp_axes)

    outer_shapes = jax.eval_shape(
        lambda: init_outer_state(fed.outer, model.init(jax.random.PRNGKey(0)))
    )

    # outer state subtrees that mirror params get params' specs; scalars replicate
    outer_sds = {}
    for key, val in outer_shapes.items():
        if key == "round":
            outer_sds[key] = _sds((), jnp.int32, mesh, P())
        else:
            outer_sds[key] = _tree_sds(val, pspecs, mesh)

    state = {
        "params": params_sds,
        "outer": outer_sds,
        "round": _sds((), jnp.int32, mesh, P()),
        "rng": _sds((2,), jnp.uint32, mesh, P()),
    }
    return state, pspecs


# ---------------------------------------------------------------------------
# input_specs() — deliverable (e)
# ---------------------------------------------------------------------------


def input_specs(
    cfg: ModelConfig,
    shape: InputShape,
    mesh: Mesh,
    *,
    tau_lowered: int = 4,
    mode: str = "federated",  # 'federated' | 'centralized' (train shapes only)
) -> Dict[str, Any]:
    """Abstract model inputs (ShapeDtypeStruct; weak-type-correct, shardable, zero
    allocation) for the given input shape.

    Training batches are PRE-SPLIT into micro-batches: federated
    (τ, C, grad_accum, B_micro, ...) with the client dim over the client axes and the
    micro-batch dim over the within-client FSDP/DDP axes (reshaping a sharded batch
    dim inside jit breaks GSPMD propagation); centralized (grad_accum, B_micro, ...).
    """
    ca = sh.client_axes(mesh)
    if shape.kind == "train":
        client_ax, fsdp_ax, C = sh.choose_client_mapping(mesh, cfg.param_count())
        b_loc = shape.global_batch // C
        fsdp_div = int(np.prod([mesh.shape[a] for a in fsdp_ax])) if fsdp_ax else 1
        ga = default_grad_accum(b_loc, shape.seq_len, fsdp_div,
                                target_tokens=_target_tokens(cfg))
        b_mb = b_loc // ga
        if mode == "federated":
            cspec = client_ax if client_ax else None
            bspec = fsdp_ax if fsdp_ax else None
            toks = _sds(
                (tau_lowered, C, ga, b_mb, shape.seq_len), jnp.int32, mesh,
                P(None, cspec, None, bspec, None),
            )
            out = {"tokens": toks}
            if cfg.enc_dec:
                out["audio_embed"] = _sds(
                    (tau_lowered, C, ga, b_mb, cfg.n_audio_frames, cfg.d_model),
                    jnp.bfloat16, mesh, P(None, cspec, None, bspec, None, None),
                )
            return out
        else:  # centralized per-step batch, micro-batches pre-split
            ga_c = default_grad_accum(
                shape.global_batch, shape.seq_len,
                fsdp_div=mesh.size // mesh.shape["model"],
                target_tokens=_target_tokens(cfg),
            )
            b_mb = shape.global_batch // ga_c
            toks = _sds((ga_c, b_mb, shape.seq_len), jnp.int32, mesh, P(None, ca, None))
            out = {"tokens": toks}
            if cfg.enc_dec:
                out["audio_embed"] = _sds(
                    (ga_c, b_mb, cfg.n_audio_frames, cfg.d_model),
                    jnp.bfloat16, mesh, P(None, ca, None, None),
                )
            return out

    if shape.kind == "prefill":
        bspec = ca if shape.global_batch >= sh.n_clients(mesh) else None
        out = {
            "tokens": _sds((shape.global_batch, shape.seq_len), jnp.int32, mesh, P(bspec, None))
        }
        if cfg.enc_dec:
            out["audio_embed"] = _sds(
                (shape.global_batch, cfg.n_audio_frames, cfg.d_model),
                jnp.bfloat16, mesh, P(bspec, None, None),
            )
        return out

    if shape.kind == "decode":
        bspec = ca if shape.global_batch >= sh.n_clients(mesh) else None
        return {
            "tokens": _sds((shape.global_batch, 1), jnp.int32, mesh, P(bspec, None)),
            "cache_index": _sds((), jnp.int32, mesh, P()),
        }
    raise ValueError(shape.kind)


def abstract_cache(cfg: ModelConfig, shape: InputShape, mesh: Mesh, model=None):
    """Abstract KV/SSM cache with serving shardings (sequence-sharded KV)."""
    model = model or build_model(cfg)
    long_ctx = shape.seq_len > 100_000
    cache_shapes = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len, jnp.bfloat16)
    )

    base_ndim = {"kv": 4, "conv": 3, "ssd": 4, "cross": 4}

    def leaf_spec(path, leaf):
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        name = keys[-1]
        if name in ("k", "v"):
            kind = "cross" if "cross" in keys else "kv"
        else:
            kind = name  # 'conv' | 'ssd'
        extra = leaf.ndim - base_ndim[kind]
        core = sh.decode_cache_pspec(mesh, leaf.shape[extra:], kind, long_ctx)
        return _sds(leaf.shape, leaf.dtype, mesh, P(*([None] * extra), *core))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_shapes)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


@dataclass
class BuiltStep:
    name: str
    fn: Callable  # jit-wrapped
    args: Tuple  # abstract args (lower(*args))
    model_flops: float  # 6·N_active·D equivalent for §Roofline
    meta: Dict[str, Any]


def default_fed_config(C: int, tau_lowered: int, grad_accum: int = 1) -> FederatedConfig:
    return FederatedConfig(
        clients_per_round=C,
        local_steps=tau_lowered,
        inner=InnerOptConfig(lr_max=3e-4, total_steps=60_000),
        outer=OuterOptConfig(name="fedmom", lr=0.7, momentum=0.9),
        grad_accum=grad_accum,
    )


def _target_tokens(cfg: ModelConfig) -> int:
    """Per-device tokens per micro-batch: activation carries scale with the model's
    widest live buffer (d_model; or the MoE expert dispatch width), so wide models
    get smaller micro-batches."""
    width = max(cfg.d_model, (cfg.moe_d_ff or 0) // 2)
    if cfg.n_heads % 16:
        # head_dim-fallback sharding replicates score blocks across the model axis;
        # scale micro-batches down to compensate (whisper 20H, coder 56H, llama4 40H)
        width *= 4
    return max(4096, 16_384 * 2048 // width)


def default_grad_accum(
    b_loc: int, seq_len: int, fsdp_div: int = 1, target_tokens: int = 16_384
) -> int:
    """Micro-batches per local step so one micro-batch is ~target_tokens per DEVICE of
    the within-client group, with the micro-batch divisible by the FSDP width."""
    rows_per_dev = max(1, target_tokens // seq_len)
    b_mb = min(b_loc, max(1, fsdp_div) * rows_per_dev)
    ga = max(1, b_loc // b_mb)
    while ga > 1 and (b_loc % ga or (b_loc // ga) % max(1, fsdp_div)):
        ga -= 1
    return ga


def build_train_step(
    cfg: ModelConfig,
    shape: InputShape,
    mesh: Mesh,
    *,
    tau_lowered: int = 4,
    remat: bool = True,
    mode: str = "federated",
    fed: Optional[FederatedConfig] = None,
    pseudo_grad_dtype: str = "float32",
    uplink: str = "float32",
    topk_fraction: float = 0.05,
    cohort_tile: Optional[int] = None,
) -> BuiltStep:
    model = build_model(cfg)
    loss_fn = lambda p, b: model.loss(p, b, remat=remat)

    if mode == "federated":
        # the trainer's round (core/round_program), lowered on the mesh: this
        # branch only maps clients onto the mesh and builds the abstract
        # arguments in the trainer's signature
        client_ax, fsdp_ax, C = sh.choose_client_mapping(mesh, cfg.param_count())
        fsdp_div = int(np.prod([mesh.shape[a] for a in fsdp_ax])) if fsdp_ax else 1
        ga = default_grad_accum(shape.global_batch // C, shape.seq_len, fsdp_div,
                                target_tokens=_target_tokens(cfg))
        fed = replace(fed or default_fed_config(C, tau_lowered, ga),
                      grad_accum=ga, pre_split_micro=True)
        if pseudo_grad_dtype != "float32":
            fed = replace(fed, pseudo_grad_dtype=pseudo_grad_dtype)
        state, pspecs = abstract_fed_state(model, mesh, fed, fsdp_ax)
        client_pspecs = sh.clientize_tree(mesh, pspecs, client_ax)
        client_shardings = jax.tree_util.tree_map(
            lambda p: NamedSharding(mesh, p), client_pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )

        def shard_clients(tree):
            return jax.lax.with_sharding_constraint(tree, client_shardings)

        codec = get_codec(uplink, topk_fraction) if uplink != "float32" else None
        batches = input_specs(cfg, shape, mesh, tau_lowered=tau_lowered, mode="federated")
        width = C
        if cohort_tile is not None:
            # streamed-cohort lowering: the compiled unit is ONE TILE of the
            # round; the host loop replays it over every tile and folds the
            # weighted partial sums (docs/aggregation.md), so the population
            # and the cohort never enter the lowering. The tile's client dim
            # shards over the same client axes as the flat round.
            client_width = int(np.prod([mesh.shape[a] for a in client_ax])) if client_ax else 1
            if cohort_tile % client_width:
                raise ValueError(
                    f"cohort_tile={cohort_tile} must be a multiple of the "
                    f"mesh client-axis width {client_width} (axes "
                    f"{list(client_ax)}): jit inputs reject uneven GSPMD "
                    f"padding on the sharded client dim"
                )
            width = cohort_tile
            batches = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    (x.shape[0], width) + x.shape[2:], x.dtype, sharding=x.sharding
                ),
                batches,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            # the tile reads only the params/round/rng lanes; the outer
            # optimizer state stays with the server program
            state = {k: state[k] for k in ("params", "round", "rng")}
        residuals = None
        if codec is not None and codec.stateful:
            # per-client error-feedback rows ride the client axis exactly like
            # the (C, ...) params replicas: same clientized pspecs
            res_shapes = jax.eval_shape(
                lambda: init_uplink_residuals(
                    codec, model.init(jax.random.PRNGKey(0)), width
                )
            )
            residuals = _tree_sds(res_shapes, client_pspecs, mesh)
        # the (C,) weights and τ-mask enter as replicated traced inputs, so
        # participation and realized step counts never perturb a sharding
        args = (state, batches, _sds((width,), jnp.float32, mesh, P()),
                residuals, _sds((width,), jnp.int32, mesh, P()))
        programs = build_round_programs(
            loss_fn, fed, codec=codec, cohort_tile=cohort_tile,
            shard_clients=shard_clients,
        )
        tokens = tau_lowered * width * (shape.global_batch // C) * shape.seq_len
        meta = {
            "tau_lowered": tau_lowered,
            "tokens_per_call": tokens,
            "clients": width,
            "grad_accum": ga,
            "client_axes": list(client_ax),
            "fsdp_axes": list(fsdp_ax),
            "uplink": uplink,
        }
        if cohort_tile is not None:
            meta["cohort_tile"] = cohort_tile
        return BuiltStep(
            name=f"{cfg.name}:{shape.name}:federated"
            + ("-tile" if cohort_tile is not None else ""),
            fn=programs.tile if cohort_tile is not None else programs.round,
            args=args,
            model_flops=6.0 * cfg.active_param_count() * tokens,
            meta=meta,
        )

    # centralized baseline: per-step gradient sync (the paper's comparison).
    # Big models ZeRO-shard params+optimizer over the batch axes (standard FSDP).
    inner = InnerOptConfig(lr_max=3e-4, total_steps=60_000)
    cen_fsdp = (
        sh.client_axes(mesh)
        if cfg.param_count() * 12 > 0.55 * 16 * (1 << 30) * mesh.shape["model"]
        else ()
    )
    params_sds, pspecs = abstract_params(model, mesh, cen_fsdp)
    abs_p = model.abstract_params()
    state = {
        "params": params_sds,
        "inner": {
            "m": _tree_sds(abs_p, pspecs, mesh),
            "v": _tree_sds(abs_p, pspecs, mesh),
            "count": _sds((), jnp.int32, mesh, P()),
        },
        "step": _sds((), jnp.int32, mesh, P()),
    }
    ga_c = default_grad_accum(
        shape.global_batch, shape.seq_len, fsdp_div=mesh.size // mesh.shape["model"],
        target_tokens=_target_tokens(cfg),
    )
    step = jax.jit(
        functools.partial(centralized_step, loss_fn, inner, grad_accum=ga_c, pre_split=True)
    )
    batch = input_specs(cfg, shape, mesh, mode="centralized")
    tokens = shape.global_batch * shape.seq_len
    mf = 6.0 * cfg.active_param_count() * tokens
    return BuiltStep(
        name=f"{cfg.name}:{shape.name}:centralized",
        fn=step,
        args=(state, batch),
        model_flops=mf,
        meta={"tokens_per_call": tokens, "grad_accum": ga_c, "fsdp_axes": list(cen_fsdp)},
    )


def build_prefill_step(cfg: ModelConfig, shape: InputShape, mesh: Mesh) -> BuiltStep:
    model = build_model(cfg)
    params_sds, _ = abstract_params(model, mesh, _serve_fsdp_axes(cfg, mesh), dtype=jnp.bfloat16)
    step = jax.jit(lambda p, b: model.prefill(p, b))
    batch = input_specs(cfg, shape, mesh)
    tokens = shape.global_batch * shape.seq_len
    mf = 2.0 * cfg.active_param_count() * tokens
    return BuiltStep(
        name=f"{cfg.name}:{shape.name}:prefill",
        fn=step,
        args=(params_sds, batch),
        model_flops=mf,
        meta={"tokens_per_call": tokens},
    )


def build_decode_step(cfg: ModelConfig, shape: InputShape, mesh: Mesh) -> BuiltStep:
    model = build_model(cfg)
    params_sds, _ = abstract_params(model, mesh, _serve_fsdp_axes(cfg, mesh), dtype=jnp.bfloat16)
    cache_sds = abstract_cache(cfg, shape, mesh, model)
    inputs = input_specs(cfg, shape, mesh)

    def serve_step(params, cache, tokens, cache_index):
        return model.decode_step(params, cache, tokens, cache_index)

    step = jax.jit(serve_step, donate_argnums=(1,))
    tokens = shape.global_batch  # one new token per sequence
    mf = 2.0 * cfg.active_param_count() * tokens
    return BuiltStep(
        name=f"{cfg.name}:{shape.name}:decode",
        fn=step,
        args=(params_sds, cache_sds, inputs["tokens"], inputs["cache_index"]),
        model_flops=mf,
        meta={"tokens_per_call": tokens, "kv_len": shape.seq_len},
    )


def build_step(cfg: ModelConfig, shape_name: str, mesh: Mesh, **kw) -> BuiltStep:
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        return build_train_step(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh)
    return build_decode_step(cfg, shape, mesh)
