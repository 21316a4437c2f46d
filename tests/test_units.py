"""Focused unit tests: outer optimizers, attention masks/positions, MoE dispatch,
SSM decode consistency, compression, autobatch, roofline parsing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.compression import (
    cast_compress,
    cast_decompress,
    init_error_feedback,
    int8_compress,
    int8_decompress,
    topk_compress,
    uplink_bytes,
)
from repro.core.inner_opt import InnerOptConfig, init_inner_state, inner_update
from repro.core.outer_opt import OuterOptConfig, init_outer_state, outer_update
from repro.models.attention import make_mask, sdpa, sdpa_chunked
from repro.models.common import alibi_slopes, apply_rope

# ---------------------------------------------------------------------------
# outer optimizers
# ---------------------------------------------------------------------------


def test_fedavg_unit_lr_is_plain_averaging():
    params = {"w": jnp.ones((3,))}
    delta = {"w": jnp.full((3,), 0.25)}  # theta - mean(theta_k)
    cfg = OuterOptConfig(name="fedavg", lr=1.0)
    new, _ = outer_update(cfg, params, delta, init_outer_state(cfg, params))
    np.testing.assert_allclose(np.asarray(new["w"]), 0.75)


def test_fedmom_nesterov_accelerates_constant_gradient():
    params = {"w": jnp.zeros((1,))}
    delta = {"w": jnp.ones((1,))}
    cfg = OuterOptConfig(name="fedmom", lr=1.0, momentum=0.9, nesterov=True)
    st = init_outer_state(cfg, params)
    p = params
    steps = []
    for _ in range(3):
        p, st = outer_update(cfg, p, delta, st)
        steps.append(float(p["w"][0]))
    # displacement per round grows under momentum
    assert steps[0] > steps[1] > steps[2]
    assert (steps[0] - steps[1]) < (steps[1] - steps[2])


def test_fedadam_bounded_step():
    params = {"w": jnp.zeros((4,))}
    delta = {"w": jnp.array([1e3, -1e3, 1e-3, 0.0])}
    cfg = OuterOptConfig(name="fedadam", lr=0.1)
    new, _ = outer_update(cfg, params, delta, init_outer_state(cfg, params))
    assert float(jnp.max(jnp.abs(new["w"]))) <= 0.11  # lr-bounded regardless of scale


def test_adamw_weight_decay_shrinks_params_with_zero_grad():
    cfg = InnerOptConfig(lr_max=0.1, weight_decay=0.5, warmup_steps=0, total_steps=10, alpha=1.0)
    params = {"w": jnp.ones((2,))}
    st = init_inner_state(cfg, params)
    grads = {"w": jnp.zeros((2,))}
    new, _, _ = inner_update(cfg, params, grads, st, jnp.int32(5))
    assert float(new["w"][0]) < 1.0


# ---------------------------------------------------------------------------
# attention internals
# ---------------------------------------------------------------------------


def test_make_mask_causal_window_and_decode_len():
    m = make_mask(jnp.arange(4), jnp.arange(4), causal=True, window=2)
    mm = np.asarray(m[0, 0, 0])
    assert mm[0, 1] == False and mm[1, 0] == True and mm[3, 1] == False  # window=2
    md = make_mask(jnp.array([5]), jnp.arange(8), causal=True, window=None, k_len=jnp.int32(6))
    assert np.asarray(md[0, 0, 0, 0]).sum() == 6


def test_chunked_attention_equals_dense():
    B, S, H, hd = 2, 512, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    pos = jnp.arange(S)
    dense = sdpa(q, k, v, make_mask(pos, pos, True, None))
    chunked = sdpa_chunked(q, k, v, q_pos=pos, k_pos=pos, causal=True, window=None,
                           k_len=None, slopes=None, chunk=128)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked), rtol=2e-5, atol=2e-5)


def test_chunked_attention_alibi_matches_dense_bias():
    B, S, H, hd = 1, 256, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    pos = jnp.arange(S)
    slopes = alibi_slopes(H)
    dist = (pos[:, None] - pos[None, :]).astype(jnp.float32)
    bias = (-slopes[:, None, None] * jnp.maximum(dist, 0.0))[None]
    dense = sdpa(q, k, v, make_mask(pos, pos, True, None), bias)
    chunked = sdpa_chunked(q, k, v, q_pos=pos, k_pos=pos, causal=True, window=None,
                           k_len=None, slopes=slopes, chunk=64)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked), rtol=2e-5, atol=2e-5)


def test_rope_preserves_norm_and_relativity():
    hd = 64
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 2, hd))
    rx = apply_rope(x, jnp.arange(8), 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(rx), axis=-1),
        rtol=1e-5,
    )
    # relativity: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, hd))
    def dot_at(i, j):
        qi = apply_rope(q, jnp.array([i]), 10_000.0)
        kj = apply_rope(k, jnp.array([j]), 10_000.0)
        return float(jnp.sum(qi * kj))
    assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4


def test_alibi_slopes_monotone_positive():
    for h in (8, 12, 16, 20):
        s = np.asarray(alibi_slopes(h))
        assert (s > 0).all() and len(s) == h


# ---------------------------------------------------------------------------
# MoE dispatch
# ---------------------------------------------------------------------------


def test_moe_capacity_drops_overflow_tokens():
    from repro.models import moe as moe_mod

    cfg = get_config("deepseek-moe-16b").reduced()
    model_desc = moe_mod.moe_ffn_desc(cfg)
    from repro.models.common import init_params

    p = init_params(jax.random.PRNGKey(0), model_desc)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    out_full, aux = moe_mod.moe_ffn(cfg, p, x, capacity_factor=8.0)  # nothing dropped
    out_tiny, _ = moe_mod.moe_ffn(cfg, p, x, capacity_factor=0.05)  # nearly all dropped
    assert np.isfinite(np.asarray(out_full)).all()
    assert float(jnp.abs(out_tiny).mean()) < float(jnp.abs(out_full).mean())
    assert float(aux) >= 1.0 - 1e-3  # Switch aux lower bound at uniform routing


def test_moe_shared_expert_always_active():
    from repro.models import moe as moe_mod
    from repro.models.common import init_params

    cfg = get_config("deepseek-moe-16b").reduced()
    p = init_params(jax.random.PRNGKey(0), moe_mod.moe_ffn_desc(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model))
    out_drop_all, _ = moe_mod.moe_ffn(cfg, p, x, capacity_factor=1e-9)
    # with all routed tokens dropped, output == shared expert path (nonzero)
    assert float(jnp.abs(out_drop_all).mean()) > 0


# ---------------------------------------------------------------------------
# SSM decode vs scan consistency (sequence processed both ways)
# ---------------------------------------------------------------------------


def test_ssm_block_decode_matches_full_scan():
    from repro.models import ssm as ssm_mod
    from repro.models.common import init_params

    cfg = get_config("mamba2-1.3b").reduced()
    p = init_params(jax.random.PRNGKey(0), ssm_mod.ssm_desc(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.d_model))
    y_full, _ = ssm_mod.ssm_block(cfg, p, x)
    cache = ssm_mod.empty_ssm_cache(cfg, 1)
    cache = {"conv": jnp.zeros_like(cache["conv"]), "ssd": cache["ssd"]}
    ys = []
    for t in range(12):
        y_t, cache = ssm_mod.ssm_block(cfg, p, x[:, t : t + 1], cache=cache, decode=True)
        ys.append(y_t)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_dec), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------


def test_bf16_cast_roundtrip_and_stochastic_rounding_unbiased():
    tree = {"w": jnp.full((2000,), 0.1001, jnp.float32)}
    det = cast_decompress(cast_compress(tree))
    assert abs(float(det["w"][0]) - 0.1001) < 1e-3
    sr = cast_decompress(cast_compress(tree, rng=jax.random.PRNGKey(0)))
    # stochastic rounding: mean over many entries approaches the true value
    assert abs(float(sr["w"].mean()) - 0.1001) < 2e-4


def test_topk_error_feedback_conserves_mass():
    tree = {"w": jnp.arange(1.0, 101.0)}
    sparse, err = topk_compress(tree, k_fraction=0.1)
    nnz = int((np.asarray(sparse["w"]) != 0).sum())
    assert nnz == 10
    np.testing.assert_allclose(
        np.asarray(sparse["w"] + err["w"]), np.asarray(tree["w"]), rtol=1e-6
    )
    # second round re-injects the residual
    sparse2, err2 = topk_compress({"w": jnp.zeros(100)}, 0.1, error=err)
    assert float(jnp.abs(sparse2["w"]).sum()) > 0  # residual mass surfaces


def test_int8_roundtrip_error_bounded():
    x = {"w": jax.random.normal(jax.random.PRNGKey(0), (512,))}
    out = int8_decompress(int8_compress(x))
    scale = float(jnp.max(jnp.abs(x["w"]))) / 127.0
    assert float(jnp.max(jnp.abs(out["w"] - x["w"]))) <= scale * 0.5 + 1e-6


def test_uplink_bytes_ordering():
    tree = {"w": jnp.zeros((1000,)), "b": jnp.zeros((10,))}
    f32 = uplink_bytes(tree, "float32")
    assert uplink_bytes(tree, "bfloat16") == f32 / 2
    assert uplink_bytes(tree, "int8") < f32 / 2
    assert uplink_bytes(tree, "topk", 0.01) < uplink_bytes(tree, "int8")


# ---------------------------------------------------------------------------
# autobatch
# ---------------------------------------------------------------------------


def test_autobatch_estimates_sane():
    from repro.launch.autobatch import estimate_micro_batch

    small = get_config("qwen3-1.7b")
    big = get_config("chameleon-34b")
    mb_small = estimate_micro_batch(small, 4096)
    mb_big = estimate_micro_batch(big, 4096)
    assert mb_small >= 1
    assert mb_big <= mb_small


# ---------------------------------------------------------------------------
# roofline parsing
# ---------------------------------------------------------------------------


def test_hlo_analyzer_nested_scan_multiplication():
    from repro.roofline.hlo_analyzer import analyze

    a = jnp.zeros((256, 256))

    def f(x):
        def inner(c, _):
            return c @ a, None

        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=5)
            return y, None

        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    compiled = jax.jit(f).lower(jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
    r = analyze(compiled.as_text())
    expected = 15 * 2 * 256**3
    assert expected * 0.95 <= r.flops <= expected * 1.3


def test_analyzer_matches_xla_on_scanfree_graph():
    from repro.roofline.hlo_analyzer import analyze

    f = jax.jit(lambda a, b: jnp.tanh(a @ b))
    c = f.lower(
        jax.ShapeDtypeStruct((128, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 32), jnp.float32),
    ).compile()
    r = analyze(c.as_text())
    xla = c.cost_analysis()["flops"]
    assert abs(r.flops - xla) / xla < 0.1


def test_evaluate_perplexity_compiles_once_per_model(tiny_model):
    """The eval forward is jitted once per model: a second evaluation (the
    next round's) reuses the compiled executable instead of recompiling."""
    from repro.data import validation_stream
    from repro.launch.compile_env import CompileCounter
    from repro.metrics import activation_l2_probe, evaluate_perplexity
    from repro.models import build_model

    cfg, _, params = tiny_model
    model = build_model(cfg)  # fresh: nothing compiled for it yet
    val = validation_stream(16, cfg.vocab_size, False)
    with CompileCounter() as first:
        ppl0 = evaluate_perplexity(model, params, val, batches=2, batch_size=2)
    with CompileCounter() as second:
        ppl1 = evaluate_perplexity(model, params, val, batches=2, batch_size=2)
    assert first.count >= 1
    assert second.count == 0
    assert np.isfinite(ppl0) and np.isfinite(ppl1)
    batch = {"tokens": jnp.asarray(val.next_batch(2))}
    activation_l2_probe(model, params, batch)
    with CompileCounter() as probe:
        activation_l2_probe(model, params, batch)
    assert probe.count == 0


@pytest.mark.parametrize("env_dir", [None, "placed"])
def test_compile_cache_dir_placement(tmp_path, monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR places the cache; without it the cache is the
    fixed <checkout>/.jax_cache."""
    from repro.launch.compile_env import CHECKOUT_ROOT, enable_compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        expect = str(CHECKOUT_ROOT / ".jax_cache")
    else:
        expect = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", expect)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == expect
        assert jax.config.jax_compilation_cache_dir == expect
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert (CHECKOUT_ROOT / "src" / "repro" / "launch" / "compile_env.py").exists()
