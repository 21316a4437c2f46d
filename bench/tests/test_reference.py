"""The plain reference against the program's round, at a small size on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import model as ref_model

SMALL = dict(n_layers=2, d_model=64, n_heads=4, d_ff=256, vocab_size=500,
             padded_vocab=512, layernorm_eps=1e-5, z_loss=1e-4)


def _program(compute_dtype="float32", seq_len=64):
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(
        get_config("photon-125m"), n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=256, vocab_size=500, max_seq_len=seq_len, compute_dtype=compute_dtype,
    )
    return build_model(cfg)


def _leaves(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_weights_from_the_seed_match_the_programs_bitwise():
    seed = 2_147_483_659  # more than 31 bits
    prog = _leaves(_program().init(jax.random.PRNGKey(seed)))
    ref = _leaves(ref_model.init_weights(SMALL, seed))
    assert prog.keys() == ref.keys()
    for k in ref:
        assert prog[k].shape == ref[k].shape and bool(jnp.all(prog[k] == ref[k])), k


@pytest.mark.parametrize("seq_len", [64, 512])  # 512 takes the program's chunked attention
def test_loss_and_gradients_match_the_program_in_float32(seq_len):
    model = _program(seq_len=seq_len)
    w = ref_model.init_weights(SMALL, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq_len), 0, 500)
    want, g_prog = jax.value_and_grad(lambda p: model.loss(p, {"tokens": tokens})[0])(w)
    with jax.default_matmul_precision("highest"):
        got, g_ref = jax.value_and_grad(lambda p: ref_model.loss(SMALL, p, tokens))(w)
    assert abs(float(got) - float(want)) < 1e-5
    gp, gr = _leaves(g_prog), _leaves(g_ref)
    for k in gr:
        scale = float(jnp.max(jnp.abs(gr[k])))
        assert float(jnp.max(jnp.abs(gp[k] - gr[k]))) <= 1e-5 * scale + 1e-12, k


def test_alibi_slopes_follow_press_et_al():
    np.testing.assert_allclose(ref_model.alibi_slopes(8), [2.0 ** -i for i in range(1, 9)])
    twelve = ref_model.alibi_slopes(12)
    np.testing.assert_allclose(
        twelve, [2.0 ** -i for i in range(1, 9)] + [2.0 ** -(i + 0.5) for i in range(4)],
        rtol=1e-6)


def test_fp8_control_departs_further_than_the_programs_bfloat16():
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 500)
    w = ref_model.init_weights(SMALL, 9)
    with jax.default_matmul_precision("highest"):
        ref = float(ref_model.loss(SMALL, w, tokens))
        fp8 = float(ref_model.loss(SMALL, w, tokens, precision="fp8"))
    bf16 = float(_program("bfloat16").loss(w, {"tokens": tokens})[0])
    assert abs(fp8 - ref) > 3 * abs(bf16 - ref)


def test_one_round_matches_the_programs_round(tiny_cell, tiny_root, tmp_path):
    """The harness drives the program's set-up rounds and the reference replays
    them: a sound run reads inside the committed limits."""
    from conftest import run_tiny

    result = run_tiny(tiny_cell, tiny_root, tmp_path / "out")
    assert result["correct"], result["checks"]
    for name, c in result["checks"].items():
        assert c["value"] < c["limit"], (name, c)
