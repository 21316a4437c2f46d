"""Where the scaled-dot-product runs: the Pallas flash kernels on the TPU for
self-attention outside decode with a static window (``attention.flash_eligible``),
the jnp paths everywhere else; and a reduced Photon model through the kernels
(interpret mode) against its ``sdpa_chunked`` path.

The platform is the only thing a CPU host cannot show, so the tests that take
the TPU's branch report ``"tpu"`` from ``jax.default_backend`` themselves."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn_mod
from repro.models.model import build_model


def _eligible(**kw):
    args = dict(platform="tpu", self_attention=True, decode=False, window=None,
                sq=2048, sk=2048, hd=64)
    args.update(kw)
    return attn_mod.flash_eligible(args.pop("platform"), **args)


@pytest.mark.parametrize("kw,want", [
    ({}, True),  # causal self-attention, ALiBi or RoPE alike: the rule never sees them
    ({"hd": 128}, True),
    ({"window": 4096}, True),  # a static window
    ({"platform": "cpu"}, False),
    ({"decode": True}, False),
    ({"self_attention": False}, False),  # cross-attention
    ({"window": jnp.int32(64)}, False),  # a traced per-layer window
    ({"sq": 1000, "sk": 1000}, False),  # no tiling
])
def test_flash_dispatch_rule(kw, want):
    assert _eligible(**kw) is want


def _photon(**kw):
    base = dict(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2, d_ff=256,
                vocab_size=512, max_seq_len=512)
    base.update(kw)
    return dataclasses.replace(get_config("photon-125m"), **base)


def _kernel_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("arch", ["photon-125m", "granite-3-2b"])  # ALiBi, RoPE
def test_causal_self_attention_takes_the_kernels_on_tpu(arch, on_tpu):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 128), jnp.int32)
    loss = lambda p: model.loss(p, {"tokens": tokens})[0]  # noqa: E731
    assert _kernel_calls(loss, params) > 0
    assert _kernel_calls(jax.grad(loss), params) > 0


def test_decode_and_traced_windows_keep_the_jnp_paths(on_tpu):
    photon = build_model(_photon())
    p = jax.eval_shape(photon.init, jax.random.PRNGKey(0))
    cache = photon.init_cache(1, 128)
    step = lambda p, c: photon.decode_step(p, c, jnp.zeros((1, 1), jnp.int32), jnp.int32(5))  # noqa: E731
    assert _kernel_calls(step, p, cache) == 0
    # gemma3's local and global layers share a scan and take their windows as data
    gemma = build_model(get_config("gemma3-4b").reduced())
    g = jax.eval_shape(gemma.init, jax.random.PRNGKey(0))
    loss = lambda p: gemma.loss(p, {"tokens": jnp.zeros((1, 128), jnp.int32)})[0]  # noqa: E731
    assert _kernel_calls(loss, g) == 0


def test_photon_loss_and_grads_through_the_kernels_match_the_chunked_path(monkeypatch):
    """A 2-layer Photon (ALiBi, bf16 compute, S = 512 so the CPU takes
    ``sdpa_chunked``) gives the same loss and gradients through the kernels
    in interpret mode, within bf16 rounding."""
    model = build_model(_photon())
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 512), 0, 512)

    def loss_and_grads():
        fn = lambda p: model.loss(p, {"tokens": tokens})[0]  # noqa: E731
        return jax.value_and_grad(fn)(params)

    want_loss, want = loss_and_grads()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got_loss, got = loss_and_grads()
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=2e-3)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), w, rtol=3e-2,
                                   atol=3e-2 * float(np.abs(w).max()),
                                   err_msg=jax.tree_util.keystr(path))
