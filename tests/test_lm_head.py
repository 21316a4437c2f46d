"""The vocabulary-tiled LM head and loss (``models/model.tiled_cross_entropy``)
against ``cross_entropy`` on the full f32 logits:
loss, metrics and the gradients w.r.t. the hidden states and the head's table,
tied and untied, with and without z-loss, ignored labels, a padded vocabulary
and a ragged last tile, in float32 and bfloat16, and under a vmap over clients
as the federated round runs it."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.model import cross_entropy, head_tiles, tiled_cross_entropy

V, V_PAD, D, B, S = 600, 768, 32, 2, 1536  # S 1536: 3 tiles, the last of 88 rows


def _cfg(tied):
    return types.SimpleNamespace(vocab_size=V, padded_vocab=V_PAD, tie_embeddings=tied)


def _inputs(key, tied, dtype, lead=()):
    """A table, hidden states and labels: a third of them the argmax of the
    logits (so that accuracy is far from 0), a fifth ignored (-1)."""
    k = jax.random.split(key, 5)
    table = 0.5 * jax.random.normal(k[0], lead + ((V_PAD, D) if tied else (D, V_PAD)))
    h = jax.random.normal(k[1], lead + (B, S, D)).astype(dtype)
    w = table if tied else jnp.swapaxes(table, -1, -2)
    top = jnp.argmax(jnp.einsum("...bsd,...vd->...bsv", h.astype(jnp.float32), w[..., :V, :]), -1)
    labels = jax.random.randint(k[2], lead + (B, S), 0, V)
    labels = jnp.where(jax.random.bernoulli(k[3], 1 / 3, labels.shape), top, labels)
    ignored = jax.random.bernoulli(k[4], 0.2, labels.shape)
    labels = jnp.where(ignored, -1, labels).at[..., -1].set(-1)
    return table, h, labels


def _losses(tied, z):
    cfg, key = _cfg(tied), "embed" if tied else "lm_head"

    def tiled(table, h, labels):
        return tiled_cross_entropy(cfg, {key: table}, h, labels, z)

    def full(table, h, labels):  # f32 products of the operands in h's dtype
        w = table.astype(h.dtype)
        logits = jnp.einsum("bsd,vd->bsv" if tied else "bsd,dv->bsv", h, w,
                            preferred_element_type=jnp.float32)
        return cross_entropy(logits[..., :V], labels, z)

    return tiled, full


def test_the_cells_tile_counts():
    """photon-125m (2 sequences of 2048 a client) and photon-1.3b-cut (1) run
    4 tiles, kanana-2's cut (1 of 4096, an eighth of the vocabulary) 8; no
    tile's f32 logits exceed the sequence-chunked loss's (B, 512, V) block
    but by the rounding to 128 rows."""
    for (seq, tokens, vocab), n in {(2048, 4096, 50368): 4, (2048, 2048, 50368): 4,
                                    (4096, 4096, 16032): 8}.items():
        tiles = head_tiles(seq, tokens, vocab)
        assert len(tiles) == n
        assert tiles[0][0] == 0 and tiles[-1][1] == vocab
        assert all(a[1] == b[0] for a, b in zip(tiles, tiles[1:]))
        widths = [v1 - v0 for v0, v1 in tiles]
        assert all(w % 128 == 0 for w in widths[:-1]) and 0 < widths[-1] <= widths[0]
        block = tokens // seq * 512 * vocab
        assert tokens * widths[0] <= block + tokens * 127


@pytest.mark.parametrize("vmapped", [False, True], ids=["one", "vmap2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("z", [0.0, 1e-4], ids=["z0", "z1e-4"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_tiled_head_matches_full_logits(tied, z, dtype, vmapped):
    assert len(head_tiles(S, B * S, V)) == 3
    tiled, full = _losses(tied, z)
    lead = (2,) if vmapped else ()
    table, h, labels = _inputs(jax.random.PRNGKey(7), tied, dtype, lead)
    vg = lambda f: jax.value_and_grad(f, argnums=(0, 1), has_aux=True)  # noqa: E731
    if vmapped:
        vg = lambda f, _vg=vg: jax.vmap(_vg(f))  # noqa: E731
    (loss, m), (g_table, g_h) = jax.jit(vg(tiled))(table, h, labels)
    (loss_r, m_r), (g_table_r, g_h_r) = jax.jit(vg(full))(table, h, labels)

    assert set(m) == set(m_r)
    np.testing.assert_array_equal(m["n_tokens"], m_r["n_tokens"])
    assert np.all(np.asarray(m_r["accuracy"]) > 0.2)
    np.testing.assert_array_equal(m["accuracy"], m_r["accuracy"])
    for k in ("ce", "z_loss") if z else ("ce",):
        np.testing.assert_allclose(m[k], m_r[k], rtol=2e-6)
    np.testing.assert_allclose(loss, loss_r, rtol=2e-6)

    # bfloat16: the reference rounds each gradient to bf16, the tiles keep f32
    tol = 1e-5 if dtype == jnp.float32 else 1.6e-2
    for g, g_r in ((g_table, g_table_r), (g_h, g_h_r)):
        assert g.dtype == g_r.dtype
        g, g_r = np.asarray(g, np.float32), np.asarray(g_r, np.float32)
        np.testing.assert_allclose(g, g_r, atol=tol * np.abs(g_r).max(), rtol=0)
    padded = g_table[..., V:, :] if tied else g_table[..., V:]
    assert not np.any(np.asarray(padded))
