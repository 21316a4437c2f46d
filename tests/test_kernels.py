"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the ref.py
pure-jnp oracles. Kernels execute in interpret mode on the CPU host."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_decode.ref import decode_attention_ref
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan import ssd
from repro.kernels.ssd_scan.ref import ssd_naive

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,hd,causal,window",
    [
        (1, 2, 2, 64, 32, True, None),
        (2, 4, 2, 128, 64, True, None),
        pytest.param(2, 8, 1, 256, 64, True, None, marks=pytest.mark.slow),  # MQA
        (1, 4, 4, 128, 64, False, None),  # bidirectional (encoder)
        pytest.param(2, 4, 2, 256, 32, True, 64, marks=pytest.mark.slow),  # window
        (1, 2, 2, 96, 64, True, None),  # non-128 seq -> smaller block
    ],
)
def test_flash_attention_matches_ref(B, Hq, Hkv, S, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(hash((B, Hq, S)) % 2**31), 3)
    q = _rand(ks[0], (B, S, Hq, hd), dtype)
    k = _rand(ks[1], (B, S, Hkv, hd), dtype)
    v = _rand(ks[2], (B, S, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    ref = attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, window=window,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(jnp.swapaxes(ref, 1, 2), np.float32),
        **TOL[dtype],
    )


def test_flash_attention_q_offset_decode_tail():
    """q_offset positions the query block at the end of the kv (chunked prefill)."""
    B, H, S, hd = 1, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q_full = _rand(ks[0], (B, S, H, hd), jnp.float32)
    k = _rand(ks[1], (B, S, H, hd), jnp.float32)
    v = _rand(ks[2], (B, S, H, hd), jnp.float32)
    full = flash_attention(q_full, k, v, causal=True, interpret=True)
    tail = flash_attention(q_full[:, 64:], k, v, causal=True, q_offset=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(tail), np.asarray(full[:, 64:]), rtol=2e-5, atol=2e-5
    )


def _alibi(h):
    from repro.models.common import alibi_slopes

    return alibi_slopes(h)


def _ref_bshd(q, k, v, causal, slopes, window=None):
    """The float32 oracle in the model's (B, S, H, hd) layout."""
    t = lambda x: jnp.swapaxes(x.astype(jnp.float32), 1, 2)  # noqa: E731
    out = attention_ref(t(q), t(k), t(v), causal=causal, window=window, slopes=slopes)
    return jnp.swapaxes(out, 1, 2)


@pytest.mark.parametrize(
    "B,Hq,Hkv,S,hd,causal,window,alibi,dtype,clients",
    [
        (1, 2, 2, 128, 64, True, None, True, jnp.float32, 0),  # MHA, ALiBi
        (1, 4, 2, 128, 64, True, None, False, jnp.float32, 0),  # GQA
        (2, 2, 2, 128, 64, False, None, True, jnp.float32, 0),  # bidirectional ALiBi
        (1, 2, 2, 256, 64, True, 96, True, jnp.float32, 0),  # static sliding window
        (1, 2, 1, 256, 128, True, None, True, jnp.bfloat16, 0),  # hd 128, MQA
        (1, 2, 2, 256, 64, True, None, False, jnp.bfloat16, 0),
        (1, 2, 2, 128, 64, True, None, True, jnp.bfloat16, 2),  # vmapped clients, as the round
    ],
)
def test_flash_attention_grads_match_ref(B, Hq, Hkv, S, hd, causal, window, alibi, dtype,
                                         clients):
    """Forward output and dQ, dK, dV of the kernels (interpret mode) against the
    float32 oracle's, over several blocks (64 in interpret mode): skipped,
    masked in strips, and whole."""
    lead = (clients,) if clients else ()
    ks = jax.random.split(jax.random.PRNGKey(S + hd + Hkv), 4)
    q = _rand(ks[0], lead + (B, S, Hq, hd), dtype)
    k = _rand(ks[1], lead + (B, S, Hkv, hd), dtype)
    v = _rand(ks[2], lead + (B, S, Hkv, hd), dtype)
    do = _rand(ks[3], lead + (B, S, Hq, hd), jnp.float32)
    slopes = _alibi(Hq) if alibi else None

    def kern(q, k, v):
        return flash_attention(q, k, v, slopes, causal=causal, window=window, interpret=True)

    def ref(q, k, v):
        return _ref_bshd(q, k, v, causal, slopes, window)

    if clients:
        kern, ref = jax.vmap(kern), jax.vmap(ref)

    def vjp(fn):
        out, back = jax.vjp(fn, q, k, v)
        return (out,) + back(do.astype(out.dtype))

    tol = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}[dtype]
    for name, got, want in zip(("out", "dq", "dk", "dv"), vjp(kern), vjp(ref)):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=tol,
            atol=tol * float(np.abs(want).max()), err_msg=name)


def test_flash_attention_alibi_matches_chunked_path():
    """The kernels' ALiBi bias equals sdpa_chunked's (scale, bias, mask), in float32."""
    from repro.models.attention import sdpa_chunked

    B, H, S, hd = 1, 2, 512, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (_rand(kk, (B, S, H, hd), jnp.float32) for kk in ks)
    pos = jnp.arange(S)
    want = sdpa_chunked(q, k, v, q_pos=pos, k_pos=pos, causal=True, window=None,
                        k_len=None, slopes=_alibi(H))
    got = flash_attention(q, k, v, _alibi(H), causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,hd,kv_len,window",
    [
        pytest.param(2, 4, 2, 256, 64, 200, None, marks=pytest.mark.slow),
        pytest.param(1, 8, 8, 512, 32, 512, None, marks=pytest.mark.slow),
        (2, 4, 1, 128, 64, 77, None),
        pytest.param(2, 4, 2, 512, 64, 400, 128, marks=pytest.mark.slow),  # window
    ],
)
def test_flash_decode_matches_ref(B, Hq, Hkv, S, hd, kv_len, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (B, 1, Hq, hd), dtype)
    k = _rand(ks[1], (B, S, Hkv, hd), dtype)
    v = _rand(ks[2], (B, S, Hkv, hd), dtype)
    out = flash_decode(q, k, v, jnp.int32(kv_len), window=window, interpret=True)
    ref = decode_attention_ref(
        q[:, 0], jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        jnp.int32(kv_len), window=window,
    )
    np.testing.assert_allclose(
        np.asarray(out[:, 0], np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,nh,hd,G,ds,chunk",
    [
        (1, 64, 2, 32, 1, 16, 16),
        pytest.param(2, 128, 4, 64, 1, 32, 32, marks=pytest.mark.slow),
        pytest.param(1, 128, 4, 32, 2, 16, 64, marks=pytest.mark.slow),  # multi-group
        (1, 100, 2, 32, 1, 16, 32),  # non-multiple seq -> padding path
    ],
)
def test_ssd_kernel_matches_naive_recurrence(B, S, nh, hd, G, ds, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = _rand(ks[0], (B, S, nh, hd), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
    Bm = _rand(ks[3], (B, S, G, ds), dtype)
    Cm = _rand(jax.random.PRNGKey(9), (B, S, G, ds), dtype)

    y_k, st_k = ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    y_n, st_n = ssd_naive(x, dt, A, Bm, Cm)
    tol = dict(rtol=1e-4, atol=1e-4) if dtype == jnp.float32 else dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(y_k, np.float32), np.asarray(y_n, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_n), rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_ssd_initial_state_continuation():
    """Processing [first half] then [second half | state] == processing whole."""
    B, S, nh, hd, G, ds = 1, 128, 2, 32, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = _rand(ks[0], (B, S, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
    Bm = _rand(ks[3], (B, S, G, ds), jnp.float32)
    Cm = _rand(jax.random.PRNGKey(7), (B, S, G, ds), jnp.float32)

    y_full, st_full = ssd(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    y1, st1 = ssd(x[:, :64], dt[:, :64], A, Bm[:, :64], Cm[:, :64], chunk=32, interpret=True)
    y2, st2 = ssd(
        x[:, 64:], dt[:, 64:], A, Bm[:, 64:], Cm[:, 64:], chunk=32,
        initial_state=st1, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, 64:]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_model_chunked_matches_naive():
    """The model-level jnp SSD (dry-run lowering path) against the recurrence."""
    from repro.models.ssm import ssd_chunked

    B, S, nh, hd, G, ds = 2, 96, 4, 32, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = _rand(ks[0], (B, S, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
    Bm = _rand(ks[3], (B, S, G, ds), jnp.float32)
    Cm = _rand(jax.random.PRNGKey(8), (B, S, G, ds), jnp.float32)
    y_c, st_c = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    y_n, st_n = ssd_naive(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_n), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_c), np.asarray(st_n), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64), (2, 8, 128), (3, 5, 7, 32)])
def test_rmsnorm_matches_ref(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = _rand(k1, shape, dtype)
    s = 1.0 + 0.1 * jax.random.normal(k2, shape[-1:])
    out = rmsnorm(x, s, interpret=True)
    ref = rmsnorm_ref(x, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )
