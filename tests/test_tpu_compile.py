"""Real-width TPU v5e compiles of the fedcore kernels (kernels/fedcore/kernel.py),
of the flash attention kernels (kernels/flash_attention/), of the grouped
expert product (models/moe.grouped_matmul, the megablox kernels) and of the
expert-share layer around it (models/moe.moe_share_ffn).

The interpret-mode parity tests (tests/test_fed_kernels.py, tests/test_kernels.py)
run the kernels' Python bodies on the CPU, which accepts programs the TPU
compiler refuses (scalar stores into VMEM, misaligned blocks, VMEM overflows).
Here each kernel is compiled, not run, for one chip of a described ``v5e:2x2``
topology, the fedcore kernels at photon-125m's packed flat size and the
attention kernels at the benchmark cells' shapes, and the compiled module must
hold the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU library, so describing it while a module is
imported would make the test workers collect different tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fedcore import kernel as K
from repro.kernels.fedcore.ops import BLOCK
from repro.kernels.flash_attention import flash_attention
from repro.models.common import alibi_slopes

# photon-125m: 123,704,832 float32 params, packed and padded to a BLOCK multiple
N_PARAMS = 123_704_832
N_PAD = -(-N_PARAMS // BLOCK) * BLOCK
C = 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-topology compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("opt", ["fedavg", "fedadam"])
def test_server_apply_compiles_for_v5e(one_chip, opt):
    n_lanes = 2 if opt == "fedadam" else 0

    def fn(d, wn, p, *rest):
        lanes, bias = rest[:n_lanes], rest[n_lanes:]
        return K.server_apply(
            d, wn, p, list(lanes), opt=opt, lr=1.0,
            bias_corr=tuple(bias) if bias else None,
        )

    shapes = [((C, N_PAD), jnp.float32), ((C,), jnp.float32), ((N_PAD,), jnp.float32)]
    shapes += [((N_PAD,), jnp.float32)] * n_lanes
    if opt == "fedadam":
        shapes += [((), jnp.float32)] * 2
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "name,fn,shapes",
    [
        ("topk_mask_ef", K.topk_mask_ef,
         [((N_PAD,), jnp.float32), ((), jnp.float32)]),
        ("sr_bf16", K.sr_bf16, [((N_PAD,), jnp.float32), ((N_PAD,), jnp.uint32)]),
        ("int8_quant", K.int8_quant, [((N_PAD,), jnp.float32), ((), jnp.float32)]),
        ("int8_dequant", K.int8_dequant, [((N_PAD,), jnp.int8), ((), jnp.float32)]),
    ],
)
def test_codec_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.mark.parametrize("B,H,S,hd", [(2, 12, 2048, 64), (1, 16, 2048, 128)])
def test_flash_attention_fwd_bwd_compile_for_v5e(one_chip, B, H, S, hd):
    """photon-125m's and photon-1.3b's causal ALiBi attention, forward and
    backward, as a client step of the benchmark cells runs it."""

    def fwd_bwd(q, k, v, do):
        f = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, alibi_slopes(H), causal=True, interpret=False)
        out, vjp = jax.vjp(f, q, k, v)
        return out, vjp(do)

    compiled = _compile(fwd_bwd, one_chip, *[((B, S, H, hd), jnp.bfloat16)] * 4)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd" in text


def test_flash_attention_mla_heads_compile_for_v5e(one_chip):
    """kanana-2-30b-a3b's latent attention as its cell runs it: 32 heads, RoPE
    (no bias), 192-wide queries and keys, 128-wide values, S 4096."""
    B, H, S, dqk, dv = 1, 32, 4096, 192, 128

    def fwd_bwd(q, k, v, do):
        f = lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False)  # noqa: E731
        out, vjp = jax.vjp(f, q, k, v)
        return out, vjp(do)

    shapes = [((B, S, H, dqk), jnp.bfloat16)] * 2 + [((B, S, H, dv), jnp.bfloat16)] * 2
    text = _compile(fwd_bwd, one_chip, *shapes).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd" in text


def test_grouped_expert_product_compiles_for_v5e(one_chip):
    """kanana-2-30b-a3b's expert share as its cell runs it: 4096 tokens × 6 slots
    sorted over 128 experts, of which 8 are held, through the SwiGLU's three
    grouped products (2048 → 768 → 2048), forward and backward."""
    from repro.models.moe import grouped_matmul

    T, K, E, held, d, f = 4096, 6, 128, 8, 2048, 768

    def fwd_bwd(x, w_gate, w_in, w_out, sizes, dy):
        def ffn(x, w_gate, w_in, w_out):
            mm = lambda a, w: grouped_matmul(a, w, sizes, 0, interpret=False)  # noqa: E731
            return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_in), w_out)

        y, vjp = jax.vjp(ffn, x, w_gate, w_in, w_out)
        return y, vjp(dy)

    shapes = [((T * K, d), jnp.bfloat16), ((held, d, f), jnp.bfloat16),
              ((held, d, f), jnp.bfloat16), ((held, f, d), jnp.bfloat16),
              ((E,), jnp.int32), ((T * K, d), jnp.bfloat16)]
    text = _compile(fwd_bwd, one_chip, *shapes).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3 * 3  # gmm, and tgmm + gmm back


def test_expert_share_layer_compiles_for_v5e_with_no_tensor_of_every_slot(one_chip, monkeypatch):
    """kanana-2-30b-a3b's expert-share layer as its cell runs it (T 4096, top-6 of
    128 experts, 8 held, d 2048, SwiGLU 768), forward and backward, the
    grouped products compiled as kernels: the routed part moves only the live
    slots' rows, R = 3,072 at a time, so no float array of all T·K = 24,576
    slots' rows is built."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import moe
    from repro.models.common import is_desc

    monkeypatch.setattr(moe, "_on_cpu", lambda: False)  # the kernels, not their interpreter
    cfg = dataclasses.replace(get_config("kanana-2-30b-a3b"), moe_experts_held=8,
                              moe_first_expert=0, compute_dtype="bfloat16")
    assert moe.trip_rows(4096, 6, 8, 128) == 3072
    p = jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.float32, sharding=one_chip),
        moe.moe_share_desc(cfg), is_leaf=is_desc)
    x = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.bfloat16, sharding=one_chip)

    def fwd_bwd(p, x, dy):
        y, vjp = jax.vjp(lambda p, x: moe.moe_share_ffn(cfg, p, x)[0], p, x)
        return y, vjp(dy)

    text = jax.jit(fwd_bwd).lower(p, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3 * 3
    every_slot = re.findall(r"(?:bf16|f16|f32)\[(?:\d+,)?(?:24576,(?:2048|768)|4096,6,2048)\]",
                            text)
    assert not every_slot, sorted(set(every_slot))
