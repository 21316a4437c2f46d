"""The FLOP and byte counts against hand sums and the program's own weights."""
import json

import counts
from conftest import BENCH


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_photon_125m_flops_per_token_by_hand():
    m = _config("photon-125m")
    # per layer 4·768² + 2·768·3072 = 7,077,888; 12 layers + the tied head 50368·768
    n = 12 * 7_077_888 + 38_682_624
    assert counts.matmul_params(m) == n == 123_617_280
    attention = 6 * 12 * 2048 * 768  # causal: 2·d per key over S/2 keys, ×3
    assert counts.train_flops_per_token(m, 2048) == 6 * n + attention == 854_949_888
    assert round(counts.train_flops_per_token(m, 2048) / 1e9, 3) == 0.855


def test_photon_13b_cut_flops_per_token_by_hand():
    m = _config("photon-1.3b-cut")
    n = 4 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 50_368 * 2048
    assert counts.matmul_params(m) == n == 304_480_256
    assert counts.train_flops_per_token(m, 2048) == 6 * n + 6 * 4 * 2048 * 2048
    assert round(counts.train_flops_per_token(m, 2048) / 1e9, 2) == 1.93


def test_round_counts_p125m():
    m = _config("photon-125m")
    flags = json.loads((BENCH / "traffic" / "sync-c2-b2.json").read_text())["flags"]
    flags["--local-steps"] = m["local_steps"]
    assert counts.round_tokens(flags) == 2 * 8 * 2 * 2048 == 65_536
    assert counts.round_flops(m, flags) == 65_536 * 854_949_888
    p = counts.all_params(m, m["padded_vocab"])
    assert counts.round_bytes(m, flags, m["padded_vocab"]) == 16 * 36 * p + 4 * p * 4


def test_all_params_matches_the_programs_weights():
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model

    for name in ("photon-125m", "photon-1.3b-cut"):
        m = _config(name)
        cfg = dataclasses.replace(get_config(m["arch"]), n_layers=m["n_layers"])
        shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
        assert counts.all_params(m, m["padded_vocab"]) == n
        assert cfg.padded_vocab == m["padded_vocab"]
