"""Operations and bytes the work requires, computed from shapes.

These are the yardstick of every utilisation and roofline share the benchmark
reports. They count what the model and the round require, not what the
program happens to compute: attention is causal, so a query attends to the
keys at and before it; the vocabulary is the model's, not the padded table.

Training FLOPs per token (forward and backward, no recomputation):

    6·N + 6·L·S·d

with N the weights that enter a matrix product per token (per layer
4·d² for the attention projections and 2·d·d_ff for the MLP, plus the tied
output head V·d), and 6·L·S·d the causal attention scores and their weighted
sum (2·d per key and layer forward, over S/2 keys on average, three times for
forward and backward).

HBM bytes of one sync round (a lower bound, float32 weights and optimizer
state): per client and inner step, the weights read for the forward and the
backward pass, the gradient written and read, AdamW's two moments read and
written and the weights written (36 bytes per parameter); then the server
reads every client's weights and the global weights and writes the new
global weights.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    d, L, dff, V = m["d_model"], m["n_layers"], m["d_ff"], m["vocab_size"]
    return L * (4 * d * d + 2 * d * dff) + V * d


def all_params(m: dict, padded_vocab: int) -> int:
    """Every parameter the optimizer updates, norms and padded rows included."""
    d, L, dff = m["d_model"], m["n_layers"], m["d_ff"]
    per_layer = 4 * d * d + 2 * d * dff + 4 * d  # two LayerNorms: scale and bias
    return L * per_layer + padded_vocab * d + 2 * d


def train_flops_per_token(m: dict, seq_len: int) -> int:
    return 6 * matmul_params(m) + 6 * m["n_layers"] * seq_len * m["d_model"]


def round_tokens(flags: dict) -> int:
    return (int(flags["--clients"]) * int(flags["--local-steps"])
            * int(flags["--batch"]) * int(flags["--seq-len"]))


def round_flops(m: dict, flags: dict) -> int:
    """One sync round's required FLOPs: the clients' training steps. The server
    phase (a few operations per parameter) is left out: it is under 0.01%."""
    return round_tokens(flags) * train_flops_per_token(m, int(flags["--seq-len"]))


def round_bytes(m: dict, flags: dict, padded_vocab: int) -> int:
    P = all_params(m, padded_vocab)
    C, tau = int(flags["--clients"]), int(flags["--local-steps"])
    return C * tau * 36 * P + 4 * P * (C + 2)
