"""Model FLOPs of a dense decoder's training step, from the configuration's
shapes and the sample lengths; never from the compiled program.

Per token, 6 FLOPs (forward 2, backward 4) per matmul parameter: every
layer's q, k, v, o, gate, up and down projections and the LM head.  The
embedding lookup is no matmul and does not count.  Attention adds, per
layer and per sample of n tokens, QK^T and PV over the n(n+1)/2 causal
pairs inside the sample (a packed row attends within its segments only):
2 matmuls x 2 FLOPs x head_dim x heads per pair forward, x3 with the
backward.  Padding, empty microbatches and recomputation (remat) are work
the program chooses to do, and count for nothing.
"""
from __future__ import annotations

from typing import Iterable


def matmul_params(run: dict) -> int:
    d, f, V = run["hidden_size"], run["intermediate_size"], run["vocab_size"]
    qd = run["num_attention_heads"] * run["head_dim"]
    kvd = run["num_key_value_heads"] * run["head_dim"]
    per_layer = d * qd + 2 * d * kvd + qd * d + 3 * d * f
    return run["num_hidden_layers"] * per_layer + d * V


def attention_flops(run: dict, n: int) -> int:
    pairs = n * (n + 1) // 2
    per_layer = 3 * 2 * 2 * run["head_dim"] * run["num_attention_heads"] * pairs
    return run["num_hidden_layers"] * per_layer


def step_flops(run: dict, lengths: Iterable[int]) -> int:
    """Model FLOPs to train on samples of these lengths once."""
    lengths = [int(n) for n in lengths]
    return (6 * matmul_params(run) * sum(lengths)
            + sum(attention_flops(run, n) for n in lengths))
