"""Plain reference of a dense decoder's SFT step: weights, loss and
gradients in straightforward ``jax.numpy``, and the step's model FLOPs.

It imports nothing of the program under test.  The weights come from
:func:`init_params`, a function of the seed alone; the benchmark hands the
same function's output to the program, so both start from one state
without either taking anything the other made.

The layer equations (pre-norm decoder, as in Qwen2 and Phi-3):

    h = x + Wo · attn(rope(Wq · n1(x)), rope(Wk · n1(x)), Wv · n1(x))
    y = h + Wd · (silu(Wg · n2(h)) * (Wu · n2(h)))
    n(x) = x / sqrt(mean(x²) + eps) * (1 + w)

with grouped-query causal softmax attention inside each sample, RoPE in
the rotate-half convention, a final norm, and logits ``n(x) · head``
(``head = embed.T`` where the configuration ties them).  The step (loss,
AdamW, rows) is ``common.Reference``'s.

The norm scales are stored as offsets from 1 (``1 + w``, initialised at
0): the same function and the same gradients as a scale initialised at 1.
Departure from Qwen2: no q/k/v biases (the program has none).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable

import jax
import jax.numpy as jnp

from bench.reference import common
from bench.reference.common import (  # noqa: F401  the harness reads these here
    AdamW, change_sq_norms, leaf_sq_norms, seed_key_data, sq_to_norms)

# the program's registry families this reference implements, with the
# activation it assumes
FAMILIES = {"dense": "swiglu"}


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the reference needs, read from a configuration file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    tied: bool

    @classmethod
    def from_config(cls, run: dict) -> "Shape":
        return cls(layers=run["num_hidden_layers"], d_model=run["hidden_size"],
                   heads=run["num_attention_heads"],
                   kv_heads=run["num_key_value_heads"],
                   head_dim=run["head_dim"], d_ff=run["intermediate_size"],
                   vocab=run["vocab_size"], norm_eps=run["rms_norm_eps"],
                   rope_theta=run["rope_theta"],
                   tied=run["tie_word_embeddings"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def param_shapes(s: Shape) -> Dict:
    """The parameter tree: layers stacked on a leading axis."""
    L, d, f, V = s.layers, s.d_model, s.d_ff, s.vocab
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    tree = {
        "embed": (V, d),
        "final_norm": (d,),
        "layers": {
            "attn_norm": (L, d),
            "attn": {"wq": (L, d, qd), "wk": (L, d, kvd), "wv": (L, d, kvd),
                     "wo": (L, qd, d)},
            "mlp_norm": (L, d),
            "mlp": {"w_up": (L, d, f), "w_down": (L, f, d),
                    "w_gate": (L, d, f)},
        },
    }
    if not s.tied:
        tree["lm_head"] = (d, V)
    return tree


def _std(path: str, shape, s: Shape) -> float:
    if path.endswith("norm"):
        return 0.0
    if path == "embed":
        return 0.02
    std = 1.0 / math.sqrt(shape[-2])  # fan-in
    if path.endswith(("wo", "w_down")):
        std /= math.sqrt(s.layers)  # residual branches
    return std


def init_params(s: Shape, key_data, dtype=jnp.float32):
    """Random weights from the key data: one normal draw per leaf, each
    from its own key, so the values do not depend on how the call is
    sharded or jitted.  Call inside ``jax.jit`` with ``out_shardings``."""
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(s), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = "/".join(k.key for k in path)
        std = _std(name, shape, s)
        if std == 0.0:
            leaves.append(jnp.zeros(shape, dtype))
            continue
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        leaves.append((z * std).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------
def _norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (B, S, H, hd); positions 0..S-1 (one sample per row)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Shape, x, lp):
    B, S, _ = x.shape
    H, KH, hd = s.heads, s.kv_heads, s.head_dim
    h = _norm(x, lp["attn_norm"], s.norm_eps)
    q = (h @ lp["attn"]["wq"]).reshape(B, S, H, hd)
    k = (h @ lp["attn"]["wk"]).reshape(B, S, KH, hd)
    v = (h @ lp["attn"]["wv"]).reshape(B, S, KH, hd)
    q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    k = jnp.repeat(k, H // KH, axis=2)
    v = jnp.repeat(v, H // KH, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + a.reshape(B, S, H * hd) @ lp["attn"]["wo"]
    h = _norm(x, lp["mlp_norm"], s.norm_eps)
    m = lp["mlp"]
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def nll_sum(s: Shape, params, tokens, targets, mask):
    """Summed next-token cross-entropy of rows (B, S) under ``mask``."""
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(lambda x, lp: (_layer(s, x, lp), None), x,
                        params["layers"])
    x = _norm(x, params["final_norm"], s.norm_eps)
    head = params["embed"].T if s.tied else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum((logz - tgt) * mask)


class Reference(common.Reference):
    param_shapes = staticmethod(param_shapes)
    init_params = staticmethod(init_params)
    nll_sum = staticmethod(nll_sum)


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------
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
    """Model FLOPs to train on samples of these lengths once, from the
    configuration's shapes, never from the compiled program.

    Per token, 6 FLOPs (forward 2, backward 4) per matmul parameter: every
    layer's q, k, v, o, gate, up and down projections and the LM head.
    The embedding lookup is no matmul and does not count.  Attention adds,
    per layer and per sample of n tokens, QK^T and PV over the n(n+1)/2
    causal pairs inside the sample (a packed row attends within its
    segments only): 2 matmuls x 2 FLOPs x head_dim x heads per pair
    forward, x3 with the backward.  Padding, empty microbatches and
    recomputation (remat) are work the program chooses to do, and count
    for nothing."""
    lengths = [int(n) for n in lengths]
    return (6 * matmul_params(run) * sum(lengths)
            + sum(attention_flops(run, n) for n in lengths))
