"""Plain reference of a DeepSeek-V2 decoder's SFT step (multi-head latent
attention, leading dense layers, then layers of routed and shared
experts): weights, loss and gradients in straightforward ``jax.numpy``,
and the step's model FLOPs.

It imports nothing of the program under test.  The weights come from
:func:`init_params`, a function of the seed alone, which the benchmark
hands to the program too.

The layer equations (pre-norm, as in DeepSeek-V2, arXiv:2405.04434):

    h = x + Wo · attn(q, k, v)                      latent attention:
      q = n1(x) Wq, per head [q_nope | q_rope]
      [c | k_rope] = n1(x) Wkv_a,  c <- n_kv(c)     (the latent, normed)
      [k_nope | v] = c Wkv_b, per head
      k = [k_nope | rope(k_rope)], one rope key shared by all heads
      attn = softmax(q k^T · scale) v, causal inside the sample
    y = h + FFN(n2(h))
      FFN = SwiGLU (first_k_dense_replace leading layers), else
      FFN = shared SwiGLU + sum over the held experts e among the token's
            top-k of routed_scaling_factor · p_e · SwiGLU_e
    n(x) = x / sqrt(mean(x²) + eps) * (1 + w)

with p = softmax over all ``router_outputs`` router logits, the top-k not
renormalized where ``norm_topk_prob`` is false, rope under YaRN
(``rope_scaling``) and scale = qk_head_dim^-0.5 · mscale(factor,
mscale_all_dim)².  Each routed expert is computed over every token and
masked by its gate: no dispatch, no capacity.  The configuration's share
of the experts (``n_routed_experts`` held of ``router_outputs``, the
first ones) is the program's: the absent experts' part is left out in
both.  The step (loss, AdamW, rows) is ``common.Reference``'s; each row
is one sample.

The sequence-wise balance loss (``seq_aux``), per sample of T tokens:
alpha · T · sum_i f_i P_i, f_i = E / (k T) · (tokens routing to i), P_i the
mean router probability of i over the sample; summed over samples and
layers and added to the summed cross-entropy (each sample counts by its
tokens, as the program packs several into a row).

Departures from the published model, shared with the program: rope in
the rotate-half convention on the layout the weights produce (DeepSeek's
checkpoints interleave the rope dims, the same function after a fixed
permutation of Wq's and Wkv_a's rope columns); norm scales stored as
offsets from 1.
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
FAMILIES = {"moe": "swiglu"}


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the reference needs, read from a configuration file."""

    layers: int
    lead: int  # leading dense layers
    d_model: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int  # the dense layers' width
    expert_ff: int
    shared_ff: int
    held: int  # routed experts held: the first ``held`` of ``router``
    router: int
    k: int
    renormalize: bool
    routed_scale: float
    vocab: int
    norm_eps: float
    rope_theta: float
    yarn: dict  # rope_scaling
    aux_alpha: float
    seq_aux: bool

    @classmethod
    def from_config(cls, run: dict) -> "Shape":
        return cls(layers=run["num_hidden_layers"],
                   lead=run["first_k_dense_replace"],
                   d_model=run["hidden_size"],
                   heads=run["num_attention_heads"],
                   kv_rank=run["kv_lora_rank"],
                   nope=run["qk_nope_head_dim"], rope=run["qk_rope_head_dim"],
                   v_dim=run["v_head_dim"], d_ff=run["intermediate_size"],
                   expert_ff=run["moe_intermediate_size"],
                   shared_ff=(run["n_shared_experts"]
                              * run["moe_intermediate_size"]),
                   held=run["n_routed_experts"], router=run["router_outputs"],
                   k=run["num_experts_per_tok"],
                   renormalize=run["norm_topk_prob"],
                   routed_scale=run["routed_scaling_factor"],
                   vocab=run["vocab_size"], norm_eps=run["rms_norm_eps"],
                   rope_theta=run["rope_theta"], yarn=run["rope_scaling"],
                   aux_alpha=run["aux_loss_alpha"], seq_aux=run["seq_aux"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.lead


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _attn(n: int, s: Shape) -> Dict:
    d, H, r = s.d_model, s.heads, s.kv_rank
    return {"wq": (n, d, H * (s.nope + s.rope)), "wkv_a": (n, d, r + s.rope),
            "kv_norm": (n, r), "wkv_b": (n, r, H * (s.nope + s.v_dim)),
            "wo": (n, H * s.v_dim, d)}


def _swiglu(prefix, d: int, f: int) -> Dict:
    return {"w_up": prefix + (d, f), "w_down": prefix + (f, d),
            "w_gate": prefix + (d, f)}


def param_shapes(s: Shape) -> Dict:
    """The parameter tree: each stack of layers on a leading axis."""
    d, V, n, m = s.d_model, s.vocab, s.lead, s.moe_layers
    return {
        "embed": (V, d),
        "final_norm": (d,),
        "lm_head": (d, V),
        "layers": {
            "lead": {"attn_norm": (n, d), "attn": _attn(n, s),
                     "mlp_norm": (n, d), "mlp": _swiglu((n,), d, s.d_ff)},
            "moe": {"attn_norm": (m, d), "attn": _attn(m, s),
                    "mlp_norm": (m, d),
                    "moe": {"router": (m, d, s.router),
                            **_swiglu((m, s.held), d, s.expert_ff)},
                    "shared_mlp": _swiglu((m,), d, s.shared_ff)},
        },
    }


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
        std = _std("/".join(k.key for k in path), shape, s)
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


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(s: Shape):
    """DeepseekV2YarnRotaryEmbedding: (inverse frequencies, cos/sin
    factor) of the rope dims."""
    y, dim, base = s.yarn, s.rope, s.rope_theta
    factor, orig = y["factor"], y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp  # 1: keep the base frequency
    inv = inter * (1.0 - mask) + extra * mask
    return inv, (_yarn_mscale(factor, y["mscale"])
                 / _yarn_mscale(factor, y["mscale_all_dim"]))


def softmax_scale(s: Shape) -> float:
    scale = (s.nope + s.rope) ** -0.5
    return scale * _yarn_mscale(s.yarn["factor"], s.yarn["mscale_all_dim"]) ** 2


def _rope(x, s: Shape):
    """x: (B, S, H, rope); positions 0..S-1 (one sample per row)."""
    S, dim = x.shape[1], x.shape[-1]
    inv, mscale = yarn_inv_freq(s)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * mscale)[None, :, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * mscale)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(s: Shape, h, a):
    B, S, _ = h.shape
    H, r = s.heads, s.kv_rank
    q = (h @ a["wq"]).reshape(B, S, H, s.nope + s.rope)
    q = jnp.concatenate([q[..., :s.nope], _rope(q[..., s.nope:], s)], -1)
    ckr = h @ a["wkv_a"]
    c = _norm(ckr[..., :r], a["kv_norm"], s.norm_eps)
    k_rope = _rope(ckr[..., r:][:, :, None, :], s)
    kv = (c @ a["wkv_b"]).reshape(B, S, H, s.nope + s.v_dim)
    k = jnp.concatenate(
        [kv[..., :s.nope], jnp.broadcast_to(k_rope, (B, S, H, s.rope))], -1)
    v = kv[..., s.nope:]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * softmax_scale(s)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return o.reshape(B, S, H * s.v_dim) @ a["wo"]


def _swiglu_apply(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def _routed(s: Shape, h, p, real):
    """The held experts' part of the routed FFN, and the layer's balance
    loss (summed over the rows, each a sample; ``real``: (B, S) its
    tokens)."""
    probs = jax.nn.softmax((h @ p["router"]).astype(jnp.float32), -1)
    top_w, top_i = jax.lax.top_k(probs, s.k)
    if s.renormalize:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(s.held):
        gate = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)
        ffn = {n: p[n][e] for n in ("w_up", "w_down", "w_gate")}
        out = out + gate[..., None].astype(h.dtype) * _swiglu_apply(h, ffn)
    aux = jnp.float32(0.0)
    if s.seq_aux:
        counts = jnp.sum(jax.nn.one_hot(top_i, s.router, dtype=jnp.float32)
                         * real[..., None, None], axis=(1, 2))  # (B, E)
        psum = jnp.sum(probs * real[..., None], axis=1)  # (B, E)
        T = jnp.maximum(jnp.sum(real, -1), 1.0)
        aux = s.aux_alpha * jnp.sum(jnp.sum(counts * psum, -1)
                                    * (s.router / s.k) / T)
    return s.routed_scale * out, aux


def _layer(s: Shape, x, lp, real):
    x = x + _mla(s, _norm(x, lp["attn_norm"], s.norm_eps), lp["attn"])
    h = _norm(x, lp["mlp_norm"], s.norm_eps)
    if "mlp" in lp:
        return x + _swiglu_apply(h, lp["mlp"]), jnp.float32(0.0)
    routed, aux = _routed(s, h, lp["moe"], real)
    return x + routed + _swiglu_apply(h, lp["shared_mlp"]), aux


def nll_sum(s: Shape, params, tokens, targets, mask):
    """Summed next-token cross-entropy of rows (B, S) under ``mask``, plus
    the balance loss.  A row holds one sample: its tokens are the loss
    tokens and the last one (none where the mask is empty)."""
    S = tokens.shape[1]
    n = jnp.sum(mask, -1)
    n = jnp.where(n > 0, n + 1, 0)
    real = (jnp.arange(S)[None, :] < n[:, None]).astype(jnp.float32)
    x = params["embed"][tokens]
    aux = jnp.float32(0.0)
    for stack in ("lead", "moe"):
        def body(carry, lp):
            x, aux = carry
            x, a = _layer(s, x, lp, real)
            return (x, aux + a), None
        (x, aux), _ = jax.lax.scan(body, (x, aux), params["layers"][stack])
    x = _norm(x, params["final_norm"], s.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum((logz - tgt) * mask) + aux


class Reference(common.Reference):
    param_shapes = staticmethod(param_shapes)
    init_params = staticmethod(init_params)
    nll_sum = staticmethod(nll_sum)


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------
def mla_params(run: dict) -> int:
    d, H, r = (run["hidden_size"], run["num_attention_heads"],
               run["kv_lora_rank"])
    dn, dr, dv = (run["qk_nope_head_dim"], run["qk_rope_head_dim"],
                  run["v_head_dim"])
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def matmul_params_per_token(run: dict) -> float:
    """Matmul parameters a token meets: the head; every layer's latent
    attention; the dense layers' SwiGLU; each expert layer's router and
    shared experts, and of its routed experts the expected k * held / E
    a token is routed to here."""
    d = run["hidden_size"]
    lead = run["first_k_dense_replace"]
    moe = run["num_hidden_layers"] - lead
    f = run["moe_intermediate_size"]
    routed = (3 * d * f * run["num_experts_per_tok"] * run["n_routed_experts"]
              / run["router_outputs"])
    per_moe = (d * run["router_outputs"] + 3 * d * run["n_shared_experts"] * f
               + routed)
    return (d * run["vocab_size"] + run["num_hidden_layers"] * mla_params(run)
            + lead * 3 * d * run["intermediate_size"] + moe * per_moe)


def attention_flops(run: dict, n: int) -> int:
    """QK^T over the qk head dim and PV over the v head dim, per causal
    pair inside a sample of n tokens, forward x3 with the backward."""
    pairs = n * (n + 1) // 2
    dqk = run["qk_nope_head_dim"] + run["qk_rope_head_dim"]
    per_layer = 3 * 2 * (dqk + run["v_head_dim"]) * run["num_attention_heads"]
    return run["num_hidden_layers"] * per_layer * pairs


def step_flops(run: dict, lengths: Iterable[int]) -> float:
    """Model FLOPs to train on samples of these lengths once, from the
    configuration's shapes, never from the compiled program: 6 per matmul
    parameter a token meets (``matmul_params_per_token``: routed experts
    at the k * held / E a token is routed to here, 0.75 for 6 of 64 with
    8 held), plus causal attention inside each sample.  The embedding
    lookup, padding and recomputation count for nothing."""
    lengths = [int(n) for n in lengths]
    return (6 * matmul_params_per_token(run) * sum(lengths)
            + sum(attention_flops(run, n) for n in lengths))
