"""Plain reference of a dense decoder's SFT step: weights, loss, gradients
and AdamW, in straightforward ``jax.numpy``.

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
(``head = embed.T`` where the configuration ties them).  The loss is the
token-mean next-token cross-entropy over every sample of a step; AdamW
follows with a global-norm gradient clip.  Each sample is one row padded
to the microbatch budget; the mask keeps padding out of the loss, and
padding sits after the sample, so causal attention never reads it.

The norm scales are stored as offsets from 1 (``1 + w``, initialised at
0): the same function and the same gradients as a scale initialised at 1.
Departure from Qwen2: no q/k/v biases (the program has none).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


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


def seed_key_data(seed: int) -> np.ndarray:
    """Two uint32 words of threefry key data from a seed of any size."""
    return np.random.SeedSequence(seed % 2 ** 64).generate_state(2, np.uint32)


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


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float


def rows(samples: Sequence[np.ndarray], S: int):
    """One padded row per sample: tokens, next-token targets, loss mask."""
    n = len(samples)
    tok = np.zeros((n, S), np.int32)
    tgt = np.zeros((n, S), np.int32)
    mask = np.zeros((n, S), np.float32)
    for i, t in enumerate(samples):
        tok[i, :len(t)] = t
        tgt[i, :len(t) - 1] = t[1:]
        mask[i, :len(t) - 1] = 1.0
    return tok, tgt, mask


@jax.jit
def leaf_sq_norms(tree):
    """Squared Frobenius norm of every leaf, each stacked layer apart, on
    the device; :func:`sq_to_norms` names them (``layers/attn/wq#3`` is
    layer 3's query weight)."""
    def one(path, x):
        x = x.astype(jnp.float32)
        if path[0].key == "layers":
            return jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), 1)
        return jnp.sum(jnp.square(x))
    return jax.tree_util.tree_map_with_path(one, tree)


@jax.jit
def change_sq_norms(a, b):
    """:func:`leaf_sq_norms` of a - b, in float32."""
    return leaf_sq_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def sq_to_norms(tree) -> Dict[str, float]:
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(k.key for k in path)
        x = np.sqrt(np.asarray(x, np.float64))
        if x.ndim:
            out.update({f"{name}#{i}": float(v) for i, v in enumerate(x)})
        else:
            out[name] = float(x)
    return out


def _spec(shape, n):
    """Shard the largest dimension the device count divides; else none."""
    dims = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in dims:
        if shape[i] % n == 0 and shape[i] >= n:
            return P(*[("r" if j == i else None) for j in range(len(shape))])
    return P()


class Reference:
    """Runs steps of the plain model over the given devices.

    ``dtype`` float32 with ``precision="highest"`` is the reference;
    bfloat16 with ``precision=None`` (the backend's default) is the
    lower-precision control:
    weights, activations, gradients and AdamW moments all in bfloat16.
    Rows go through in blocks of one row per device, and AdamW's moments
    wait in host memory while the gradient is computed, so a step fits
    where the program's does.
    """

    def __init__(self, shape: Shape, opt: AdamW, S: int, devices,
                 dtype=jnp.float32, precision: Optional[str] = "highest"):
        self.s, self.opt, self.S = shape, opt, S
        self.dtype, self.precision = dtype, precision
        self.mesh = Mesh(np.asarray(devices), ("r",))
        n = len(devices)
        self.n = n
        shapes = param_shapes(shape)
        is_shape = lambda x: isinstance(x, tuple)
        self.p_sh = jax.tree.map(
            lambda sh: NamedSharding(self.mesh, _spec(sh, n)), shapes,
            is_leaf=is_shape)
        row_sh = NamedSharding(self.mesh, P("r", None))
        rep = NamedSharding(self.mesh, P())
        self._init = jax.jit(lambda kd: init_params(shape, kd, dtype),
                             out_shardings=self.p_sh)
        zeros = lambda p: jax.tree.map(jnp.zeros_like, p)
        self._zeros = jax.jit(zeros, out_shardings=self.p_sh)

        def grad_block(params, acc, tok, tgt, mask):
            l, g = jax.value_and_grad(
                lambda p: nll_sum(shape, p, tok, tgt, mask))(params)
            return jax.tree.map(lambda a, b: a + b.astype(a.dtype), acc, g), l

        self._grad = jax.jit(
            grad_block, in_shardings=(self.p_sh, self.p_sh, row_sh, row_sh,
                                      row_sh),
            out_shardings=(self.p_sh, rep), donate_argnums=(1,))
        self._update = jax.jit(
            self._adamw, donate_argnums=(0, 1, 2, 3),
            in_shardings=(self.p_sh, self.p_sh, self.p_sh, self.p_sh, rep,
                          rep),
            out_shardings=(self.p_sh, self.p_sh, self.p_sh, rep))

    def _adamw(self, params, grads, m, v, step, tokens):
        o = self.opt
        dt = self.dtype
        grads = jax.tree.map(lambda g: g / tokens.astype(dt), grads)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree.leaves(grads)))
        if o.grad_clip > 0:
            scale = jnp.minimum(1.0, o.grad_clip / jnp.maximum(gn, 1e-12))
            grads = jax.tree.map(lambda g: g * scale.astype(dt), grads)
        c1 = (1.0 - o.b1 ** step).astype(dt)
        c2 = (1.0 - o.b2 ** step).astype(dt)
        m = jax.tree.map(lambda m, g: (o.b1 * m + (1 - o.b1) * g).astype(dt),
                         m, grads)
        v = jax.tree.map(
            lambda v, g: (o.b2 * v + (1 - o.b2) * g * g).astype(dt), v, grads)

        def upd(p, m, v):
            delta = (m / c1) / (jnp.sqrt(v / c2) + o.eps)
            if o.weight_decay:
                delta = delta + o.weight_decay * p
            return (p - o.lr * delta).astype(dt)

        return jax.tree.map(upd, params, m, v), m, v, grads

    def init(self, seed: int):
        return self._init(seed_key_data(seed))

    def run(self, seed: int, steps: List[Sequence[np.ndarray]],
            tokens: Optional[List[int]] = None) -> dict:
        """Train ``len(steps)`` steps from the seed's weights.  Returns each
        step's loss, the first step's clipped gradient per leaf, and the
        parameters' change over all the steps per leaf (norms).  Each
        step's loss and gradient are divided by its samples' loss tokens,
        or by ``tokens[i]`` where given."""
        with (jax.default_matmul_precision(self.precision) if self.precision
              else contextlib.nullcontext()):
            params = self.init(seed)
            m = jax.device_get(self._zeros(params))
            v = jax.tree.map(np.copy, m)
            losses, grad_norms = [], None
            for i, samples in enumerate(steps, start=1):
                acc = self._zeros(params)
                lsum = 0.0
                n_tok = (tokens[i - 1] if tokens
                         else sum(len(t) - 1 for t in samples))
                for b in range(0, len(samples), self.n):
                    block = list(samples[b:b + self.n])
                    pad = self.n - len(block)
                    tok, tgt, mask = rows(block + [block[0]] * pad, self.S)
                    mask[len(block):] = 0.0
                    acc, l = self._grad(params, acc, tok, tgt, mask)
                    lsum += float(l)
                losses.append(lsum / n_tok)
                m, v = jax.device_put((m, v), (self.p_sh, self.p_sh))
                params, m, v, g = self._update(
                    params, acc, m, v, jnp.float32(i), jnp.float32(n_tok))
                m, v = jax.device_get((m, v))
                if i == 1:
                    grad_norms = sq_to_norms(leaf_sq_norms(g))
                del g
            del m, v
            p0 = self.init(seed)
            out = {"loss": losses, "grad": grad_norms,
                   "change": sq_to_norms(change_sq_norms(params, p0))}
        del params, p0
        return out
