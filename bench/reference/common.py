"""What every plain reference shares, whatever its architecture: seeded
key data, per-leaf norms, padded rows, AdamW and the loop that trains a
step of rows in blocks.

It imports nothing of the program under test.  A reference module
(``bench/reference/<name>.py``, see ``bench/reference/__init__.py``)
subclasses :class:`Reference` with its own parameter tree, weights and
loss, and re-exports the helpers here so that the harness finds them in
the module the configuration names.

Each step is trained as the program trains it: the token-mean next-token
cross-entropy over every sample of the step, then AdamW after a
global-norm gradient clip.  Each sample is one row padded to the
microbatch budget; the mask keeps padding out of the loss, and padding
sits after the sample, so causal attention never reads it.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def seed_key_data(seed: int) -> np.ndarray:
    """Two uint32 words of threefry key data from a seed of any size."""
    return np.random.SeedSequence(seed % 2 ** 64).generate_state(2, np.uint32)


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

    A subclass sets three functions of its architecture:
    ``param_shapes(shape)``, the parameter tree as shape tuples;
    ``init_params(shape, key_data, dtype)``; and
    ``nll_sum(shape, params, tokens, targets, mask)``, the summed
    next-token cross-entropy of rows (B, S) under ``mask``.

    ``dtype`` float32 with ``precision="highest"`` is the reference;
    bfloat16 with ``precision=None`` (the backend's default) is the
    lower-precision control:
    weights, activations, gradients and AdamW moments all in bfloat16.
    Rows go through in blocks of one row per device, and AdamW's moments
    wait in host memory while the gradient is computed, so a step fits
    where the program's does.
    """

    param_shapes = init_params = nll_sum = None

    def __init__(self, shape, opt: AdamW, S: int, devices,
                 dtype=jnp.float32, precision: Optional[str] = "highest"):
        self.s, self.opt, self.S = shape, opt, S
        self.dtype, self.precision = dtype, precision
        self.mesh = Mesh(np.asarray(devices), ("r",))
        n = len(devices)
        self.n = n
        shapes = self.param_shapes(shape)
        is_shape = lambda x: isinstance(x, tuple)
        self.p_sh = jax.tree.map(
            lambda sh: NamedSharding(self.mesh, _spec(sh, n)), shapes,
            is_leaf=is_shape)
        row_sh = NamedSharding(self.mesh, P("r", None))
        rep = NamedSharding(self.mesh, P())
        init, nll_sum = self.init_params, self.nll_sum
        self._init = jax.jit(lambda kd: init(shape, kd, dtype),
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
