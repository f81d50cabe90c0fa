"""Sharded AdamW.

Operates leaf-wise on any pytree — including ``FSDPShard`` storage, where it
runs entirely on each device's own shard (the "server" role of the
decentralized parameter server: optimizer state never moves).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 1e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0  # global-norm clip; 0 disables


def adamw_init(params):
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    return {"m": zeros(params), "v": zeros(params), "step": jnp.zeros((), jnp.int32)}


def _global_norm(tree):
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


@jax.named_scope("adamw")
def adamw_update(cfg: AdamWConfig, params, grads, state, lr_scale=1.0,
                 grad_norm=None):
    """One AdamW step, the clip included, under the ``adamw`` name scope.
    ``grad_norm`` may be supplied externally when the local leaves are
    shards of a larger tree (pass the true global norm)."""
    step = state["step"] + 1
    if cfg.grad_clip > 0:
        gn = _global_norm(grads) if grad_norm is None else grad_norm
        scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gn, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)

    b1, b2 = cfg.b1, cfg.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    lr = cfg.lr * lr_scale

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g32
        v = b2 * v + (1 - b2) * jnp.square(g32)
        mhat = m / c1
        vhat = v / c2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay:
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}
