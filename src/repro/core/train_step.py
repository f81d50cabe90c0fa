"""End-to-end FSDP train step: shard_map gradient pass + sharded AdamW.

The gradient pass runs under ``shard_map`` over the FSDP axis with the
chosen (comm, schedule) — ``comm`` is a ``repro.core.backend`` registry
name and the schedule loop is the shared ``build_schedule_grad`` seam; the
optimizer update runs on the globally-sharded storage arrays under plain
jit (elementwise, no communication — the "server" update of the
decentralized PS).

Vocabulary note: the executable engines take ``comm`` (how bytes move:
'collective' | 'odc' | 'odc-overlap' | 'hier') and ``schedule`` (where
gathers/scatters are placed: 'layer' | 'minibatch' | 'overlap'); the
simulator's ``scheme=`` names the same backends (legacy 'overlap' aliases
'odc-overlap').  All three knobs resolve through the same registry.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fsdp as F
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init, adamw_update


def batch_pspecs(batch, axis="data"):
    """Microbatch stacks are (M, local_batch, ...): shard dim 1 over the DP
    axis."""
    return jax.tree.map(
        lambda x: P(None, axis, *([None] * (x.ndim - 2))), batch
    )


def make_loss_sum_fn(cfg, *, remat=True, block_kv=512, moe_groups=0):
    def loss_sum_fn(params_or_storage, mb, pxform):
        val, metrics = T.loss(
            cfg, params_or_storage, mb, remat=remat, block_kv=block_kv,
            moe_groups=moe_groups, pxform=pxform, reduction="sum",
        )
        return val, metrics["tokens"]

    return loss_sum_fn


class FSDPTrainer:
    """Owns sharded storage + optimizer state and the jitted step fn."""

    def __init__(self, cfg, mesh, fcfg: F.FSDPConfig, opt_cfg: AdamWConfig,
                 *, remat=True, block_kv=512, moe_groups=0):
        self.cfg = cfg
        self.mesh = mesh
        self.fcfg = fcfg
        self.opt_cfg = opt_cfg
        self.loss_sum_fn = make_loss_sum_fn(
            cfg, remat=remat, block_kv=block_kv, moe_groups=moe_groups
        )
        self._step_fn = None

    # ------------------------------------------------------------------
    def init(self, params):
        ax = self.fcfg.axis_name
        n = 1
        for a in ([ax] if isinstance(ax, str) else ax):
            n *= self.mesh.shape[a]
        storage = F.shard_params(self.cfg, params, n)
        storage = F.place_storage(storage, self.mesh, ax)
        opt_state = jax.jit(adamw_init)(storage)
        return storage, opt_state

    # ------------------------------------------------------------------
    def step(self, storage, opt_state, batch, lr_scale=1.0):
        if self._step_fn is None:
            self._step_fn = self._build(batch)
        return self._step_fn(storage, opt_state, batch, jnp.float32(lr_scale))

    def _build(self, batch_example):
        fcfg, mesh = self.fcfg, self.mesh
        grad_fn = F.fsdp_loss_and_grad(self.loss_sum_fn, fcfg)
        ax = fcfg.axis_name

        def whole_step(storage, opt_state, batch, lr_scale):
            sspecs = F.storage_pspecs(storage, ax)
            bspecs = batch_pspecs(batch, ax)
            axis_names = set([ax] if isinstance(ax, str) else list(ax))
            if fcfg.pod_axis:
                axis_names.add(fcfg.pod_axis)
                # batch additionally sharded over the pod axis on dim 1
                bspecs = jax.tree.map(
                    lambda x: P(None, (fcfg.pod_axis, ax) if isinstance(ax, str)
                                else tuple([fcfg.pod_axis] + list(ax)),
                                *([None] * (x.ndim - 2))),
                    batch,
                )
            sharded_grad = jax.shard_map(
                grad_fn,
                mesh=mesh,
                in_specs=(sspecs, bspecs),
                out_specs=(sspecs, P()),
                check_vma=False,
                axis_names=axis_names,
            )
            grads, metrics = sharded_grad(storage, batch)
            new_storage, new_opt = adamw_update(
                self.opt_cfg, storage, grads, opt_state, lr_scale=lr_scale
            )
            return new_storage, new_opt, metrics

        return jax.jit(whole_step, donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    def lower(self, storage, opt_state, batch_shapes):
        """Lower (no execution) for dry-run/roofline analysis."""
        if self._step_fn is None:
            self._step_fn = self._build(batch_shapes)
        return self._step_fn.lower(
            storage, opt_state, batch_shapes, jnp.float32(1.0)
        )
