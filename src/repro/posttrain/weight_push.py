"""ODC weight push: trainer shards -> materialized generator params.

Between training minibatches the generator's parameter copy must be
refreshed from the trainer's FSDP shards.  This is the posttrain face of
the paper's §3 primitives: the SAME per-parameter gather the training
step runs (p2p ring for 'odc', fused all-gather for 'collective',
two-tier for 'hier'), but one-sided and outside AD —
``CommBackend.weight_push`` — so for the p2p backends the refresh rides
the decentralized-PS path with **no global barrier**: each generator-side
consumer pulls shards from the owners without interrupting their compute
(``push_blocks_trainer`` is False for the ODC family, True for
'collective'; ``repro.sim.simulate_posttrain`` charges the timing).

On a single bulk-synchronous host the asynchrony itself cannot be
realized (same caveat as the training engines); what this module realizes
is the communication schedule — the lowered HLO of a push carries the
backend's permute chains / collectives, and the returned params are
bit-identical to the trainer's (gather is exact).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, List, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.core import backend as B
from repro.core.gspmd import (
    GSPMDConfig, _data_dims, _keep_axes, param_pspecs,
)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.obs import metrics as obs_metrics


def make_weight_push(cfg: ModelConfig, mesh, gcfg: GSPMDConfig):
    """Returns ``push(params) -> params_full``: every FSDP-sharded leaf
    gathered over the manual (data, pod) axes with the configured comm
    backend, leaving any model-axis tensor parallelism to GSPMD.  Jitted;
    call under the mesh context."""
    rules = gcfg.rules
    backend = B.get_backend(gcfg.comm)
    da = rules.data if isinstance(rules.data, tuple) else (rules.data,)
    manual = tuple(da) + ((rules.pod,) if rules.pod else ())

    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, gcfg.param_dtype),
        jax.random.PRNGKey(0))
    pspecs = param_pspecs(cfg, params_shape, rules, mesh)
    manual_pspecs = jax.tree.map(lambda s: _keep_axes(s, manual), pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
    out_specs = jax.tree.map(lambda s: P(*([None] * len(s))), manual_pspecs,
                             is_leaf=lambda x: isinstance(x, P))

    def push_local(params_local):
        def g(leaf, spec):
            dd = _data_dims(spec, da)
            if not dd:
                return leaf  # replicated over the FSDP axes already
            dim, axes = dd[0]
            ax = axes if len(axes) > 1 else axes[0]
            return backend.weight_push(
                ax, dim=dim, device_profile=gcfg.device_profile)(leaf)

        return jax.tree.map(g, params_local, pspecs)

    sharded = jax.shard_map(
        push_local, mesh=mesh, in_specs=(manual_pspecs,),
        out_specs=out_specs, check_vma=False, axis_names=set(manual))
    return jax.jit(sharded)


def push_comm_sites(cfg: ModelConfig, mesh,
                    gcfg: GSPMDConfig) -> List[Tuple[float, int, int]]:
    """Per-leaf ``(shard_bytes, world, group)`` of ONE full weight push —
    the byte-accounting twin of ``make_weight_push``'s gather set.  The
    push primitive itself carries no recording (its gather runs outside
    ``param_gather``'s traced sites), so the driver charges
    ``record_comm('push', ...)`` per push event from this list.  ``group``
    is the trailing (intra-tier) axis width — two-tier backends split
    their volume on it, flat backends ignore it."""
    rules = gcfg.rules
    da = rules.data if isinstance(rules.data, tuple) else (rules.data,)
    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, gcfg.param_dtype),
        jax.random.PRNGKey(0))
    pspecs = param_pspecs(cfg, params_shape, rules, mesh)
    sites: List[Tuple[float, int, int]] = []

    def visit(leaf, spec):
        dd = _data_dims(spec, da)
        if not dd:
            return leaf  # replicated over the FSDP axes: no push traffic
        _, axes = dd[0]
        world = 1
        for a in axes:
            world *= mesh.shape[a]
        if world > 1:
            nbytes = float(math.prod(leaf.shape)) * leaf.dtype.itemsize
            sites.append((nbytes / world, world, mesh.shape[axes[-1]]))
        return leaf

    jax.tree.map(visit, params_shape, pspecs)
    return sites


@dataclasses.dataclass
class WeightPusher:
    """Stateful wrapper: push + version bookkeeping for the pipeline.

    ``push(params, version)`` refreshes the generator copy and records the
    trainer version it now holds; ``pushes`` counts refreshes so drivers
    can report push traffic alongside staleness.
    """

    cfg: ModelConfig
    mesh: Any
    gcfg: GSPMDConfig
    version: int = -1
    pushes: int = 0

    def __post_init__(self):
        self._fn = make_weight_push(self.cfg, self.mesh, self.gcfg)
        self._sites = None  # computed on first recorded push
        self.params = None

    def _record_push(self):
        """Charge one full push's comm bytes to the active registry."""
        if obs_metrics.active() is None:
            return
        if self._sites is None:
            self._sites = push_comm_sites(self.cfg, self.mesh, self.gcfg)
        backend = B.get_backend(self.gcfg.comm)
        for shard_bytes, world, group in self._sites:
            backend.record_comm("push", shard_bytes, world=world,
                                group=group)

    def push(self, params, version: int):
        with self.mesh:
            self.params = self._fn(params)
        self._record_push()
        self.version = version
        self.pushes += 1
        return self.params

    @property
    def blocks_generator(self) -> bool:
        """Whether this backend's push is a fleet-wide barrier the decode
        slots must join (``push_blocks_trainer``: True for 'collective',
        False for the p2p ODC family — the paper's non-intrusive push)."""
        return bool(B.get_backend(self.gcfg.comm).push_blocks_trainer)

    def push_live(self, engine, params, version: int):
        """Refresh a RUNNING continuous engine between decode steps.

        Materializes the trainer's shards exactly as ``push`` does, then
        publishes them into the engine under the backend's barrier
        semantics: a collective push stalls every decode slot for the
        measured push time (a broadcast is a barrier every consumer
        joins), a p2p push lands on the engine's push lane only and
        overlaps subsequent decode steps.  In-flight requests keep the
        version they pinned at admission — the engine's no-torn-reads
        contract — so a push never perturbs a token already scheduled.
        """
        t0 = time.perf_counter()
        with self.mesh:
            self.params = self._fn(params)
        jax.block_until_ready(self.params)
        dt = time.perf_counter() - t0
        self._record_push()
        self.version = version
        self.pushes += 1
        engine.publish(self.params, version,
                       barrier=self.blocks_generator, push_time=dt)
        return self.params
