"""First-class communication-backend registry — the (comm, schedule, scheme)
seam.

Before this module the paper's knobs were raw strings re-branched in four
places: ``core/odc.py`` (``if comm == "collective"``), ``core/fsdp.py`` /
``core/gspmd.py`` (``if schedule == "minibatch"``), and ``sim/engine.py``
(``scheme in ("odc", "overlap")``).  A :class:`CommBackend` now owns every
side of one communication strategy:

  * the executable primitives (inside ``shard_map``): ``gather`` /
    ``scatter_accumulate`` and the differentiable ``param_gather`` wrapper
    whose custom VJP turns a parameter gather into the matching gradient
    scatter-accumulate;
  * the hardware realization hooks (``kernel_gather`` /
    ``kernel_scatter_accumulate`` — the one-sided remote-DMA Pallas kernels
    in ``repro.kernels``), where one exists;
  * its simulator cost hook (``layer_comm_time``) and scheduling
    ``policy`` (a ``repro.sim.timeline.SchedulingPolicy`` object — how the
    timeline engine places its events: per-layer lockstep, independent
    device progress, or pipelined prefetch; ``discipline`` is the policy's
    name, kept as the legacy string view);
  * the posttrain **weight push** (``weight_push`` / ``weight_push_time`` /
    ``push_blocks_trainer``): the trainer→generator parameter refresh the
    asynchronous rollout pipeline (``repro.posttrain``) issues between
    minibatches — the same bytes as a gather, but one-sided and
    non-differentiable, so p2p backends refresh the generator without a
    trainer-side barrier while 'collective' stalls every trainer device.

Registered backends (canonical name → semantics):

  ``collective``   fused ``all_gather`` / ``psum_scatter`` (FSDP baseline;
                   lockstep per-layer barriers in the simulator).
  ``odc``          p2p ring gather / scatter-accumulate (paper §3);
                   independent device progress, barrier at the minibatch end.
  ``odc-overlap``  same primitives as ``odc`` but implies the double-buffered
                   prefetch schedule (``schedule='overlap'``); pipelined in
                   the simulator.  Alias: ``overlap`` (the legacy sim scheme
                   name).
  ``hier``         hierarchical (node × device) ODC: parameters sharded over
                   a 2D FSDP mesh; gather = intra-node collective all-gather
                   + inter-node profile-ordered p2p ring (scatter mirrors
                   it).  Keeps the collective's NVSwitch-class intra-node
                   path while the cross-node traffic rides node-level p2p
                   streams — avoiding both the per-layer barrier and ODC's
                   cross-node efficiency penalty (paper Fig. 11).
  ``pipe``         pipeline-parallel ODC: parameters sharded over a 2D
                   ``(pipe, data)`` mesh — hier's two-tier transport with
                   the pipe axis as the p2p tier, so stage boundaries are
                   direct sends, never collectives — scheduled by the 1F1B
                   microbatch order (``schedule='1f1b'`` implied; the sim
                   places per-stage lanes from the same
                   ``instructions_1f1b`` stream the executable loop
                   issues).
  ``pipe-int8``    ``pipe`` with the chunked-int8 compressed wire: the
                   cross-stage ring payload is quantized (1 byte/value +
                   one f32 scale per ``odc.INT8_CHUNK`` values) via
                   ``odc.ring_gather_q8`` / ``ring_scatter_accumulate_q8``
                   and their Pallas kernels; the intra-stage collective
                   tier stays full precision.  With compression off
                   (``pipe``) the transport is bit-exact with ``hier``.
  ``cp``           context-parallel ring attention over a ``(data, cp)``
                   mesh: parameter transport is flat ODC's (identical
                   bytes), the sequence dim is sharded over ``cp``, and
                   attention circulates KV chunks p2p around the cp ring
                   (``core.cp.ring_attention`` — bit-identical to
                   monolithic flash attention on the gathered sequence).
                   Alias: ``cp-ring``.

Every legacy string flag keeps working: ``comm='collective'|'odc'`` and sim
``scheme='collective'|'odc'|'overlap'`` all resolve through
:func:`get_backend`, and the resolved backends run the exact ops the old
string ladders selected — byte-identical numerics on the old paths.

``build_schedule_grad`` is the second half of the seam: the gradient-loop
builder for the three schedules (``layer`` / ``minibatch`` / ``overlap``),
previously duplicated between ``core/train_step.py::FSDPTrainer._build``
and ``core/gspmd.py::make_train_step``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.balance.cost import DeviceProfile
from repro.core import odc
from repro.obs import metrics as obs_metrics
from repro.sim.timeline import (
    CONTEXT_RING,
    INDEPENDENT,
    LOCKSTEP,
    PIPE_1F1B,
    PIPELINED,
    ContextRingPolicy,
    SchedulingPolicy,
    instructions_1f1b,
)

AxisNames = Union[str, Sequence[str]]

#: the engine schedule vocabulary (where gathers/scatters are *placed*);
#: orthogonal to the backend (how each gather/scatter *moves bytes*).
SCHEDULES = ("layer", "minibatch", "overlap", "1f1b")


# ===========================================================================
# backend base + registry
# ===========================================================================
class CommBackend:
    """One communication strategy, end to end (executable + simulated)."""

    #: canonical registry name
    name: str = "?"
    #: legacy spellings that resolve to this backend
    aliases: tuple = ()
    #: timeline scheduling policy when this backend is named as a scheme:
    #: LOCKSTEP (per-layer barrier over all devices, paper Eq. 1),
    #: INDEPENDENT (each device runs free until the minibatch end), or
    #: PIPELINED (independent + per-layer comm hidden under compute).
    #: A policy object, so ``repro.sim`` can compose any backend's cost
    #: model with any policy (``simulate_minibatch(..., policy=...)``).
    policy: SchedulingPolicy = INDEPENDENT
    #: engine schedule this backend forces (None = honor the caller's knob)
    implied_schedule: Optional[str] = None
    #: whether a trainer→generator weight push stalls the TRAINER: a fused
    #: collective broadcast is a barrier every trainer device joins, while
    #: the p2p backends push one-sided (the generator pulls shards without
    #: interrupting the owner's compute — paper §3.2's non-intrusive
    #: property, the whole point of the posttrain weight-push primitive).
    push_blocks_trainer: bool = False

    @property
    def discipline(self) -> str:
        """Legacy string view of the scheduling policy."""
        return self.policy.name

    # -- comm-byte accounting (repro.obs) -----------------------------------
    # One volume model serves both sides of the seam: the executable
    # primitives record through ``_record_traced`` (at jit trace time,
    # into the per-step ledger) and the simulator cost hooks record
    # through ``_sim_record_layer`` / the push and ring-hop hooks
    # (immediately), all via ``comm_volume`` — so a simulated and a real
    # run of one config emit the SAME counter names:
    #
    #   comm.messages / comm.bytes_logical / comm.bytes_wire
    #       {backend=<name>, op=gather|scatter|push|ring_hop,
    #        tier=flat|intra|inter}
    #   comm.message_bytes (histogram, log2 buckets), same labels
    #
    # Everything below is pure addition on the side: no recording call
    # feeds back into gathered values or simulated float arithmetic, and
    # with no registry active every site returns immediately.

    def wire_factor(self, tier: str) -> float:
        """Wire bytes per logical byte on ``tier`` (compression ratio)."""
        return 1.0

    def comm_volume(self, op: str, shard_bytes: float, world: int,
                    group: Optional[int] = None):
        """``[(tier, messages, logical_bytes, wire_bytes)]`` for moving one
        ``shard_bytes`` shard set with this backend on a ``world``-wide
        axis (``group`` = intra tier width for two-tier backends).

        Base model is the flat p2p ring: ``world - 1`` hops, each carrying
        one shard — ``(world-1)/world`` of the full tensor in total.
        """
        if world <= 1:
            return []
        logical = (world - 1) * shard_bytes
        return [("flat", world - 1, logical,
                 logical * self.wire_factor("flat"))]

    def record_comm(self, op: str, shard_bytes: float, *, world: int,
                    group: Optional[int] = None, scale: float = 1.0,
                    per_step: bool = False):
        """Record one shard-set move into the active registry (a no-op
        without one).  ``per_step=True`` routes through the trace-time
        ledger (``Counter.inc_per_step``) — for sites that run inside a
        compiled program and fire once per trace, not once per step."""
        reg = obs_metrics.active()
        if reg is None:
            return
        for tier, msgs, logical, wire in self.comm_volume(
                op, shard_bytes, world, group):
            labels = dict(backend=self.name, op=op, tier=tier)
            n = reg.counter("comm.messages", **labels)
            bl = reg.counter("comm.bytes_logical", **labels)
            bw = reg.counter("comm.bytes_wire", **labels)
            h = reg.histogram("comm.message_bytes", **labels)
            msg_bytes = wire / msgs if msgs else 0.0
            if per_step:
                n.inc_per_step(msgs * scale)
                bl.inc_per_step(logical * scale)
                bw.inc_per_step(wire * scale)
                h.observe_per_step(msg_bytes, msgs * scale)
            else:
                n.inc(msgs * scale)
                bl.inc(logical * scale)
                bw.inc(wire * scale)
                h.observe(msg_bytes, msgs * scale)

    def _axis_sizes(self, axis_name: AxisNames):
        """``(world, group)`` of the sharding axes, readable only inside a
        shard_map trace; ``(0, None)`` outside one (recording skipped)."""
        try:
            return odc.axis_size(axis_name), None
        except Exception:
            return 0, None

    def _record_traced(self, op: str, x, axis_name: AxisNames, *,
                       full: bool = False):
        """Trace-time accounting for one executable primitive: called on
        the per-device view inside shard_map, so ``x`` is the local shard
        (or, with ``full=True``, the full-size tensor — the gradient
        cotangent a scatter-accumulate consumes)."""
        if obs_metrics.active() is None:
            return
        world, group = self._axis_sizes(axis_name)
        if world <= 1:
            return
        nbytes = float(x.size) * x.dtype.itemsize
        shard = nbytes / world if full else nbytes
        self.record_comm(op, shard, world=world, group=group, per_step=True)

    def _sim_group(self, comm_model, devices: int) -> Optional[int]:
        """The intra-tier width the simulator models (None = flat)."""
        return None

    def _sim_record_layer(self, comm_model, devices: int):
        """Simulator-side twin of ``_record_traced``: one per-layer shard
        set gathered + scattered, recorded when a cost hook prices it."""
        reg = obs_metrics.active()
        if reg is None or devices <= 1:
            return
        shard = comm_model.layer_param_bytes / devices
        group = self._sim_group(comm_model, devices)
        self.record_comm("gather", shard, world=devices, group=group)
        self.record_comm("scatter", shard, world=devices, group=group)

    def _sim_record_push(self, comm_model, devices: int, layers: int):
        reg = obs_metrics.active()
        if reg is None or devices <= 1 or layers <= 0:
            return
        shard = comm_model.layer_param_bytes / devices
        self.record_comm("push", shard, world=devices,
                         group=self._sim_group(comm_model, devices),
                         scale=float(layers))

    # -- executable primitives (inside shard_map) ---------------------------
    def gather(self, x, axis_name: AxisNames, *,
               device_profile: Optional[DeviceProfile] = None):
        """Local shard (c, ...) -> full tensor (n*c, ...) along dim 0."""
        raise NotImplementedError

    def scatter_accumulate(self, y, axis_name: AxisNames, *,
                           device_profile: Optional[DeviceProfile] = None):
        """Full-size contribution (n*c, ...) -> owned accumulated shard
        (c, ...) along dim 0."""
        raise NotImplementedError

    def param_gather(self, axis_name: AxisNames, *, dim: int = 0,
                     device_profile: Optional[DeviceProfile] = None):
        """gather(x_shard) -> x_full along ``dim`` with a custom VJP whose
        backward pass is this backend's gradient scatter-accumulate
        (paper §3: differentiating a parameter *gather* emits the gradient
        *scatter-accumulate*)."""
        g_fn = functools.partial(self.gather, axis_name=axis_name,
                                 device_profile=device_profile)
        s_fn = functools.partial(self.scatter_accumulate,
                                 axis_name=axis_name,
                                 device_profile=device_profile)

        @jax.named_scope("comm.gather")
        def _g(x):
            self._record_traced("gather", x, axis_name)
            if dim == 0:
                return g_fn(x)
            return jnp.moveaxis(g_fn(jnp.moveaxis(x, dim, 0)), 0, dim)

        @jax.named_scope("comm.scatter")
        def _s(y):
            self._record_traced("scatter", y, axis_name, full=True)
            if dim == 0:
                return s_fn(y)
            return jnp.moveaxis(s_fn(jnp.moveaxis(y, dim, 0)), 0, dim)

        @jax.custom_vjp
        def gather(x):
            return _g(x)

        def fwd(x):
            return _g(x), None

        def bwd(_, ct):
            return (_s(ct),)

        gather.defvjp(fwd, bwd)
        return gather

    # -- posttrain weight push ---------------------------------------------
    def weight_push(self, axis_name: AxisNames, *, dim: int = 0,
                    device_profile: Optional[DeviceProfile] = None):
        """Non-differentiable shard refresh: trainer shard -> materialized
        tensor for a generator-side consumer (``repro.posttrain``).  The
        same bytes move as in ``param_gather``'s forward — p2p ring for the
        ODC family, fused all-gather for 'collective' — but no VJP is
        attached (rollout generation never differentiates through the
        push) and gradients are explicitly stopped."""
        g_fn = functools.partial(self.gather, axis_name=axis_name,
                                 device_profile=device_profile)

        def push(x):
            x = jax.lax.stop_gradient(x)
            if dim == 0:
                return g_fn(x)
            return jnp.moveaxis(g_fn(jnp.moveaxis(x, dim, 0)), 0, dim)

        return push

    def weight_push_time(self, comm_model, devices: int,
                         layers: int) -> float:
        """Seconds one full trainer→generator parameter refresh costs in
        ``repro.sim``'s posttrain model: ``layers`` per-layer shard sets
        moved with this backend's wire cost.  Whether the TRAINER also
        stalls for it is ``push_blocks_trainer``."""
        if layers <= 0:
            return 0.0
        self._sim_record_push(comm_model, devices, layers)
        # price through layer_comm_time WITHOUT its gather/scatter
        # recording — these bytes are a push, accounted just above
        with obs_metrics.suppressed():
            return layers * self.layer_comm_time(comm_model, devices)

    # -- hardware realization (Pallas one-sided remote DMA) -----------------
    #: whether repro.kernels carries a one-sided remote-DMA realization of
    #: this backend's primitives (the jnp primitives are its oracle)
    has_kernels: bool = False

    def kernel_gather(self, x_shard, axis_name: str, **kw):
        raise NotImplementedError(
            f"backend {self.name!r} has no Pallas kernel realization")

    def kernel_scatter_accumulate(self, y, axis_name: str, **kw):
        raise NotImplementedError(
            f"backend {self.name!r} has no Pallas kernel realization")

    # -- simulator cost hook ------------------------------------------------
    def layer_comm_time(self, comm_model, devices: int) -> float:
        """Seconds of per-layer FSDP communication charged by ``repro.sim``
        for this backend on a ``devices``-wide axis (``comm_model`` is a
        ``sim.engine.CommModel``)."""
        raise NotImplementedError

    def __repr__(self):
        return f"<CommBackend {self.name!r}>"


_REGISTRY: dict = {}


def register_backend(backend: CommBackend) -> CommBackend:
    """Register a backend under its canonical name and aliases."""
    for name in (backend.name,) + tuple(backend.aliases):
        if name in _REGISTRY:
            raise ValueError(f"comm backend name {name!r} already registered "
                             f"(by {_REGISTRY[name].name!r})")
        _REGISTRY[name] = backend
    return backend


def get_backend(name) -> CommBackend:
    """Resolve a backend by canonical name or legacy alias.  Passing an
    already-resolved :class:`CommBackend` returns it unchanged."""
    if isinstance(name, CommBackend):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown comm backend {name!r}; registered: "
            f"{sorted(set(b.name for b in _REGISTRY.values()))} "
            f"(+ aliases {sorted(n for n, b in _REGISTRY.items() if n != b.name)})"
        ) from None


def backend_names(*, include_aliases: bool = False):
    """Canonical backend names (optionally with legacy aliases), for CLI
    ``choices=`` lists and error messages."""
    names = sorted(set(b.name for b in _REGISTRY.values()))
    if include_aliases:
        names += sorted(n for n, b in _REGISTRY.items() if n != b.name)
    return tuple(names)


def resolve(comm, schedule: str):
    """(backend, schedule) for an engine config: the backend may force its
    implied schedule (``comm='odc-overlap'`` ⇒ ``schedule='overlap'``);
    otherwise the caller's schedule knob is honored unchanged."""
    backend = get_backend(comm)
    schedule = backend.implied_schedule or schedule
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; one of {SCHEDULES}")
    return backend, schedule


# ===========================================================================
# the registered backends
# ===========================================================================
class CollectiveBackend(CommBackend):
    """Fused XLA collectives — the FSDP baseline (paper Fig. 1)."""

    name = "collective"
    policy = LOCKSTEP
    push_blocks_trainer = True  # a fused broadcast is a global barrier

    def comm_volume(self, op, shard_bytes, world, group=None):
        # same logical bytes as the ring, fused into ONE collective launch
        if world <= 1:
            return []
        logical = (world - 1) * shard_bytes
        return [("flat", 1, logical, logical * self.wire_factor("flat"))]

    def gather(self, x, axis_name, *, device_profile=None):
        return odc.collective_gather(x, axis_name)

    def scatter_accumulate(self, y, axis_name, *, device_profile=None):
        return odc.collective_scatter(y, axis_name)

    def layer_comm_time(self, comm_model, devices):
        self._sim_record_layer(comm_model, devices)
        return comm_model.layer_comm_time(devices, False)


class ODCBackend(CommBackend):
    """p2p ring gather / scatter-accumulate (paper §3, Fig. 5); the chains
    walk a ``DeviceProfile``'s ring order when one applies."""

    name = "odc"
    has_kernels = True

    def gather(self, x, axis_name, *, device_profile=None):
        return odc.ring_gather(x, axis_name, device_profile=device_profile)

    def scatter_accumulate(self, y, axis_name, *, device_profile=None):
        return odc.ring_scatter_accumulate(y, axis_name,
                                           device_profile=device_profile)

    def kernel_gather(self, x_shard, axis_name, **kw):
        from repro.kernels import ops
        return ops.odc_gather(x_shard, axis_name, **kw)

    def kernel_scatter_accumulate(self, y, axis_name, **kw):
        from repro.kernels import ops
        return ops.odc_scatter_accumulate(y, axis_name, **kw)

    def layer_comm_time(self, comm_model, devices):
        self._sim_record_layer(comm_model, devices)
        return comm_model.layer_comm_time(devices, True)


class OverlapODCBackend(ODCBackend):
    """ODC with the double-buffered prefetch issue order: same gathers and
    scatter-accumulates as ``odc`` (bit-identical values), pipelined one
    layer ahead.  ``schedule='overlap'`` is implied in the engines; in the
    simulator comm is charged only where it exceeds compute."""

    name = "odc-overlap"
    aliases = ("overlap",)  # legacy sim scheme spelling
    policy = PIPELINED
    implied_schedule = "overlap"


class HierBackend(CommBackend):
    """Hierarchical (node × device) ODC.

    Parameters are sharded over a 2D FSDP mesh ``(node, device)`` —
    node-major, so a ``PartitionSpec(('node', 'device'))`` dim lays chunks
    out exactly as the two-stage gather reconstructs them:

      gather   x_shard --all_gather('device')--> node chunk
                       --ring_gather('node')---> full tensor
      scatter  ct_full --ring_scatter_accumulate('node')--> node chunk
                       --psum_scatter('device')----------> owned shard

    The intra-node stage rides the fused collective on NVSwitch-class
    links; only the inter-node stage is p2p, and it moves ONE aggregated
    node-level stream per hop (full RDMA bandwidth — no ``odc``-style
    cross-node efficiency penalty, paper Fig. 11) while keeping ODC's
    minibatch-level barrier discipline.

    A leaf sharded over a single (trailing) axis — the 1-D norms/biases
    that ``leaf_pspec`` shards over the innermost data axis only — uses
    that tier's native collective; hierarchy needs at least two axes.

    ``device_profile`` granularity: a profile over the devices of the
    *inter* ring is used directly; a device-granular profile over the full
    ``node × device`` world is collapsed to node granularity
    (``DeviceProfile.node_collapse`` — a node is gated by its slowest
    member) before ordering the inter-node ring.
    """

    name = "hier"

    @staticmethod
    def split_axes(axis_name: AxisNames):
        """(inter_axes, intra_axis): the trailing (minor) mesh axis is the
        intra-node tier, everything before it the inter-node ring."""
        ax = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        if len(ax) < 2:
            return None, ax[0]
        inter = ax[:-1] if len(ax) > 2 else ax[0]
        return inter, ax[-1]

    def _axis_sizes(self, axis_name):
        inter, intra = self.split_axes(axis_name)
        try:
            g = odc.axis_size(intra)
            if inter is None:  # single-tier leaf: one intra collective
                return g, g
            return g * odc.axis_size(inter), g
        except Exception:
            return 0, None

    def _sim_group(self, comm_model, devices):
        return min(comm_model.devices_per_node, devices)

    def comm_volume(self, op, shard_bytes, world, group=None):
        """Two-tier split: one fused intra collective per move plus
        ``n - 1`` node-level p2p hops, where ``n = world / group`` nodes
        each hold a ``group``-shard chunk.  ``group >= world`` (or no
        group) degenerates to a single intra-tier collective — the 1-D
        leaf / single-node path."""
        if world <= 1:
            return []
        g = group or world
        if g >= world:
            logical = (world - 1) * shard_bytes
            return [("intra", 1, logical,
                     logical * self.wire_factor("intra"))]
        n = world // g
        intra = (g - 1) * shard_bytes  # this node's chunk, minus my shard
        inter = (n - 1) * g * shard_bytes  # the other nodes' chunks
        return [
            ("intra", 1, intra, intra * self.wire_factor("intra")),
            ("inter", n - 1, inter, inter * self.wire_factor("inter")),
        ]

    def _node_profile(self, device_profile, inter: AxisNames,
                      intra: str) -> Optional[DeviceProfile]:
        if device_profile is None:
            return None
        nodes = odc.axis_size(inter)
        if device_profile.world_size == nodes:
            return device_profile
        group = odc.axis_size(intra)
        if device_profile.world_size == nodes * group:
            return device_profile.node_collapse(group)
        return None  # size mismatch — natural ring (same as flat ODC)

    def gather(self, x, axis_name, *, device_profile=None):
        inter, intra = self.split_axes(axis_name)
        if inter is None:  # single-tier leaf: native collective
            return odc.collective_gather(x, intra)
        x = odc.collective_gather(x, intra)
        prof = self._node_profile(device_profile, inter, intra)
        return odc.ring_gather(x, inter, device_profile=prof)

    def scatter_accumulate(self, y, axis_name, *, device_profile=None):
        inter, intra = self.split_axes(axis_name)
        if inter is None:
            return odc.collective_scatter(y, intra)
        prof = self._node_profile(device_profile, inter, intra)
        y = odc.ring_scatter_accumulate(y, inter, device_profile=prof)
        return odc.collective_scatter(y, intra)

    def layer_comm_time(self, comm_model, devices):
        self._sim_record_layer(comm_model, devices)
        cm, d = comm_model, devices
        g = min(cm.devices_per_node, d)
        if d <= g:  # single node: identical to the others' intra path
            return cm.layer_comm_time(d, False)
        n = d // g  # nodes on the inter ring
        k = cm.layer_param_bytes
        # intra all-gather reconstructs only this node's 1/n chunk; the
        # inter ring then moves the other chunks at full RDMA bandwidth
        # (one aggregated node-level stream per hop — no p2p efficiency
        # penalty, unlike flat ODC's interleaved cross-node hops)
        intra = (g - 1) / g * (k / n)
        inter = (n - 1) / n * k
        return cm.latency + intra / cm.intra_bw + inter / cm.inter_bw


class PipeBackend(HierBackend):
    """Pipeline-parallel ODC over a 2D ``(pipe, data)`` mesh.

    Transport is hier's two-tier path with the roles recast: the trailing
    ``data`` axis is the intra-stage tier (fused collective over the
    devices that share a stage), and the leading ``pipe`` axis is the p2p
    tier — every cross-stage move is a direct ring send between stage
    peers, never a collective, which is what lets stages progress on the
    1F1B schedule without a global barrier.  With ``compress=False`` the
    bytes moved are bit-exact with ``hier`` on the same mesh (the fp32
    fallback contract); ``pipe-int8`` quantizes the cross-stage payload to
    chunked int8 (``odc.ring_gather_q8`` / ``ring_scatter_accumulate_q8``,
    with Pallas remote-DMA realizations in ``repro.kernels.quant``).

    Scheduling: ``schedule='1f1b'`` is implied — the executable gradient
    loop issues microbatch forwards/backwards in the
    ``instructions_1f1b`` order (warmup/steady/drain), and the sim's
    ``PipelineStagePolicy`` places per-stage lanes from the same stream,
    so executable and simulated schedules share their shape by
    construction.

    Simulator cost hooks: ``layer_comm_time`` models ONE stage-boundary
    microbatch message (an activation- or gradient-sized p2p send of
    ``act_fraction`` of a layer's shard-set bytes), not a full shard-set
    move; ``weight_push_time`` keeps the full two-tier shard-set cost
    (pushes move parameters, not activations), with the int8 wire
    shrinking only the cross-stage term.
    """

    name = "pipe"
    policy = PIPE_1F1B
    implied_schedule = "1f1b"
    has_kernels = True
    #: compress the cross-stage (inter-tier) wire payload to chunked int8
    compress = False
    #: modeled bytes of one stage-boundary activation/grad microbatch
    #: message, as a fraction of one layer's parameter shard set
    #: (``CommModel.layer_param_bytes``) — a modeling knob, not measured
    act_fraction = 0.25
    #: chunked-int8 wire bytes per fp32 value: 1 value byte + one f32
    #: scale per ``odc.INT8_CHUNK`` values, vs 4 bytes uncompressed
    int8_wire_factor = (1.0 + 4.0 / odc.INT8_CHUNK) / 4.0

    def wire_factor(self, tier):
        # only the cross-stage p2p tier rides the compressed wire; the
        # intra-stage collective stays full precision — so pipe-int8's
        # 0.254× wire ratio shows up on tier=inter counters only
        if self.compress and tier == "inter":
            return self.int8_wire_factor
        return 1.0

    def gather(self, x, axis_name, *, device_profile=None):
        inter, intra = self.split_axes(axis_name)
        if inter is None:  # single-tier leaf: native collective
            return odc.collective_gather(x, intra)
        x = odc.collective_gather(x, intra)
        prof = self._node_profile(device_profile, inter, intra)
        if self.compress:
            return odc.ring_gather_q8(x, inter, device_profile=prof)
        return odc.ring_gather(x, inter, device_profile=prof)

    def scatter_accumulate(self, y, axis_name, *, device_profile=None):
        inter, intra = self.split_axes(axis_name)
        if inter is None:
            return odc.collective_scatter(y, intra)
        prof = self._node_profile(device_profile, inter, intra)
        if self.compress:
            y = odc.ring_scatter_accumulate_q8(y, inter, device_profile=prof)
        else:
            y = odc.ring_scatter_accumulate(y, inter, device_profile=prof)
        return odc.collective_scatter(y, intra)

    def kernel_gather(self, x_shard, axis_name, **kw):
        from repro.kernels import ops
        if self.compress:
            return ops.odc_gather_q8(x_shard, axis_name, **kw)
        return ops.odc_gather(x_shard, axis_name, **kw)

    def kernel_scatter_accumulate(self, y, axis_name, **kw):
        from repro.kernels import ops
        if self.compress:
            return ops.odc_scatter_accumulate_q8(y, axis_name, **kw)
        return ops.odc_scatter_accumulate(y, axis_name, **kw)

    def layer_comm_time(self, comm_model, devices):
        # one stage-boundary microbatch message: activations forward /
        # gradients backward, p2p between adjacent stages
        cm = comm_model
        if devices <= 1:
            return 0.0
        # accounting stays on the parameter shard sets the executable
        # transport moves per layer (hier's two-tier volumes, with the
        # int8 wire on the inter tier) — the hook's *time* prices the
        # activation message, but the bytes counters must match what a
        # real pipe run records through param_gather
        self._sim_record_layer(cm, devices)
        vol = cm.layer_param_bytes * self.act_fraction
        if self.compress:
            vol *= self.int8_wire_factor
        return cm.latency + vol / cm.inter_bw

    def weight_push_time(self, comm_model, devices, layers):
        # a push moves full parameter shard sets on hier's two-tier path;
        # only the cross-stage p2p bytes ride the compressed wire
        if layers <= 0:
            return 0.0
        self._sim_record_push(comm_model, devices, layers)
        cm, d = comm_model, devices
        g = min(cm.devices_per_node, d)
        if d <= g:
            return layers * cm.layer_comm_time(d, False)
        n = d // g
        k = cm.layer_param_bytes
        intra = (g - 1) / g * (k / n)
        inter = (n - 1) / n * k
        if self.compress:
            inter *= self.int8_wire_factor
        per = cm.latency + intra / cm.intra_bw + inter / cm.inter_bw
        return layers * per


class PipeInt8Backend(PipeBackend):
    """``pipe`` with the chunked-int8 compressed cross-stage wire."""

    name = "pipe-int8"
    compress = True


class CpRingBackend(ODCBackend):
    """Context-parallel ring attention over a ``(data, cp)`` mesh.

    Parameter transport is flat ODC's, unchanged: parameters stay
    ZeRO-sharded over the *flat* ``(data, cp)`` world (``ring_gather`` /
    ``ring_scatter_accumulate`` linearize multi-axis tuples), so the
    per-layer FSDP wire bytes — and ``layer_comm_time`` — are identical
    to ``odc`` at the same world size.  What cp adds is *inside* the
    layer: the sequence dim of every batch leaf is sharded over ``cp``
    and attention runs ``core.cp.ring_attention`` — each hop moves one
    KV chunk p2p over the cp ring while the online-softmax state stays
    put (bit-identical to monolithic flash attention on the gathered
    sequence; see ``core/cp.py``).

    The simulator charges those hops through :meth:`ring_hop_time` and
    the ``context-ring`` policy: ``L * (cp-1)`` hops per microbatch, a
    term that is literally ``0.0`` at cp=1 — a cp=1 run schedules
    float-exactly like flat ODC (the degeneration contract
    ``benchmarks/cp_sweep.py`` pins).  Token-level chunk balance
    (``lb_token``) is what makes the axis pay: a dominant sequence is
    split over the cp ranks, dividing the straggler device's compute by
    ``cp`` where no minibatch-level plan can.
    """

    name = "cp"
    aliases = ("cp-ring",)
    policy = CONTEXT_RING
    #: modeled bytes of ONE cp ring hop's KV payload as a fraction of a
    #: layer's parameter shard-set bytes, before the 1/cp sequence split:
    #: k+v for the layer's kv heads ≈ an eighth of the layer stack's
    #: weights at GQA ratios — a modeling knob, like pipe.act_fraction
    kv_fraction = 0.125

    def ring_hop_time(self, comm_model, cp: int) -> float:
        """Seconds for one KV-chunk hop on a ``cp``-deep ring: each rank
        forwards its 1/cp sequence slice of the layer's K and V blocks to
        the next rank (intra-node NVSwitch-class links — cp ranks are
        co-located by construction of ``make_cp_mesh``)."""
        cm = comm_model
        if cp <= 1:
            return 0.0
        vol = cm.layer_param_bytes * self.kv_fraction / cp
        # one full KV circulation = cp-1 hops of one chunk each — the
        # same (cp-1)-message flat volume the executable ring records
        # per _gather_seq call (op=ring_hop, tier=flat)
        self.record_comm("ring_hop", vol, world=cp)
        return cm.latency + vol / cm.intra_bw

    def ring_policy(self, comm_model, cp: int) -> ContextRingPolicy:
        """The scheduling policy for a ``cp``-deep run of this backend."""
        if cp <= 1:
            return CONTEXT_RING  # hop term 0.0 — float-exact flat ODC
        return ContextRingPolicy(cp, self.ring_hop_time(comm_model, cp))

    def record_ring_hop(self, x, axis_name: AxisNames):
        """Executable-side twin of :meth:`ring_hop_time`'s accounting —
        called by ``core.cp`` once per KV-block ring circulation, with
        ``x`` the local sequence chunk each hop forwards."""
        if obs_metrics.active() is None:
            return
        try:
            cp = odc.axis_size(axis_name)
        except Exception:
            return
        if cp <= 1:
            return
        self.record_comm("ring_hop", float(x.size) * x.dtype.itemsize,
                         world=cp, per_step=True)


COLLECTIVE = register_backend(CollectiveBackend())
ODC = register_backend(ODCBackend())
ODC_OVERLAP = register_backend(OverlapODCBackend())
HIER = register_backend(HierBackend())
PIPE = register_backend(PipeBackend())
PIPE_INT8 = register_backend(PipeInt8Backend())
CP = register_backend(CpRingBackend())


# ===========================================================================
# shared schedule-driven gradient loop (flat + GSPMD engines)
# ===========================================================================
def build_schedule_grad(schedule: str, *, loss_sum: Callable,
                        gather_all: Optional[Callable] = None,
                        pxform: Optional[Callable] = None,
                        prefetch: Optional[Callable] = None,
                        checkpoint_minibatch: bool = False,
                        pipe_stages: int = 1,
                        pipe_interleave: bool = False,
                        zero_counts=None):
    """The gradient loop for one device's microbatches under a schedule.

    Shared by the flat (``core/train_step.py``) and GSPMD
    (``core/gspmd.py``) engines — the loop structure is the paper's
    contribution and must not fork between them.

      loss_sum(params, mb, pxform, prefetch) -> (nll_sum, counts)
                counts: the token count, or a pytree of counts whose
                zeros are ``zero_counts`` (default: a float32 scalar)
      gather_all(params_local) -> fully-materialized params
                                  (schedule='minibatch'/'1f1b')
      pxform    per-layer materialization hook ('layer'/'overlap')
      prefetch  one-slot-ahead materialization hook ('overlap' only)
      checkpoint_minibatch  schedule='1f1b' only: remat each microbatch's
                forward (GSPMD engine), whose residuals would otherwise wait
                in flight for its backward; the other schedules run each
                microbatch's forward and backward back to back
      pipe_stages / pipe_interleave  schedule='1f1b' only: the pipeline
                depth whose stage-0 ``instructions_1f1b`` order the
                microbatch forwards/backwards are issued in, and the
                interleaved (halved-warmup) variant flag

    Returns grad_core(params_local, microbatches) -> (lsum, counts,
    grads), counts summed over the microbatches, to be wrapped in shard_map
    and normalized by the caller.
    """
    if zero_counts is None:
        zero_counts = jnp.float32(0.0)
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; one of {SCHEDULES}")

    if schedule == "1f1b":
        if gather_all is None:
            raise ValueError("schedule='1f1b' needs a gather_all hook")
        if pipe_stages <= 0:
            raise ValueError(
                f"schedule='1f1b' needs pipe_stages >= 1, got {pipe_stages}")

        def grad_core(params_local, microbatches):
            # ODC placement under the pipeline issue order: parameters are
            # gathered ONCE (through jax.vjp, so the matching gradient
            # scatter-accumulate is emitted once per parameter when the
            # accumulated cotangent is pulled back at the end — the
            # minibatch-schedule comm volume), while the microbatch
            # forwards/backwards are issued in the stage-0 1F1B order:
            # warmup forwards build the in-flight residual window (bounded
            # at warmup+1 microbatches, the whole point of 1F1B vs
            # all-forwards-then-all-backwards), steady state alternates
            # F/B, the drain flushes it.
            full, gather_vjp = jax.vjp(gather_all, params_local)
            M = jax.tree_util.tree_leaves(microbatches)[0].shape[0]

            def fwd_one(fp, mb):
                return loss_sum(fp, mb, None, None)

            f = jax.checkpoint(fwd_one) if checkpoint_minibatch else fwd_one

            order = instructions_1f1b(M, pipe_stages,
                                      interleave=pipe_interleave)
            lsum = jnp.float32(0.0)
            tok = zero_counts
            grad_full = None
            pending = {}
            for op, j in order:
                if op == "F":
                    mb = jax.tree.map(lambda x: x[j], microbatches)
                    l, vjp_fn, t = jax.vjp(
                        lambda fp: f(fp, mb), full, has_aux=True)
                    lsum = lsum + l
                    tok = jax.tree.map(jnp.add, tok, t)
                    pending[j] = (vjp_fn, l)
                else:
                    vjp_fn, l = pending.pop(j)
                    (ct,) = vjp_fn(jnp.ones_like(l))
                    grad_full = ct if grad_full is None else \
                        jax.tree.map(jnp.add, grad_full, ct)
            assert not pending, "1F1B order left unpaired forwards"
            if grad_full is None:  # M == 0: no microbatches, zero grads
                grad_full = jax.tree.map(jnp.zeros_like, full)
            (grads,) = gather_vjp(grad_full)
            return lsum, tok, grads

        return grad_core

    if schedule == "minibatch":
        if gather_all is None:
            raise ValueError("schedule='minibatch' needs a gather_all hook")

        def grad_core(params_local, microbatches):
            # ODC placement: gather each parameter once per minibatch
            # (through jax.vjp); gradients accumulate LOCALLY across
            # microbatches (no collective in the loop), and pulling the sum
            # back through the gather emits exactly one scatter-accumulate
            # per parameter at the minibatch end (paper Fig. 2).  Each
            # microbatch's forward runs once, right before its backward.
            full, gather_vjp = jax.vjp(gather_all, params_local)
            lsum, tok, grad_full = _accumulate_grads(
                lambda fp, mb: loss_sum(fp, mb, None, None),
                full, microbatches, zero_counts)
            (grads,) = gather_vjp(grad_full)
            return lsum, tok, grads

        return grad_core

    # FSDP placement ('layer'): per-layer gather in fwd + per-layer
    # scatter-accumulate in bwd, once per microbatch (paper Fig. 1).
    # 'overlap' keeps that structure but software-pipelines it: the
    # prefetch hook materializes layer l+1 inside iteration l (and AD then
    # defers layer l+1's scatter into layer l's backward) — same ops,
    # overlap-friendly issue order.
    pf = prefetch if schedule == "overlap" else None

    def grad_core(params_local, microbatches):
        return _accumulate_grads(
            lambda pl, mb: loss_sum(pl, mb, pxform, pf),
            params_local, microbatches, zero_counts)

    return grad_core


def _accumulate_grads(loss_fn: Callable, params, microbatches, zero_counts):
    """Scan the microbatches: each one's loss and gradient in one
    value_and_grad, the gradients summed in a carry.

      loss_fn(params, mb) -> (nll_sum, counts), counts shaped as
      ``zero_counts``

    Returns (lsum, counts, grads); zeros for M == 0.
    """
    gfun = jax.value_and_grad(loss_fn, has_aux=True)

    def body(carry, mb):
        lsum, tok, gacc = carry
        (l, t), g = gfun(params, mb)
        gacc = jax.tree.map(jnp.add, gacc, g)
        return (lsum + l, jax.tree.map(jnp.add, tok, t), gacc), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (lsum, tok, grads), _ = jax.lax.scan(
        body, (jnp.float32(0.0), zero_counts, zeros), microbatches)
    return lsum, tok, grads
