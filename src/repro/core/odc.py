"""On-Demand Communication primitives (paper §3), pure-JAX level.

The raw gather / scatter-accumulate primitives for FSDP, usable inside
``shard_map``.  They are packaged into first-class backends by the
``repro.core.backend`` registry ('collective' | 'odc' | 'odc-overlap' |
'hier'); the two base flavors are:

* ``comm='collective'`` — the FSDP baseline: one fused ``all_gather`` /
  ``psum_scatter`` per parameter (XLA lowers these to ring/hierarchical
  collectives — the synchronization-barrier pattern of paper Fig. 1).

* ``comm='odc'`` — the ODC pattern: the all-gather is decomposed into a
  chain of point-to-point transfers (``lax.ppermute`` — XLA
  ``collective-permute``, the TPU p2p primitive), and the reduce-scatter
  into a chain of p2p *scatter-accumulate* steps (paper Fig. 5).  Total
  volume is identical (paper Table 2); the topology is p2p.

Both are wrapped in ``custom_vjp`` so that differentiating through a
parameter *gather* automatically emits the matching gradient
*scatter-accumulate* — FSDP falls out of AD.

``prefetch_scan`` builds the overlapped schedule on top: a
double-buffered layer scan that issues layer l+1's gather during layer
l's compute (and, through the same custom VJP, layer l+1's scatter during
layer l's backward) — ``schedule='overlap'`` in the GSPMD engine.

The Pallas remote-DMA kernels in ``repro.kernels.odc_gather`` /
``odc_scatter`` are the NVSHMEM-equivalent one-sided realization of the same
primitives; these jnp versions are their lowering-friendly equivalents and
the numerical oracles.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.balance.cost import DeviceProfile
from repro.obs import metrics as obs_metrics

AxisNames = Union[str, Sequence[str]]


def _axis_tuple(axis_name: AxisNames):
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def axis_size(axis_name: AxisNames):
    ax = _axis_tuple(axis_name)
    n = 1
    for a in ax:
        n *= jax.lax.axis_size(a)
    return n


def axis_index(axis_name: AxisNames):
    """Linearized index over (possibly multiple) mesh axes."""
    ax = _axis_tuple(axis_name)
    idx = jax.lax.axis_index(ax[0])
    for a in ax[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _ring_perm(n: int, order: Optional[Sequence[int]] = None):
    """Ring permutation pairs; ``order`` walks the ring through the devices
    in that sequence (default: natural order).  Any order is
    semantics-preserving — ``ring_gather``/``ring_scatter_accumulate`` index
    shards through the same order — but a ``DeviceProfile``-derived order
    keeps a straggler's slow hops on one ring segment."""
    if order is None:
        return [(j, (j + 1) % n) for j in range(n)]
    assert sorted(order) == list(range(n)), order
    return [(order[j], order[(j + 1) % n]) for j in range(n)]


def _ppermute_next(x, axis_name: AxisNames,
                   order: Optional[Sequence[int]] = None):
    """Send to the next device on the linearized ring — a single p2p hop."""
    ax = _axis_tuple(axis_name)
    if len(ax) == 1:
        return jax.lax.ppermute(x, ax[0],
                                _ring_perm(jax.lax.axis_size(ax[0]), order))
    # multi-axis linearized ring: permute within the minor axis; the wrap
    # element moves one step along the major axis. Implemented as a minor-axis
    # ring followed by a conditional major-axis shift of the wrap position.
    # For simplicity and identical semantics we use the flat ppermute over the
    # combined axes, which JAX supports by passing the axis tuple.
    sizes = [jax.lax.axis_size(a) for a in ax]
    n = 1
    for s in sizes:
        n *= s
    return jax.lax.ppermute(x, ax, _ring_perm(n, order))


def _ring_order(axis_name: AxisNames,
                device_profile: Optional[DeviceProfile]):
    """Resolve the profile to a concrete ring order for this axis, or None
    (natural ring) when no profile applies or its size doesn't match."""
    if device_profile is None:
        return None
    n = axis_size(axis_name)
    if device_profile.world_size != n:
        return None
    order = device_profile.ring_order()
    if order == list(range(n)):
        return None  # natural ring — keep the canonical perm
    return order


def _ring_pos(order: Optional[Sequence[int]], me, n: int):
    """(my ring position, position→device lookup) for a possibly traced
    device index ``me``."""
    if order is None:
        return me, None
    inv = [0] * n
    for pos, d in enumerate(order):
        inv[d] = pos
    pos = jnp.asarray(inv, jnp.int32)[me]
    return pos, jnp.asarray(order, jnp.int32)


# ===========================================================================
# ODC p2p primitives (ring decomposition of the collectives)
# ===========================================================================
def ring_gather(x, axis_name: AxisNames,
                device_profile: Optional[DeviceProfile] = None):
    """ODC *gather*: reconstruct the full tensor from per-device shards with
    a chain of point-to-point transfers (no fused collective).

    x: local shard, shape (c, ...). Returns (n*c, ...), identical on every
    device along ``axis_name``.

    device_profile: optional heterogeneity model; the chain then walks the
    profile's ring order (stragglers adjacent) instead of the natural
    device order.  The reconstructed tensor is identical either way — only
    which peer each hop talks to changes.
    """
    n = axis_size(axis_name)
    me = axis_index(axis_name)
    c = x.shape[0]
    order = _ring_order(axis_name, device_profile)
    pos, pos2dev = _ring_pos(order, me, n)

    buf = jnp.zeros((n * c,) + x.shape[1:], x.dtype)
    buf = jax.lax.dynamic_update_slice_in_dim(buf, x, me * c, 0)

    def body(i, carry):
        buf, cur = carry
        cur = _ppermute_next(cur, axis_name, order)
        # the shard that just arrived: i+1 ring positions behind me
        if order is None:
            src = (me - i - 1) % n
        else:
            src = pos2dev[(pos - i - 1) % n]
        buf = jax.lax.dynamic_update_slice_in_dim(buf, cur, src * c, 0)
        return buf, cur

    buf, _ = jax.lax.fori_loop(0, n - 1, body, (buf, x))
    return buf


def ring_scatter_accumulate(y, axis_name: AxisNames,
                            device_profile: Optional[DeviceProfile] = None):
    """ODC *scatter-accumulate*: each device pushes its contribution for
    every shard to the shard owner, who accumulates (p2p reduce-scatter).

    y: full-size local contribution, shape (n*c, ...). Returns the owner's
    accumulated shard, shape (c, ...).  ``device_profile``: see
    ``ring_gather`` — owner semantics are unchanged, only the hop order.
    """
    n = axis_size(axis_name)
    me = axis_index(axis_name)
    c = y.shape[0] // n
    order = _ring_order(axis_name, device_profile)
    pos, pos2dev = _ring_pos(order, me, n)

    def blk(j):
        return jax.lax.dynamic_slice_in_dim(y, j * c, c, 0)

    def chunk_at(ring_offset):
        """Chunk owned by the device ``ring_offset`` positions behind me."""
        if order is None:
            return (me - ring_offset) % n
        return pos2dev[(pos - ring_offset) % n]

    # ring reduce-scatter: start with the partial for my ring predecessor's
    # chunk, push it around the ring; after n-1 hops every device holds the
    # full sum of its own chunk.
    acc = blk(chunk_at(1))

    def body(h, acc):
        acc = _ppermute_next(acc, axis_name, order)
        acc = acc + blk(chunk_at(1 + h))
        return acc

    return jax.lax.fori_loop(1, n, body, acc)


# ===========================================================================
# chunked int8 wire format + compressed (q8) ring primitives
# ===========================================================================
#: values per scale chunk — the wire format of the q8 kernels and the sim's
#: byte model (1 int8 byte per value + one f32 scale per INT8_CHUNK values)
INT8_CHUNK = 256


def quantize_chunked(x, chunk: int = INT8_CHUNK):
    """Symmetric per-chunk int8 quantization (the compressed wire format).

    The tensor is flattened, zero-padded to a multiple of ``chunk``, and
    each chunk is scaled by ``absmax / 127`` (1.0 for an all-zero chunk, so
    zeros round-trip exactly).  Returns ``(q, scales)`` with ``q`` int8 of
    shape ``(n_chunks, chunk)`` and ``scales`` f32 of shape
    ``(n_chunks, 1)``.

    Error bound (round-to-nearest): per element
    ``|x - dequant(q)| <= scale / 2 = absmax(chunk) / 254`` — documented
    and asserted by the quantization-error bound test.
    """
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, chunk)
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scales = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(blocks / scales), -127, 127).astype(jnp.int8)
    return q, scales.astype(jnp.float32)


def dequantize_chunked(q, scales, shape, dtype=jnp.float32):
    """Invert :func:`quantize_chunked`: ``(n_chunks, chunk)`` int8 values +
    per-chunk scales back to a tensor of ``shape`` (padding dropped)."""
    flat = (q.astype(jnp.float32) * scales).reshape(-1)
    size = 1
    for s in shape:
        size *= s
    return flat[:size].reshape(shape).astype(dtype)


def ring_gather_q8(x, axis_name: AxisNames,
                   device_profile: Optional[DeviceProfile] = None,
                   chunk: int = INT8_CHUNK):
    """Compressed ODC gather: the ring payload is each *origin* shard's
    chunked-int8 encoding (values + per-chunk scales), quantized ONCE at
    its source and relayed verbatim hop to hop — so the error does not
    compound with ring distance.  Every received shard is dequantized into
    the output; the local shard lands exactly (no quantization).

    Per-element error vs :func:`ring_gather`:
    ``<= absmax(chunk) / 254`` (see :func:`quantize_chunked`); wire bytes
    per hop shrink from ``4`` per value to ``1 + 4/chunk``.
    """
    n = axis_size(axis_name)
    me = axis_index(axis_name)
    c = x.shape[0]
    order = _ring_order(axis_name, device_profile)
    pos, pos2dev = _ring_pos(order, me, n)

    buf = jnp.zeros((n * c,) + x.shape[1:], x.dtype)
    buf = jax.lax.dynamic_update_slice_in_dim(buf, x, me * c, 0)
    q, scales = quantize_chunked(x, chunk)

    def body(i, carry):
        buf, q, scales = carry
        q = _ppermute_next(q, axis_name, order)
        scales = _ppermute_next(scales, axis_name, order)
        if order is None:
            src = (me - i - 1) % n
        else:
            src = pos2dev[(pos - i - 1) % n]
        shard = dequantize_chunked(q, scales, x.shape, x.dtype)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, shard, src * c, 0)
        return buf, q, scales

    buf, _, _ = jax.lax.fori_loop(0, n - 1, body, (buf, q, scales))
    return buf


def ring_scatter_accumulate_q8(y, axis_name: AxisNames,
                               device_profile: Optional[DeviceProfile] = None,
                               chunk: int = INT8_CHUNK):
    """Compressed ODC scatter-accumulate: partial sums accumulate in the
    input dtype, but each hop's *wire* payload is the chunked-int8 encoding
    of the outgoing partial sum (a reduce-scatter must send partial sums,
    so — unlike the gather — each of the ``n-1`` hops requantizes; the
    per-hop error is ``<= scale/2`` and compounds at most ``n-1`` times
    into the owner's final chunk)."""
    n = axis_size(axis_name)
    me = axis_index(axis_name)
    c = y.shape[0] // n
    order = _ring_order(axis_name, device_profile)
    pos, pos2dev = _ring_pos(order, me, n)

    def blk(j):
        return jax.lax.dynamic_slice_in_dim(y, j * c, c, 0)

    def chunk_at(ring_offset):
        if order is None:
            return (me - ring_offset) % n
        return pos2dev[(pos - ring_offset) % n]

    acc = blk(chunk_at(1))
    shape, dtype = acc.shape, acc.dtype

    def body(h, acc):
        q, scales = quantize_chunked(acc, chunk)
        q = _ppermute_next(q, axis_name, order)
        scales = _ppermute_next(scales, axis_name, order)
        arrived = dequantize_chunked(q, scales, shape, dtype)
        return arrived + blk(chunk_at(1 + h))

    return jax.lax.fori_loop(1, n, body, acc)


# ===========================================================================
# collective baselines
# ===========================================================================
def collective_gather(x, axis_name: AxisNames):
    return jax.lax.all_gather(x, _axis_tuple(axis_name), tiled=True)


def collective_scatter(y, axis_name: AxisNames):
    return jax.lax.psum_scatter(y, _axis_tuple(axis_name), tiled=True)


# ===========================================================================
# differentiable gather: fwd = param gather, bwd = grad scatter-accumulate
# ===========================================================================
def make_param_gather(axis_name: AxisNames, comm="collective",
                      dim: int = 0,
                      device_profile: Optional[DeviceProfile] = None):
    """Returns gather(x_shard) -> x_full along ``dim`` with a custom VJP
    whose backward pass is the matching gradient scatter-accumulate on the
    same backend (paper §3: differentiating a parameter *gather* emits the
    gradient *scatter-accumulate*).

    ``comm`` is a backend name resolved through the
    ``repro.core.backend`` registry ('collective' | 'odc' | 'odc-overlap'
    | 'hier', plus legacy aliases) or an already-resolved ``CommBackend``.

    device_profile: with a p2p backend, the chains walk the profile's
    ring order (stragglers adjacent) — values are unchanged."""
    from repro.core import backend as B  # odc is imported by backend
    return B.get_backend(comm).param_gather(
        axis_name, dim=dim, device_profile=device_profile)


def make_scatter_accumulate(axis_name: AxisNames, comm="collective",
                            device_profile: Optional[DeviceProfile] = None):
    """Registry-resolved gradient scatter-accumulate for ``axis_name``."""
    from repro.core import backend as B
    return functools.partial(B.get_backend(comm).scatter_accumulate,
                             axis_name=axis_name,
                             device_profile=device_profile)


# ===========================================================================
# overlapped schedule: software-pipelined (double-buffered) layer scan
# ===========================================================================
def prefetch_scan(body, init, params_xs, rest_xs, *, prefetch,
                  remat: bool = False):
    """Layer scan with one-slot-ahead parameter prefetch (schedule='overlap').

    Runs ``body(carry, (layer_params, *rest_slice))`` over the leading
    (stacked-layer) axis of ``params_xs``, where ``layer_params`` was
    materialized by ``prefetch`` (the FSDP gather transform) one iteration
    EARLY: iteration ``l`` issues the gather chain for layer ``l+1``'s
    shards *before* running layer ``l``'s compute, then hands the result to
    iteration ``l+1`` through the scan carry.  Inside the compiled loop
    body the layer-``l+1`` gather has no data dependence on the layer-``l``
    matmuls, so the scheduler is free to run the p2p chain underneath them
    — the prefetch/overlap discipline of PyTorch-FSDP forward prefetch and
    Zeppelin, expressed in issue order (repro.sim charges the timing).

    The backward pass falls out of AD with exactly the mirrored
    discipline: the scatter-accumulate for layer ``l+1``'s gradients (the
    custom-VJP transpose of its gather, issued in forward iteration ``l``)
    is emitted in *backward* iteration ``l`` — i.e. during layer ``l``'s
    backward compute — so gradient communication is prefetched too.

    Costs vs the plain per-layer scan: one redundant gather per scan (the
    last iteration prefetches layer 0 again; its result is dead and the
    cotangent through it is zero), plus the gathered carry is a scan
    residual under ``remat`` — i.e. with rematerialization the gathered
    layers are saved rather than re-gathered, matching the memory
    footprint of ``schedule='minibatch'`` (which materializes everything
    up front) rather than ``schedule='layer'``.

    ``rest_xs`` is a tuple of extra scanned inputs (windows, caches, ...)
    that ride along un-prefetched.
    """
    first = prefetch(jax.tree.map(lambda a: a[0], params_xs))
    # xs[l] -> shard slice of layer l+1 (mod L): the slice whose gather is
    # issued during layer l's compute.
    ahead = jax.tree.map(lambda a: jnp.roll(a, -1, axis=0), params_xs)
    L = jax.tree_util.tree_leaves(params_xs)[0].shape[0]

    def wrapped(c, scanned):
        carry, cur = c
        nxt_shard, rest = scanned
        # the scan body traces ONCE but runs L times per step — scale the
        # trace-time comm accounting so the ledger stays exact
        with obs_metrics.trace_scale(L):
            nxt = prefetch(nxt_shard)  # issue layer l+1's gather FIRST
        carry, y = body(carry, (cur,) + tuple(rest))
        return (carry, nxt), y

    if remat:
        wrapped = jax.checkpoint(wrapped)
    (carry, _), ys = jax.lax.scan(wrapped, (init, first),
                                  (ahead, tuple(rest_xs)))
    return carry, ys
