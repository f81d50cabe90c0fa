"""ODC *gather* as a one-sided remote-DMA ring kernel (TPU).

The paper's `gather` pulls parameter shards from peers over RDMA
(NVSHMEM ``get_mem``).  The TPU-native equivalent is the put+signal model:
each device forwards shards around the ring with
``pltpu.make_async_remote_copy`` — one-sided writes into the neighbor's
buffer, synchronized only by DMA semaphores between the two endpoints.
There is NO fused collective and NO global barrier: every hop is a
pairwise producer/consumer handoff, which is exactly the non-intrusive
property §3.2 needs (the peer's compute core is never interrupted; the
DMA engines move the bytes).

Layout: shards and the gathered output live in HBM (``pl.ANY``) and every
hop is an HBM-to-HBM DMA straight into the neighbor's output slot for that
shard.  Nothing is staged in VMEM, so a shard of any size fits, and since
each output slot is written exactly once no slot is ever reused — the ring
needs no credits.  A sender never waits for its receiver, so a fast left
neighbor may have several pushes in flight into me; each output slot
therefore has its own receive semaphore, and a shard is forwarded only
after the wait on ITS semaphore (a shared one could be satisfied by a later
hop that landed first).

Two entry points:

  odc_gather_pallas         one layer's shard set -> full layer
  odc_gather_layers_pallas  a stacked (L, c, ...) shard set -> (L, n, c, ...)
                            with the rings of consecutive layers chained
                            without a barrier between them, so layer l+1's
                            first hop can be in flight while layer l's last
                            shards are still arriving elsewhere on the ring
                            — the cross-layer prefetch that backs
                            ``schedule='overlap'``.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import remote_interpret


def _forward(out_at, recv_at, src, nxt, right, send_sem):
    """One hop: push shard ``src`` from my output into the right neighbor's
    output slot for it, then wait until shard ``nxt`` (my left neighbor's
    push) has landed in mine."""
    rdma = pltpu.make_async_remote_copy(
        src_ref=out_at(src), dst_ref=out_at(src),
        send_sem=send_sem, recv_sem=recv_at(src),
        device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH)
    rdma.start()
    rdma.wait_send()
    # pairwise sync with the two ring neighbors only
    pltpu.make_async_copy(out_at(nxt), out_at(nxt), recv_at(nxt)).wait()


def _gather_kernel(x_ref, out_ref, send_sem, recv_sem, *, num, axis_name):
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, num)
    pltpu.sync_copy(x_ref, out_ref.at[me])  # my own shard, HBM -> HBM

    def hop(i, _):
        # hop 0 forwards my shard; hop i forwards what arrived on hop i-1
        src = jax.lax.rem(me - i + num, num)
        nxt = jax.lax.rem(me - i - 1 + num, num)
        _forward(lambda s: out_ref.at[s], lambda s: recv_sem.at[s], src, nxt,
                 right, send_sem)
        return 0

    jax.lax.fori_loop(0, num - 1, hop, 0)


def odc_gather_pallas(x, *, axis_name: str, interpret=None):
    """x: local shard (c, ...) inside shard_map -> (n, c, ...) stacked
    shards (caller reshapes to the tiled gather layout)."""
    n = jax.lax.axis_size(axis_name)
    kernel = functools.partial(_gather_kernel, num=n, axis_name=axis_name)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n,) + x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA((n,))],
        interpret=remote_interpret(interpret),
    )(x)


def _gather_layers_kernel(x_ref, out_ref, send_sem, recv_sem, *, num,
                          layers, axis_name):
    """Chained rings over a stacked (L, c, ...) shard set: layer l+1 starts
    as soon as this device has finished layer l's hops.  The left neighbor
    may run layers ahead, so receive semaphores are per (layer, slot)."""
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, num)

    def layer(l, _):
        pltpu.sync_copy(x_ref.at[l], out_ref.at[l, me])

        def hop(i, _):
            src = jax.lax.rem(me - i + num, num)
            nxt = jax.lax.rem(me - i - 1 + num, num)
            _forward(lambda s: out_ref.at[l, s], lambda s: recv_sem.at[l, s],
                     src, nxt, right, send_sem)
            return 0

        jax.lax.fori_loop(0, num - 1, hop, 0)
        return 0

    jax.lax.fori_loop(0, layers, layer, 0)


def odc_gather_layers_pallas(x, *, axis_name: str, interpret=None):
    """x: stacked local shards (L, c, ...) inside shard_map ->
    (L, n, c, ...): every layer's full shard set, gathered by L chained
    rings (no per-layer barrier)."""
    n = jax.lax.axis_size(axis_name)
    L = x.shape[0]
    kernel = functools.partial(_gather_layers_kernel, num=n, layers=L,
                               axis_name=axis_name)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((L, n) + x.shape[1:], x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA((L, n))],
        interpret=remote_interpret(interpret),
    )(x)
