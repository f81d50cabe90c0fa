"""ODC *scatter-accumulate* as a one-sided remote-DMA ring kernel (TPU).

The paper's workers push gradient contributions to shard owners who
accumulate on receipt (a polling daemon on GPU).  On TPU the push is a
remote DMA into the receiver's receive slot and the "daemon" is simply the
owner's own accumulate after the pairwise semaphore fires — no host
involvement, no global barrier.  After n-1 hops every device holds the
fully-accumulated sum for the chunk it owns.

Layout: contributions, the running partial sum (kept in the output) and the
two receive slots all live in HBM; each hop is an HBM-to-HBM remote DMA, and
the accumulate streams row blocks of at most ``BLOCK_BYTES`` through VMEM,
so a chunk of any size fits.  The two receive slots are reused every other
hop: a sender holds until the receiver has consumed the slot it is about to
overwrite (a credit signaled back after the receiver's accumulate).

``odc_scatter_accumulate_layers_pallas`` extends the two receive slots
across a stacked (L, n, c, ...) input: the ring chains of consecutive
layers share them through one global hop counter, so layer l's pushes start
while layer l+1's are still draining — the backward-side twin of the
cross-layer gather prefetch (``schedule='overlap'`` issues layer l's
scatter during layer l-1's backward).
"""
from __future__ import annotations

import functools
import math

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import remote_interpret

# VMEM bytes per operand of one accumulate block (two operands are live)
BLOCK_BYTES = 2 << 20


def block_rows(chunk_shape, itemsize: int, align: int = 8) -> int:
    """Rows of the leading dim per VMEM block: the largest divisor of it
    whose block fits ``BLOCK_BYTES``, a multiple of ``align`` (the sublane
    tile) where one exists."""
    c = chunk_shape[0]
    row = itemsize * math.prod(chunk_shape[1:])
    fits = [d for d in range(1, c + 1)
            if c % d == 0 and d * row <= BLOCK_BYTES] or [1]
    return max([d for d in fits if d % align == 0] or fits)


def accumulate(x_src, stage_src, acc_dst, a_buf, b_buf):
    """acc_dst = x_src + stage_src over HBM refs, one VMEM row block at a
    time (``a_buf``/``b_buf`` are the block buffers)."""
    rows = a_buf.shape[0]

    def blk(r, _):
        rs = pl.ds(pl.multiple_of(r * rows, rows), rows)
        pltpu.sync_copy(x_src.at[rs], a_buf)
        pltpu.sync_copy(stage_src.at[rs], b_buf)
        a_buf[...] = a_buf[...] + b_buf[...]
        pltpu.sync_copy(a_buf, acc_dst.at[rs])
        return 0

    jax.lax.fori_loop(0, acc_dst.shape[0] // rows, blk, 0)


def _ring_hop(t, acc_ref, stage_ref, send_sem, recv_sem, credit_sem, *,
              right):
    """Push my partial sum into slot t%2 of the right neighbor and wait for
    my left neighbor's push into mine.  Two slots give two hops of slack;
    from the third push on a sender waits for a credit."""
    slot = jax.lax.rem(t, 2)

    @pl.when(t >= 2)
    def _backpressure():
        pltpu.semaphore_wait(credit_sem, 1)

    rdma = pltpu.make_async_remote_copy(
        src_ref=acc_ref, dst_ref=stage_ref.at[slot],
        send_sem=send_sem, recv_sem=recv_sem.at[slot],
        device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH)
    rdma.start()
    rdma.wait()
    return slot


def _credit(t, credit_sem, *, left, hops_total):
    @pl.when(t <= hops_total - 3)
    def _signal():  # stage[t%2] consumed — left may overwrite it
        pltpu.semaphore_signal(credit_sem, 1, device_id=(left,),
                               device_id_type=pltpu.DeviceIdType.MESH)


def _scatter_layers_kernel(x_ref, out_ref, stage_ref, a_buf, b_buf, send_sem,
                           recv_sem, credit_sem, *, num, layers, axis_name):
    """Chained scatter-accumulate rings over (L, n, c, ...) contributions.

    ``out_ref[l]`` is layer l's running partial sum (its previous send has
    completed by the time it is overwritten — rdma.wait is the
    producer/consumer handoff); the receive slots are indexed by a global
    hop counter t so consecutive layers' pushes interleave through them.
    """
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, num)
    left = jax.lax.rem(me - 1 + num, num)
    hops_total = layers * (num - 1)
    # start with my contribution for the chunk owned by my left neighbor
    first = jax.lax.rem(me - 1 + num, num)

    def layer(l, _):
        acc = out_ref.at[l]
        pltpu.sync_copy(x_ref.at[l, first], acc)

        def hop(h, _):
            t = l * (num - 1) + h - 1  # global hop counter
            slot = _ring_hop(t, acc, stage_ref, send_sem, recv_sem,
                             credit_sem, right=right)
            # owner-side accumulate (the paper's daemon, sans daemon): add
            # my own contribution for the chunk that just arrived
            chunk = jax.lax.rem(me - 1 - h + num, num)
            accumulate(x_ref.at[l, chunk], stage_ref.at[slot], acc, a_buf,
                       b_buf)
            _credit(t, credit_sem, left=left, hops_total=hops_total)
            return 0

        jax.lax.fori_loop(1, num, hop, 0)
        return 0

    jax.lax.fori_loop(0, layers, layer, 0)


def odc_scatter_accumulate_layers_pallas(y, *, axis_name: str,
                                         interpret=None):
    """y: stacked contributions (L, n, c, ...) inside shard_map ->
    (L, c, ...): each layer's owned chunk, accumulated over all devices,
    with the L rings chained through one pair of receive slots."""
    n = jax.lax.axis_size(axis_name)
    assert y.shape[1] == n, (y.shape, n)
    L = y.shape[0]
    chunk_shape = y.shape[2:]
    rows = block_rows(chunk_shape, y.dtype.itemsize)
    kernel = functools.partial(_scatter_layers_kernel, num=n, layers=L,
                               axis_name=axis_name)
    # Mosaic allocates scratch only in VMEM/SMEM, so the HBM receive slots
    # are a second output that the caller drops
    out, _ = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((L,) + chunk_shape, y.dtype),
                   jax.ShapeDtypeStruct((2,) + chunk_shape, y.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.VMEM((rows,) + chunk_shape[1:], y.dtype),
            pltpu.VMEM((rows,) + chunk_shape[1:], y.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        interpret=remote_interpret(interpret),
    )(y)
    return out


def odc_scatter_accumulate_pallas(y, *, axis_name: str, interpret=None):
    """y: full-size local contribution (n, c, ...) inside shard_map ->
    (c, ...): the accumulated sum of chunk ``me`` over all devices (the
    one-layer case of the chained kernel)."""
    return odc_scatter_accumulate_layers_pallas(
        y[None], axis_name=axis_name, interpret=interpret)[0]
