"""Chunked-int8 wire codec + compressed (q8) ring kernels (TPU Pallas).

The ``pipe-int8`` backend moves stage-boundary activations/grads and
posttrain weight pushes over a compressed wire: each 256-value chunk is
encoded as int8 values plus one f32 scale (``absmax / 127``), shrinking
wire bytes per value from 4 to ``1 + 4/256``.  This module carries the
hardware realization:

  quantize / dequantize       whole-block VMEM codec kernels (the wire
                              format of ``repro.core.odc.quantize_chunked``)
  odc_gather_q8_pallas        the ring gather of ``odc_gather.py`` with the
                              payload quantized ONCE at its source and the
                              (values, scales) pair relayed verbatim hop to
                              hop — error does not compound with distance
  odc_scatter_accumulate_q8_pallas
                              the scatter-accumulate ring with each hop's
                              outgoing partial sum requantized (a
                              reduce-scatter must send partials, so error
                              compounds at most n-1 hops)

Same discipline as the fp32 rings (``odc_gather.py``, ``odc_scatter.py``):
HBM refs (``pl.ANY``), HBM-to-HBM one-sided ``make_async_remote_copy`` per
payload stream (values and scales ride separate DMAs sharing one credit),
and VMEM only for bounded row blocks of the codec math.  The jnp q8
primitives in ``repro.core.odc`` are the numerical oracles — same formula,
same hop order, so interpret-mode results are bit-identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode, remote_interpret
from repro.kernels.odc_scatter import block_rows


# ===========================================================================
# codec kernels: (n_chunks, chunk) f32  <->  int8 values + per-chunk scales
# ===========================================================================
def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scales = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scales), -127.0, 127.0
                          ).astype(jnp.int8)
    s_ref[...] = scales


def quantize_pallas(blocks, *, interpret=None):
    """(n_chunks, chunk) f32 -> ((n_chunks, chunk) int8, (n_chunks, 1) f32
    scales); an all-zero chunk gets scale 1.0 so zeros round-trip exactly."""
    nc, chunk = blocks.shape
    return pl.pallas_call(
        _quantize_kernel,
        out_shape=(jax.ShapeDtypeStruct((nc, chunk), jnp.int8),
                   jax.ShapeDtypeStruct((nc, 1), jnp.float32)),
        interpret=interpret_mode(interpret),
    )(blocks.astype(jnp.float32))


def _dequantize_kernel(q_ref, s_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def dequantize_pallas(q, scales, *, interpret=None):
    """((n_chunks, chunk) int8, (n_chunks, 1) f32) -> (n_chunks, chunk) f32."""
    return pl.pallas_call(
        _dequantize_kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret_mode(interpret),
    )(q, scales)


# ===========================================================================
# compressed ring gather: quantize once at source, relay (q, scales) verbatim
# ===========================================================================
def _gather_q8_kernel(q_ref, s_ref, qout_ref, sout_ref, send_sem, qrecv_sem,
                      srecv_sem, *, num, axis_name):
    """Each hop pushes one encoded shard (values + scales) from my output
    straight into the right neighbor's output slot for it; per-slot receive
    semaphores, as in ``odc_gather``."""
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, num)

    # my own encoding lands in my output slot
    pltpu.sync_copy(q_ref, qout_ref.at[me])
    pltpu.sync_copy(s_ref, sout_ref.at[me])

    def hop(i, _):
        src = jax.lax.rem(me - i + num, num)  # who encoded this shard
        nxt = jax.lax.rem(me - i - 1 + num, num)
        for out, recv in ((qout_ref, qrecv_sem), (sout_ref, srecv_sem)):
            rdma = pltpu.make_async_remote_copy(
                src_ref=out.at[src], dst_ref=out.at[src],
                send_sem=send_sem, recv_sem=recv.at[src],
                device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            rdma.wait_send()
        for out, recv in ((qout_ref, qrecv_sem), (sout_ref, srecv_sem)):
            pltpu.make_async_copy(out.at[nxt], out.at[nxt],
                                  recv.at[nxt]).wait()
        return 0

    jax.lax.fori_loop(0, num - 1, hop, 0)


def odc_gather_q8_pallas(q, scales, *, axis_name: str, interpret=None):
    """(q, scales): the local shard's chunked-int8 encoding inside
    shard_map -> ((n, n_chunks, chunk) int8, (n, n_chunks, 1) f32): every
    device's encoding, each quantized once at its origin (the caller
    dequantizes, and may overwrite its own slot with the exact shard)."""
    n = jax.lax.axis_size(axis_name)
    nc, chunk = q.shape
    kernel = functools.partial(_gather_q8_kernel, num=n, axis_name=axis_name)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((n, nc, chunk), jnp.int8),
                   jax.ShapeDtypeStruct((n, nc, 1), jnp.float32)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((n,)),
            pltpu.SemaphoreType.DMA((n,)),
        ],
        interpret=remote_interpret(interpret),
    )(q, scales)


# ===========================================================================
# compressed scatter-accumulate: requantize the partial sum at every hop
# ===========================================================================
def _scatter_q8_kernel(x_ref, out_ref, qsnd_ref, ssnd_ref, qstage_ref,
                       sstage_ref, a_buf, q_buf, s_buf, qsend_sem, ssend_sem,
                       qrecv_sem, srecv_sem, credit_sem, *, num, axis_name):
    """``out_ref`` is the running f32 partial sum; the wire buffers and the
    two receive slots live in HBM, and the codec runs over VMEM row blocks
    of ``a_buf.shape[0]`` chunks."""
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, num)
    left = jax.lax.rem(me - 1 + num, num)
    rows = a_buf.shape[0]
    nblk = out_ref.shape[0] // rows

    def blocks(body):
        def blk(r, _):
            body(pl.ds(pl.multiple_of(r * rows, rows), rows))
            return 0
        jax.lax.fori_loop(0, nblk, blk, 0)

    # start with my contribution for the chunk owned by my left neighbor
    first = jax.lax.rem(me - 1 + num, num)
    pltpu.sync_copy(x_ref.at[first], out_ref)

    def hop(h, _):
        slot = jax.lax.rem(h, 2)

        # the wire payload is the chunked-int8 encoding of the outgoing
        # partial sum (the previous hop's waits freed the send buffers)
        def encode(rs):
            pltpu.sync_copy(out_ref.at[rs], a_buf)
            acc = a_buf[...]
            absmax = jnp.max(jnp.abs(acc), axis=1, keepdims=True)
            scales = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
            q_buf[...] = jnp.clip(jnp.round(acc / scales), -127.0, 127.0
                                  ).astype(jnp.int8)
            s_buf[...] = scales
            pltpu.sync_copy(q_buf, qsnd_ref.at[rs])
            pltpu.sync_copy(s_buf, ssnd_ref.at[rs])

        blocks(encode)

        @pl.when(h >= 3)  # two receive slots = two hops of slack
        def _backpressure():
            pltpu.semaphore_wait(credit_sem, 1)

        rdmas = [pltpu.make_async_remote_copy(
            src_ref=src, dst_ref=stage.at[slot], send_sem=send,
            recv_sem=recv.at[slot], device_id=(right,),
            device_id_type=pltpu.DeviceIdType.MESH)
            for src, stage, send, recv in (
                (qsnd_ref, qstage_ref, qsend_sem, qrecv_sem),
                (ssnd_ref, sstage_ref, ssend_sem, srecv_sem))]
        for rdma in rdmas:
            rdma.start()
        for rdma in rdmas:
            rdma.wait()

        # owner-side accumulate: dequantize the arrived partial and add my
        # own contribution for the chunk that just arrived
        chunk = jax.lax.rem(me - 1 - h + num, num)

        def decode(rs):
            pltpu.sync_copy(x_ref.at[chunk, rs], a_buf)
            pltpu.sync_copy(qstage_ref.at[slot, rs], q_buf)
            pltpu.sync_copy(sstage_ref.at[slot, rs], s_buf)
            a_buf[...] = a_buf[...] + (
                q_buf[...].astype(jnp.float32) * s_buf[...])
            pltpu.sync_copy(a_buf, out_ref.at[rs])

        blocks(decode)

        @pl.when(h <= num - 3)
        def _credit():  # stage[slot] consumed — left may overwrite it
            pltpu.semaphore_signal(credit_sem, 1, device_id=(left,),
                                   device_id_type=pltpu.DeviceIdType.MESH)

        return 0

    jax.lax.fori_loop(1, num, hop, 0)


def odc_scatter_accumulate_q8_pallas(blocks, *, axis_name: str,
                                     interpret=None):
    """blocks: per-destination contributions (n, n_chunks, chunk) f32
    inside shard_map -> (n_chunks, chunk) f32: the accumulated sum of
    chunk ``me`` over all devices, every hop's wire traffic int8."""
    n = jax.lax.axis_size(axis_name)
    assert blocks.shape[0] == n, (blocks.shape, n)
    nc, chunk = blocks.shape[1:]
    # int8 rows tile by 32: keep the codec blocks whole int8 tiles
    rows = block_rows((nc, chunk), 4, align=32)
    kernel = functools.partial(_scatter_q8_kernel, num=n, axis_name=axis_name)
    # Mosaic allocates scratch only in VMEM/SMEM: the HBM wire buffers and
    # receive slots are extra outputs that the caller drops
    out, *_ = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((nc, chunk), jnp.float32),
                   jax.ShapeDtypeStruct((nc, chunk), jnp.int8),
                   jax.ShapeDtypeStruct((nc, 1), jnp.float32),
                   jax.ShapeDtypeStruct((2, nc, chunk), jnp.int8),
                   jax.ShapeDtypeStruct((2, nc, 1), jnp.float32)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY) for _ in range(5)),
        scratch_shapes=[
            pltpu.VMEM((rows, chunk), jnp.float32),
            pltpu.VMEM((rows, chunk), jnp.int8),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        interpret=remote_interpret(interpret),
    )(blocks.astype(jnp.float32))
    return out
