"""ODC gather fused with the consumer matmul (collective matmul).

Computes ``y = x @ W`` where W is row-sharded over the FSDP axis
(W_d: (k/n, f) on device d) WITHOUT ever materializing the full W:
while the MXU multiplies the shard that is already resident, the next
shard travels the ring via one-sided remote DMA.  This is the paper's
§6.1 "overlapping communication with computation" taken to its limit —
the gather never exists as a separate step, so there is nothing to
synchronize on except the pairwise hop semaphores.

  hop i (device me): y += x[:, cols(src_i)] @ shard_i   ∥   DMA shard_i → right

where src_i = (me - i) mod n is the owner of the currently-resident shard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import remote_interpret


def _gather_matmul_kernel(x_ref, w_ref, out_ref, wbuf_ref, acc_ref,
                          send_sem, recv_sem, credit_sem, *, num, axis_name):
    me = jax.lax.axis_index(axis_name)
    mesh_id = pltpu.DeviceIdType.MESH
    right = (jax.lax.rem(me + 1, num),)
    left = (jax.lax.rem(me - 1 + num, num),)
    c = w_ref.shape[0]  # rows per shard

    pltpu.sync_copy(w_ref, wbuf_ref.at[0])
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # Credit-based flow control: the two staging slots give two hops of
    # slack; from hop 2 on, a send may only start once the right neighbor
    # has *consumed* the slot it is about to overwrite (it signals a credit
    # back after its own wait).  Without this, a fast producer overruns a
    # slow consumer's buffer — one-sided comm needs explicit back-pressure.
    def hop(i, _):
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i >= 2)
        def _backpressure():
            pltpu.semaphore_wait(credit_sem, 1)

        rdma = pltpu.make_async_remote_copy(
            src_ref=wbuf_ref.at[slot],
            dst_ref=wbuf_ref.at[nxt],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[nxt],
            device_id=right,
            device_id_type=mesh_id,
        )
        rdma.start()
        # matmul on the resident shard while the DMA is in flight
        src = jax.lax.rem(me - i + num, num)  # owner of resident shard
        xs = jax.lax.dynamic_slice_in_dim(x_ref[...], src * c, c, axis=1)
        acc_ref[...] += jnp.dot(xs, wbuf_ref[slot],
                                preferred_element_type=jnp.float32)
        rdma.wait()

        @pl.when(i <= num - 3)
        def _credit():  # slot `slot` is free for the left neighbor now
            pltpu.semaphore_signal(credit_sem, 1, device_id=left,
                                   device_id_type=mesh_id)

        return 0

    # num hops: the final hop's send returns each shard to its owner (one
    # redundant hop) so every hop is symmetric across devices.
    jax.lax.fori_loop(0, num, hop, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def gather_matmul_pallas(x, w_shard, *, axis_name: str,
                         interpret=None):
    """x: (m, k) replicated; w_shard: (k/n, f) local rows.  Returns
    (m, f) = x @ W_full, identical on every device along ``axis_name``."""
    m, k = x.shape
    c, f = w_shard.shape
    kernel = functools.partial(
        _gather_matmul_kernel, num=jax.lax.axis_size(axis_name),
        axis_name=axis_name)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, f), x.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, c, f), w_shard.dtype),
            pltpu.VMEM((m, f), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        interpret=remote_interpret(interpret),
    )(x, w_shard)
