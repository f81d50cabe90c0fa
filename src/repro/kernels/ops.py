"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) compiles the kernels on a TPU and
interprets them anywhere else; ``repro.kernels.interpret_mode`` decides,
for every kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gather_matmul import gather_matmul_pallas
from repro.kernels.odc_gather import odc_gather_layers_pallas, odc_gather_pallas
from repro.kernels.odc_scatter import (
    odc_scatter_accumulate_layers_pallas,
    odc_scatter_accumulate_pallas,
)
from repro.kernels.quant import (
    dequantize_pallas,
    odc_gather_q8_pallas,
    odc_scatter_accumulate_q8_pallas,
    quantize_pallas,
)
from repro.kernels.ssd_scan import ssd_scan_pallas


def odc_gather(x_shard, axis_name: str, *, interpret=None):
    """Inside shard_map: (c, ...) local shard -> (n*c, ...) full tensor,
    via one-sided remote-DMA ring hops (no fused collective)."""
    stacked = odc_gather_pallas(x_shard, axis_name=axis_name,
                                interpret=interpret)
    n = stacked.shape[0]
    return stacked.reshape((n * x_shard.shape[0],) + x_shard.shape[1:])


def odc_scatter_accumulate(y, axis_name: str, *, interpret=None):
    """Inside shard_map: (n*c, ...) local contribution -> (c, ...) owned,
    fully-accumulated chunk."""
    n = jax.lax.axis_size(axis_name)
    c = y.shape[0] // n
    stacked = y.reshape((n, c) + y.shape[1:])
    return odc_scatter_accumulate_pallas(stacked, axis_name=axis_name,
                                         interpret=interpret)


def odc_gather_layers(x_stacked, axis_name: str, *, interpret=None):
    """Inside shard_map: (L, c, ...) stacked local shards -> (L, n*c, ...)
    per-layer full tensors.  The L ring chains run with no inter-layer
    barrier (cross-layer prefetch, schedule='overlap')."""
    stacked = odc_gather_layers_pallas(x_stacked, axis_name=axis_name,
                                       interpret=interpret)
    L, n, c = stacked.shape[0], stacked.shape[1], stacked.shape[2]
    return stacked.reshape((L, n * c) + stacked.shape[3:])


def odc_scatter_accumulate_layers(y_stacked, axis_name: str, *,
                                  interpret=None):
    """Inside shard_map: (L, n*c, ...) stacked contributions -> (L, c, ...)
    owned, fully-accumulated chunks, with the L scatter rings chained
    through one pair of receive slots."""
    n = jax.lax.axis_size(axis_name)
    L, full = y_stacked.shape[0], y_stacked.shape[1]
    c = full // n
    stacked = y_stacked.reshape((L, n, c) + y_stacked.shape[2:])
    return odc_scatter_accumulate_layers_pallas(stacked, axis_name=axis_name,
                                                interpret=interpret)


def _chunk_blocks(x, chunk):
    """Flatten + zero-pad to the (n_chunks, chunk) codec layout."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, chunk)


def quantize_int8(x, *, interpret=None):
    """Chunked-int8 encode (Pallas codec kernel): any-shape tensor ->
    ((n_chunks, chunk) int8 values, (n_chunks, 1) f32 scales) — the wire
    format of ``repro.core.odc.quantize_chunked`` (its jnp oracle)."""
    from repro.core.odc import INT8_CHUNK
    return quantize_pallas(_chunk_blocks(x, INT8_CHUNK), interpret=interpret)


def dequantize_int8(q, scales, shape, dtype=jnp.float32, *, interpret=None):
    """Invert :func:`quantize_int8` back to a tensor of ``shape``."""
    flat = dequantize_pallas(q, scales, interpret=interpret).reshape(-1)
    size = 1
    for s in shape:
        size *= s
    return flat[:size].reshape(shape).astype(dtype)


def odc_gather_q8(x_shard, axis_name: str, *, interpret=None):
    """Inside shard_map: (c, ...) local shard -> (n*c, ...) full tensor
    with the ring payload chunked-int8 compressed — quantized ONCE at each
    shard's origin (error does not compound with ring distance); the local
    shard lands exactly."""
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    q, scales = quantize_int8(x_shard, interpret=interpret)
    qs, ss = odc_gather_q8_pallas(q, scales, axis_name=axis_name,
                                  interpret=interpret)
    size = x_shard.size
    flat = (qs.astype(jnp.float32) * ss).reshape(n, -1)[:, :size]
    shards = flat.reshape((n,) + x_shard.shape).astype(x_shard.dtype)
    shards = jax.lax.dynamic_update_index_in_dim(
        shards, x_shard.astype(shards.dtype), me, 0)
    return shards.reshape((n * x_shard.shape[0],) + x_shard.shape[1:])


def odc_scatter_accumulate_q8(y, axis_name: str, *, interpret=None):
    """Inside shard_map: (n*c, ...) local contribution -> (c, ...) owned,
    fully-accumulated chunk, with every hop's outgoing partial sum
    requantized to the chunked-int8 wire format."""
    from repro.core.odc import INT8_CHUNK
    n = jax.lax.axis_size(axis_name)
    c = y.shape[0] // n
    flat = y.reshape(n, -1).astype(jnp.float32)
    pad = (-flat.shape[1]) % INT8_CHUNK
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    blocks = flat.reshape(n, -1, INT8_CHUNK)
    out = odc_scatter_accumulate_q8_pallas(blocks, axis_name=axis_name,
                                           interpret=interpret)
    csize = y.size // n
    return out.reshape(-1)[:csize].reshape((c,) + y.shape[1:]).astype(y.dtype)


def gather_matmul(x, w_shard, axis_name: str, *, interpret=None):
    """Inside shard_map: x (m, k) replicated, w_shard (k/n, f) local ->
    (m, f) = x @ W_full, with the ring DMA hidden under the matmuls."""
    return gather_matmul_pallas(x, w_shard, axis_name=axis_name,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "logit_softcap", "blk_q", "blk_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                    q_positions=None, kv_positions=None, q_segment_ids=None,
                    kv_segment_ids=None, blk_q=128, blk_k=128,
                    interpret=None):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, logit_softcap=logit_softcap,
        q_positions=q_positions, kv_positions=kv_positions,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
        blk_q=blk_q, blk_k=blk_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int, interpret=None):
    return ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                           interpret=interpret)
