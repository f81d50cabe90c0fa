"""Context parallelism: ring attention over a ``cp`` mesh axis.

The fourth mesh dimension.  The sequence dim of every batch leaf is
sharded over ``cp``; everything outside attention is position-local, so
only attention needs communication — each device keeps its q shard and
the KV shards circulate around the cp ring via the ODC p2p primitives
(``core.odc.ring_gather``, or the one-sided remote-DMA kernel ring from
``kernels.odc_gather`` via ``gather_impl='kernel'``).

Bit-identity contract.  The online-softmax (m, l, acc) state is carried
across KV chunks with ``kernels.flash_attention.flash_attention_state``;
chunks are swept in ascending global position order and the final
normalization reuses the kernel's exact formula, so the per-row update
sequence — and therefore the output, bitwise — is identical to running
the monolithic ``flash_attention_pallas`` on the gathered sequence
(provided every chunk length is a multiple of ``blk_k``, which keeps the
kv block partition literally the same).  The raw ``pallas_call`` has no
AD rule, so the VJP story is explicit: the backward gathers the full
sequence and applies ``flash_attention_bwd_ref`` — the very function that
defines ``flash_attention_diff``'s (the differentiable monolithic
wrapper's) VJP — then slices this device's shard back out, so cotangents
are bitwise the single-device VJP's by construction (the interpret-mode
reproduction trades bwd memory for that guarantee; a chunked bwd is a
straightforward extension).

Causal load balance.  Under a causal mask, contiguous sharding gives the
last rank ~2× the unmasked score area of a mid ring.  The head+tail
interleave assigns device r of n the global chunk pair (r, 2n-1-r): every
device owns one early and one late chunk, equalizing unmasked area.
Masking is position-based (true global positions circulate with the KV),
so the interleaved layout is transparent to correctness; masked
chunk-steps are exact float no-ops in the kernel's update algebra, which
is what lets the simulator's ``ContextRingPolicy`` model them as skipped
hops without breaking the bit-identity story on real hardware.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import odc
from repro.kernels import interpret_mode
from repro.kernels.flash_attention import (finish_attention,
                                           flash_attention_bwd_ref,
                                           flash_attention_state)


# ---------------------------------------------------------------------------
# head+tail interleaved chunk layout
# ---------------------------------------------------------------------------
def interleave_indices(total: int, cp: int) -> np.ndarray:
    """Device-layout order of global sequence indices.

    The global sequence is cut into ``2*cp`` equal chunks; device r's
    local shard is [chunk r, chunk 2*cp-1-r] — one head, one tail, so the
    causal unmasked area is equal across ranks.  Returns a permutation
    ``perm`` with ``x_device_layout = x_global[perm]``.
    """
    assert total % (2 * cp) == 0, (total, cp)
    chunk = total // (2 * cp)
    idx = np.arange(total).reshape(2 * cp, chunk)
    order = []
    for r in range(cp):
        order += [r, 2 * cp - 1 - r]
    return idx[order].reshape(-1)


def unshuffle_indices(total: int, cp: int) -> np.ndarray:
    """Inverse of :func:`interleave_indices`:
    ``x_global = x_device_layout[unshuffle_indices(total, cp)]``."""
    perm = interleave_indices(total, cp)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(total)
    return inv


def _unshuffle_gathered(x, cp: int):
    """Ring-gathered (device order) -> global order along leading axis.

    With interleave, device r's shard is (chunk r, chunk 2n-1-r); the
    device-order concatenation reshaped to (n, 2, chunk, ...) holds the
    head chunks in [:, 0] (ascending) and the tail chunks in [:, 1]
    (descending).  Pure reshape/flip/concat — an exact permutation.
    """
    n = cp
    chunk = x.shape[0] // (2 * n)
    g = x.reshape((n, 2, chunk) + x.shape[1:])
    return jnp.concatenate([g[:, 0], g[::-1, 1]], 0).reshape(
        (2 * n * chunk,) + x.shape[1:])


def _reshuffle_global(x, cp: int):
    """Global order -> ring device order along the leading axis (the exact
    inverse of :func:`_unshuffle_gathered`)."""
    n = cp
    chunk = x.shape[0] // (2 * n)
    g = x.reshape((2 * n, chunk) + x.shape[1:])
    pairs = jnp.stack([g[:n], g[n:][::-1]], 1)  # (n, 2, chunk, ...)
    return pairs.reshape((2 * n * chunk,) + x.shape[1:])


# ---------------------------------------------------------------------------
# ring attention (inside shard_map, cp axis in scope)
# ---------------------------------------------------------------------------
def _gather_seq(x, axis_name, gather_impl):
    """Ring-gather a (B, S_loc, ...) tensor's sequence dim over the cp
    axis -> (B, n*S_loc, ...) in ring device order, via p2p hops only."""
    xs = jnp.moveaxis(x, 1, 0)  # (S_loc, B, ...)
    from repro.core import backend as _backend
    _backend.CP.record_ring_hop(xs, axis_name)
    if gather_impl == "kernel":
        from repro.kernels import ops
        full = ops.odc_gather(xs, axis_name)
    else:
        full = odc.ring_gather(xs, axis_name)
    return jnp.moveaxis(full, 0, 1)


def _chunk_blk_k(chunk: int, blk_k: int) -> int:
    """Largest block size <= blk_k that divides the chunk (no mid-sequence
    padding blocks -> the kv block partition matches the monolithic
    kernel's whenever chunk % blk_k == 0)."""
    b = min(blk_k, chunk)
    return b if chunk % b == 0 else math.gcd(chunk, b)


def _ring_fwd_impl(static, q, k, v, qp, kp, qs, ks):
    (axis_name, causal, window, softcap, scale, blk_q, blk_k, interpret,
     gather_impl, interleave) = static
    n = odc.axis_size(axis_name)
    S_loc = q.shape[1]
    nchunks = 2 * n if interleave else n
    assert S_loc % 2 == 0 or not interleave, S_loc
    chunk = S_loc // 2 if interleave else S_loc

    kf = _gather_seq(k, axis_name, gather_impl)
    vf = _gather_seq(v, axis_name, gather_impl)
    kpf = _gather_seq(kp[..., None], axis_name, gather_impl)[..., 0]
    ksf = _gather_seq(ks[..., None], axis_name, gather_impl)[..., 0]
    if interleave:
        kf, vf, kpf, ksf = (jnp.moveaxis(
            _unshuffle_gathered(jnp.moveaxis(x, 1, 0), n), 0, 1)
            for x in (kf, vf, kpf, ksf))

    bk = _chunk_blk_k(chunk, blk_k)
    carry = None
    for c in range(nchunks):  # ascending global chunk order — the
        sl = slice(c * chunk, (c + 1) * chunk)  # monolithic kv block order
        carry = flash_attention_state(
            q, kf[:, sl], vf[:, sl], carry, causal=causal, window=window,
            logit_softcap=softcap, q_positions=qp, kv_positions=kpf[:, sl],
            q_segment_ids=qs, kv_segment_ids=ksf[:, sl],
            blk_q=blk_q, blk_k=bk, scale=scale, interpret=interpret)
    return finish_attention(carry, q.dtype)


def _ring_bwd_impl(static, res, g):
    (axis_name, causal, window, softcap, scale, blk_q, blk_k, interpret,
     gather_impl, interleave) = static
    q, k, v, qp, kp, qs, ks = res
    n = odc.axis_size(axis_name)
    me = odc.axis_index(axis_name)
    S_loc = q.shape[1]

    def full(x):
        f = _gather_seq(x, axis_name, "jnp")
        if interleave:
            f = jnp.moveaxis(_unshuffle_gathered(jnp.moveaxis(f, 1, 0), n),
                             0, 1)
        return f

    qf, kf, vf, gf = full(q), full(k), full(v), full(g)
    qpf = full(qp[..., None])[..., 0]
    kpf = full(kp[..., None])[..., 0]
    qsf = full(qs[..., None])[..., 0]
    ksf = full(ks[..., None])[..., 0]

    # the SAME function that defines the monolithic wrapper's VJP
    # (flash_attention_diff), applied to bitwise-identical gathered inputs
    # -> bitwise-identical cotangents, sliced back to this device's shard
    dqf, dkf, dvf = flash_attention_bwd_ref(
        qf, kf, vf, gf, causal=causal, window=window, logit_softcap=softcap,
        q_positions=qpf, kv_positions=kpf, q_segment_ids=qsf,
        kv_segment_ids=ksf, scale=scale)

    def local(df):
        # global order -> ring device order, then my contiguous block is
        # exactly my local (interleaved) layout
        ds = jnp.moveaxis(df, 1, 0)
        if interleave:
            ds = _reshuffle_global(ds, n)
        ds = jax.lax.dynamic_slice_in_dim(ds, me * S_loc, S_loc, 0)
        return jnp.moveaxis(ds, 0, 1)

    z = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (local(dqf), local(dkf), local(dvf),
            z(qp), z(kp), z(qs), z(ks))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_attn(static, q, k, v, qp, kp, qs, ks):
    return _ring_fwd_impl(static, q, k, v, qp, kp, qs, ks)


def _ring_attn_fwd(static, q, k, v, qp, kp, qs, ks):
    out = _ring_fwd_impl(static, q, k, v, qp, kp, qs, ks)
    return out, (q, k, v, qp, kp, qs, ks)


_ring_attn.defvjp(_ring_attn_fwd, _ring_bwd_impl)


def ring_attention(q, k, v, *, axis_name="cp", causal=True, window=0,
                   logit_softcap=0.0, q_positions=None, kv_positions=None,
                   q_segment_ids=None, kv_segment_ids=None, blk_q=128,
                   blk_k=128, scale=None, interpret=None,
                   gather_impl="jnp", interleave=True):
    """Context-parallel self-attention for one (B, S_loc, H, hd) q shard.

    Call inside ``shard_map`` with ``axis_name`` in scope.  k/v/positions/
    segment ids are this device's matching sequence shards (self-attention
    layout); KV circulates over the cp ring, q stays put.  With
    ``interleave=True`` the local shard is the head+tail chunk pair laid
    out by :func:`interleave_indices` — positions/segment ids must carry
    the TRUE global values, which makes masking layout-transparent.

    Forward is bitwise the monolithic ``flash_attention_pallas`` on the
    gathered sequence; backward takes that kernel's own VJP (see module
    docstring).  ``gather_impl``: 'jnp' (``odc.ring_gather``) or 'kernel'
    (the remote-DMA ring from ``kernels.odc_gather``) — identical results.
    """
    B, S, H, hd = q.shape
    if scale is None:
        scale = hd ** -0.5
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if kv_positions is None:
        kv_positions = q_positions
    if q_segment_ids is None:
        q_segment_ids = jnp.zeros((B, S), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = q_segment_ids
    static = (axis_name, bool(causal), int(window), float(logit_softcap),
              float(scale), int(blk_q), int(blk_k), interpret_mode(interpret),
              gather_impl, bool(interleave))
    return _ring_attn(static, q, k, v, q_positions, kv_positions,
                      q_segment_ids, kv_segment_ids)


# ---------------------------------------------------------------------------
# model hook: install ring attention as the layers.py attention impl
# ---------------------------------------------------------------------------
def allgather_attention(q, k, v, *, axis_name="cp", causal=True, window=0,
                        logit_softcap=0.0, q_positions=None,
                        kv_positions=None, q_segment_ids=None,
                        kv_segment_ids=None, block_kv=0, scale=None,
                        interleave=True):
    """The differentiable fallback cp attention: all_gather the KV shards
    over the cp axis and run the jnp blockwise kernel with the local q.

    Used where the bitwise ring path can't engage — a *traced* sliding
    window (mixed local/global layer scans carry the window through the
    scan).  ``jax.lax.all_gather``'s transpose is a ``psum_scatter``, so AD
    works end to end; masking is position/segment based, so results are
    correct (not bitwise) for any KV chunk layout — KV is still restored
    to global order for determinism parity with the single-device path.
    """
    from repro.models.layers import blockwise_attention

    n = odc.axis_size(axis_name)
    B, S_loc = q.shape[:2]
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S_loc), (B, S_loc))
    if kv_positions is None:
        kv_positions = q_positions

    def full(x):
        f = jax.lax.all_gather(x, axis_name, axis=1, tiled=True)
        if interleave:
            f = jnp.take(f, unshuffle_indices(f.shape[1], n), axis=1)
        return f

    kf, vf, kpf = full(k), full(v), full(kv_positions)
    ksf = full(kv_segment_ids) if kv_segment_ids is not None else None
    return blockwise_attention(
        q, kf, vf, causal=causal, window=window,
        logit_softcap=logit_softcap, q_positions=q_positions,
        kv_positions=kpf, q_segment_ids=q_segment_ids,
        kv_segment_ids=ksf, block_kv=block_kv or kf.shape[1], scale=scale)


def cp_attention_impl(axis_name="cp", *, blk_q=128, blk_k=128,
                      interpret=None, gather_impl="jnp", interleave=True):
    """An ``attn_apply``-compatible impl that rings over ``axis_name``.

    Install at trace time (inside the shard_mapped grad function) with
    ``layers.set_attention_impl`` and restore the previous impl in a
    ``finally``.  Static-window layers take the bitwise ring path; a
    traced window falls back to :func:`allgather_attention`.
    """
    def impl(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
             q_positions=None, kv_positions=None, q_segment_ids=None,
             kv_segment_ids=None, block_kv=0, scale=None):
        if k.shape[1] != q.shape[1]:
            raise NotImplementedError(
                "cp ring attention is a training-path impl (self-attention "
                "layout); decode caches are served by the flat backends")
        if not isinstance(window, (int, np.integer)):
            return allgather_attention(
                q, k, v, axis_name=axis_name, causal=causal, window=window,
                logit_softcap=logit_softcap, q_positions=q_positions,
                kv_positions=kv_positions, q_segment_ids=q_segment_ids,
                kv_segment_ids=kv_segment_ids, block_kv=block_kv,
                scale=scale, interleave=interleave)
        return ring_attention(
            q, k, v, axis_name=axis_name, causal=causal, window=int(window),
            logit_softcap=logit_softcap, q_positions=q_positions,
            kv_positions=kv_positions, q_segment_ids=q_segment_ids,
            kv_segment_ids=kv_segment_ids, blk_q=blk_q,
            blk_k=min(blk_k, block_kv) if block_kv else blk_k,
            scale=scale, interpret=interpret, gather_impl=gather_impl,
            interleave=interleave)

    return impl
