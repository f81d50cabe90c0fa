"""Blockwise (flash) attention Pallas kernel for TPU.

Supports the features the assigned architectures need: causal masking,
sliding-window locality (gemma2/gemma3 local layers), logit soft-capping
(gemma2/grok), GQA (q-heads grouped over kv-heads), and packed-sequence
segment masking.

Grid: (batch·q_heads, q_blocks, kv_blocks) — kv dimension iterated
sequentially per core with the online-softmax state (m, l, acc) carried in
VMEM scratch across kv steps.  BlockSpecs tile q/k/v into VMEM: block
shapes are (1, blk_q, hd) / (1, blk_k, hd) with hd padded by the caller to
a 128 multiple for MXU alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -2.0e38


def _attn_update(qpos_ref, kpos_ref, qseg_ref, kseg_ref,
                 q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                 *, causal, window, softcap, scale):
    """One online-softmax step: fold the current kv block into (m, l, acc)."""
    q = q_ref[0].astype(jnp.float32) * scale  # (blk_q, hd)
    k = k_ref[0].astype(jnp.float32)          # (blk_k, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    qp = qpos_ref[0]  # (blk_q,)
    kp = kpos_ref[0]  # (blk_k,)
    rel = qp[:, None] - kp[None, :]
    mask = kp[None, :] >= 0  # negative kv positions = padding
    if causal:
        mask &= rel >= 0
    if window > 0:
        mask &= rel < window
    qs = qseg_ref[0]
    ks = kseg_ref[0]
    mask &= qs[:, None] == ks[None, :]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _attn_kernel(qpos_ref, kpos_ref, qseg_ref, kseg_ref,
                 q_ref, k_ref, v_ref, out_ref,
                 m_ref, l_ref, acc_ref,
                 *, causal, window, softcap, scale, num_kv_blocks):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _attn_update(qpos_ref, kpos_ref, qseg_ref, kseg_ref,
                 q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                 causal=causal, window=window, softcap=softcap, scale=scale)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)[:, None]
                      ).astype(out_ref.dtype)


def _attn_state_kernel(qpos_ref, kpos_ref, qseg_ref, kseg_ref,
                       q_ref, k_ref, v_ref,
                       m0_ref, l0_ref, acc0_ref,
                       m_out_ref, l_out_ref, acc_out_ref,
                       m_ref, l_ref, acc_ref,
                       *, causal, window, softcap, scale, num_kv_blocks):
    """Same sweep as ``_attn_kernel`` but the softmax state enters through
    carry inputs and leaves unnormalized — the ring-attention building
    block.  A fresh carry (m=NEG_INF, l=0, acc=0) makes the first chunk's
    update sequence bitwise identical to ``_attn_kernel``'s."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = m0_ref[0]
        l_ref[...] = l0_ref[0]
        acc_ref[...] = acc0_ref[0]

    _attn_update(qpos_ref, kpos_ref, qseg_ref, kseg_ref,
                 q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                 causal=causal, window=window, softcap=softcap, scale=scale)

    @pl.when(ik == num_kv_blocks - 1)
    def _emit():
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]
        acc_out_ref[0] = acc_ref[...]


def flash_attention_pallas(q, k, v, *, causal=True, window=0,
                           logit_softcap=0.0, q_positions=None,
                           kv_positions=None, q_segment_ids=None,
                           kv_segment_ids=None, blk_q=128, blk_k=128,
                           scale=None, interpret=None):
    """q: (B, S, H, hd); k, v: (B, T, KH, hd) with H % KH == 0.

    Returns (B, S, H, hd).  S/T are padded to block multiples internally.
    """
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    if scale is None:
        scale = hd ** -0.5
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if q_segment_ids is None:
        q_segment_ids = jnp.zeros((B, S), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = jnp.zeros((B, T), jnp.int32)

    blk_q = min(blk_q, S)
    blk_k = min(blk_k, T)
    pad_q = (-S) % blk_q
    pad_k = (-T) % blk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_q)),
                              constant_values=0)
        q_segment_ids = jnp.pad(q_segment_ids, ((0, 0), (0, pad_q)),
                                constant_values=-2)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad_k)),
                               constant_values=-(10 ** 9))
        kv_segment_ids = jnp.pad(kv_segment_ids, ((0, 0), (0, pad_k)),
                                 constant_values=-1)
    Sp, Tp = S + pad_q, T + pad_k
    nq, nk = Sp // blk_q, Tp // blk_k

    # (B, S, H, hd) -> (B*H, S, hd) with kv-head mapping h -> h // G
    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Sp, hd)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * KH, Tp, hd)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * KH, Tp, hd)

    grid = (B * H, nq, nk)
    kernel = functools.partial(
        _attn_kernel, causal=causal, window=int(window),
        softcap=float(logit_softcap), scale=float(scale), num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q), lambda bh, iq, ik: (bh // H, iq)),
            pl.BlockSpec((1, blk_k), lambda bh, iq, ik: (bh // H, ik)),
            pl.BlockSpec((1, blk_q), lambda bh, iq, ik: (bh // H, iq)),
            pl.BlockSpec((1, blk_k), lambda bh, iq, ik: (bh // H, ik)),
            pl.BlockSpec((1, blk_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, blk_k, hd),
                         lambda bh, iq, ik: ((bh // H) * KH + (bh % H) // G,
                                             ik, 0)),
            pl.BlockSpec((1, blk_k, hd),
                         lambda bh, iq, ik: ((bh // H) * KH + (bh % H) // G,
                                             ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q_positions, kv_positions, q_segment_ids, kv_segment_ids, qh, kh, vh)

    out = out.reshape(B, H, Sp, hd)[:, :, :S]
    return jnp.moveaxis(out, 1, 2)


def _attn_mask(q_positions, kv_positions, q_segment_ids, kv_segment_ids,
               *, causal, window):
    """(B, S, T) boolean mask — the same predicate ``_attn_update`` applies
    blockwise."""
    rel = q_positions[:, :, None] - kv_positions[:, None, :]
    mask = kv_positions[:, None, :] >= 0
    if causal:
        mask &= rel >= 0
    if window > 0:
        mask &= rel < window
    mask &= q_segment_ids[:, :, None] == kv_segment_ids[:, None, :]
    return mask


def flash_attention_bwd_ref(q, k, v, g, *, causal=True, window=0,
                            logit_softcap=0.0, q_positions=None,
                            kv_positions=None, q_segment_ids=None,
                            kv_segment_ids=None, scale=None):
    """Deterministic jnp backward for the flash kernel's math: recompute
    the (masked, soft-capped) probabilities and apply the closed-form
    softmax/attention VJP.  Materializes (B, H, S, T) scores — fine at
    interpret-mode test scale.  This single function defines the VJP for
    both the monolithic wrapper (:func:`flash_attention_diff`) and the
    context-parallel ring (``core.cp``): identical inputs give bitwise
    identical cotangents, which is what the cp golden test pins.
    """
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    if scale is None:
        scale = hd ** -0.5
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if q_segment_ids is None:
        q_segment_ids = jnp.zeros((B, S), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = jnp.zeros((B, T), jnp.int32)

    qf = q.astype(jnp.float32)
    kq = jnp.repeat(k.astype(jnp.float32), G, axis=2)  # (B, T, H, hd)
    vq = jnp.repeat(v.astype(jnp.float32), G, axis=2)
    gf = g.astype(jnp.float32)

    s = jnp.einsum("bshd,bthd->bhst", qf * scale, kq)
    if logit_softcap > 0.0:
        t = jnp.tanh(s / logit_softcap)
        s = logit_softcap * t
    mask = _attn_mask(q_positions, kv_positions, q_segment_ids,
                      kv_segment_ids, causal=causal, window=window)
    s = jnp.where(mask[:, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
    pn = p / l[..., None]

    dv_q = jnp.einsum("bhst,bshd->bthd", pn, gf)
    dp = jnp.einsum("bshd,bthd->bhst", gf, vq)
    delta = jnp.sum(pn * dp, axis=-1)
    ds = pn * (dp - delta[..., None])
    if logit_softcap > 0.0:
        ds = ds * (1.0 - t * t)
    dq = jnp.einsum("bhst,bthd->bshd", ds, kq) * scale
    dk_q = jnp.einsum("bhst,bshd->bthd", ds, qf) * scale
    dk = dk_q.reshape(B, T, KH, G, hd).sum(3)
    dv = dv_q.reshape(B, T, KH, G, hd).sum(3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_diff(static, q, k, v, qp, kp, qs, ks):
    causal, window, softcap, scale, blk_q, blk_k, interpret = static
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, logit_softcap=softcap,
        q_positions=qp, kv_positions=kp, q_segment_ids=qs,
        kv_segment_ids=ks, blk_q=blk_q, blk_k=blk_k, scale=scale,
        interpret=interpret)


def _flash_diff_fwd(static, q, k, v, qp, kp, qs, ks):
    return _flash_diff(static, q, k, v, qp, kp, qs, ks), \
        (q, k, v, qp, kp, qs, ks)


def _flash_diff_bwd(static, res, g):
    causal, window, softcap, scale, _, _, _ = static
    q, k, v, qp, kp, qs, ks = res
    dq, dk, dv = flash_attention_bwd_ref(
        q, k, v, g, causal=causal, window=window, logit_softcap=softcap,
        q_positions=qp, kv_positions=kp, q_segment_ids=qs,
        kv_segment_ids=ks, scale=scale)
    import numpy as np
    z = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return dq, dk, dv, z(qp), z(kp), z(qs), z(ks)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention_diff(q, k, v, *, causal=True, window=0,
                         logit_softcap=0.0, q_positions=None,
                         kv_positions=None, q_segment_ids=None,
                         kv_segment_ids=None, blk_q=128, blk_k=128,
                         scale=None, interpret=None):
    """Differentiable ``flash_attention_pallas``: the raw ``pallas_call``
    has no AD rule, so this wraps it in a custom VJP whose backward is
    :func:`flash_attention_bwd_ref`.  Forward is bitwise the kernel."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    if scale is None:
        scale = hd ** -0.5
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if q_segment_ids is None:
        q_segment_ids = jnp.zeros((B, S), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = jnp.zeros((B, T), jnp.int32)
    static = (bool(causal), int(window), float(logit_softcap), float(scale),
              int(blk_q), int(blk_k), interpret_mode(interpret))
    return _flash_diff(static, q, k, v, q_positions, kv_positions,
                       q_segment_ids, kv_segment_ids)


def fresh_carry(B, S, H, hd):
    """The pre-first-kv-block softmax state: exactly what ``_attn_kernel``
    writes at ik == 0, so a sweep started from this carry is bitwise
    identical to the monolithic kernel's."""
    return (jnp.full((B, S, H), NEG_INF, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32),
            jnp.zeros((B, S, H, hd), jnp.float32))


def finish_attention(carry, dtype=jnp.float32):
    """Normalize a carried (m, l, acc) state — elementwise the same ops as
    ``_attn_kernel``'s final step, so the result is bitwise identical to
    letting the kernel normalize."""
    _, l, acc = carry
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def flash_attention_state(q, k, v, carry=None, *, causal=True, window=0,
                          logit_softcap=0.0, q_positions=None,
                          kv_positions=None, q_segment_ids=None,
                          kv_segment_ids=None, blk_q=128, blk_k=128,
                          scale=None, interpret=None):
    """One online-softmax sweep of q over a kv *chunk*, carrying state.

    q: (B, S, H, hd); k, v: (B, T, KH, hd) — T is the chunk length, not
    the full sequence.  ``carry`` is None (fresh state) or the (m, l, acc)
    returned by the previous chunk's call, shapes (B, S, H) / (B, S, H) /
    (B, S, H, hd), all float32.  Returns the updated (m, l, acc); finish
    with :func:`finish_attention`.

    Sweeping a partition of the kv sequence chunk-by-chunk in ascending
    position order, with T % blk_k == 0 for every chunk (no mid-sequence
    padding blocks), replays the monolithic kernel's exact update sequence
    per q row — the finished output is bitwise identical to
    ``flash_attention_pallas`` on the concatenated sequence.
    """
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    if scale is None:
        scale = hd ** -0.5
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if q_segment_ids is None:
        q_segment_ids = jnp.zeros((B, S), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = jnp.zeros((B, T), jnp.int32)
    if carry is None:
        carry = fresh_carry(B, S, H, hd)
    m, l, acc = carry

    blk_q = min(blk_q, S)
    blk_k = min(blk_k, T)
    pad_q = (-S) % blk_q
    pad_k = (-T) % blk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_q)),
                              constant_values=0)
        q_segment_ids = jnp.pad(q_segment_ids, ((0, 0), (0, pad_q)),
                                constant_values=-2)
        m = jnp.pad(m, ((0, 0), (0, pad_q), (0, 0)),
                    constant_values=NEG_INF)
        l = jnp.pad(l, ((0, 0), (0, pad_q), (0, 0)))
        acc = jnp.pad(acc, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad_k)),
                               constant_values=-(10 ** 9))
        kv_segment_ids = jnp.pad(kv_segment_ids, ((0, 0), (0, pad_k)),
                                 constant_values=-1)
    Sp, Tp = S + pad_q, T + pad_k
    nq, nk = Sp // blk_q, Tp // blk_k

    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Sp, hd)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * KH, Tp, hd)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * KH, Tp, hd)
    mh = jnp.moveaxis(m, 2, 1).reshape(B * H, Sp)
    lh = jnp.moveaxis(l, 2, 1).reshape(B * H, Sp)
    acch = jnp.moveaxis(acc, 2, 1).reshape(B * H, Sp, hd)

    grid = (B * H, nq, nk)
    kernel = functools.partial(
        _attn_state_kernel, causal=causal, window=int(window),
        softcap=float(logit_softcap), scale=float(scale), num_kv_blocks=nk)

    qspec = pl.BlockSpec((1, blk_q), lambda bh, iq, ik: (bh // H, iq))
    kspec = pl.BlockSpec((1, blk_k), lambda bh, iq, ik: (bh // H, ik))
    st1 = pl.BlockSpec((1, blk_q), lambda bh, iq, ik: (bh, iq))
    st2 = pl.BlockSpec((1, blk_q, hd), lambda bh, iq, ik: (bh, iq, 0))
    m_o, l_o, acc_o = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec, kspec, qspec, kspec,
            pl.BlockSpec((1, blk_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, blk_k, hd),
                         lambda bh, iq, ik: ((bh // H) * KH + (bh % H) // G,
                                             ik, 0)),
            pl.BlockSpec((1, blk_k, hd),
                         lambda bh, iq, ik: ((bh // H) * KH + (bh % H) // G,
                                             ik, 0)),
            st1, st1, st2,
        ],
        out_specs=[st1, st1, st2],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Sp), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Sp, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q_positions, kv_positions, q_segment_ids, kv_segment_ids,
      qh, kh, vh, mh, lh, acch)

    return (jnp.moveaxis(m_o.reshape(B, H, Sp)[:, :, :S], 1, 2),
            jnp.moveaxis(l_o.reshape(B, H, Sp)[:, :, :S], 1, 2),
            jnp.moveaxis(acc_o.reshape(B, H, Sp, hd)[:, :, :S], 1, 2))
