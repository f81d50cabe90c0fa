"""Core neural-net building blocks (pure JAX, functional, pytree params).

Everything here is written to be usable from three places:
  * the GSPMD engine (pjit; shapes at production scale) — so attention is
    blockwise (flash-style online softmax via ``lax.scan``) and never
    materializes (S, S) score matrices;
  * the explicit shard_map FSDP engine;
  * CPU smoke tests at reduced scale.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -2.0e38

# ---------------------------------------------------------------------------
# activation-sharding hook: engines may install a trace-time function
# (tensor, kind) -> tensor that applies with_sharding_constraint, anchoring
# GSPMD's choices on the big attention intermediates (see core/gspmd's
# serve builders).  kinds: "q_heads", "kv_heads", "attn_out".
# ---------------------------------------------------------------------------
_ACT_SHARDER = None


def set_activation_sharder(fn):
    global _ACT_SHARDER
    _ACT_SHARDER = fn


def shard_act(x, kind: str):
    return _ACT_SHARDER(x, kind) if _ACT_SHARDER is not None else x


# ---------------------------------------------------------------------------
# attention-impl hook: swap the pure-jnp blockwise attention for the Pallas
# flash kernel (repro.kernels.ops.flash_attention) on TPU.  The replacement
# must accept blockwise_attention's keyword signature.
# ---------------------------------------------------------------------------
_ATTN_IMPL = None


def set_attention_impl(fn):
    """fn(q, k, v, **kw) or None to restore the jnp path.

    Returns the previously installed impl so callers can restore it."""
    global _ATTN_IMPL
    prev = _ATTN_IMPL
    _ATTN_IMPL = fn
    return prev


def get_attention_impl():
    return _ATTN_IMPL


class _AttnImplGuard:
    """Handle returned by the impl installers: holds the displaced impl and
    restores it on ``close()`` / ``with``-exit, so a test or module can't
    leak its attention backend into the next one."""

    def __init__(self, prev):
        self._prev = prev
        self._done = False

    def close(self):
        if not self._done:
            self._done = True
            set_attention_impl(self._prev)

    restore = close

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def use_pallas_flash_attention(*, interpret=None, blk_q=128, blk_k=128):
    """Install the Pallas flash-attention kernel as the attention impl.

    Returns a guard usable as a context manager; on exit (or ``.close()``)
    the previously installed impl is restored:

        with use_pallas_flash_attention():
            loss = step(...)
    """
    from repro.kernels.flash_attention import flash_attention_diff

    def impl(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
             q_positions=None, kv_positions=None, q_segment_ids=None,
             kv_segment_ids=None, block_kv=0, scale=None):
        if not isinstance(window, (int, np.integer)):
            # traced per-layer window (mixed local/global scans): the kernel
            # needs a static window — fall back to the jnp path
            return blockwise_attention(
                q, k, v, causal=causal, window=window,
                logit_softcap=logit_softcap, q_positions=q_positions,
                kv_positions=kv_positions, q_segment_ids=q_segment_ids,
                kv_segment_ids=kv_segment_ids,
                block_kv=block_kv or k.shape[1], scale=scale)
        # the custom-VJP wrapper: pallas forward, closed-form jnp backward
        # (raw pallas_call has no AD rule)
        return flash_attention_diff(
            q, k, v, causal=causal, window=int(window),
            logit_softcap=logit_softcap,
            q_positions=q_positions, kv_positions=kv_positions,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            blk_q=blk_q, blk_k=min(blk_k, block_kv) if block_kv else blk_k,
            scale=scale, interpret=interpret)

    return _AttnImplGuard(set_attention_impl(impl))


# --------------------------------------------------------------------------
# initialization helpers
# --------------------------------------------------------------------------
def _normal(key, shape):
    """Standard normals, fenced so that a jitted (e.g. sharded) init cannot
    fold the caller's scale into the sampler's own arithmetic: the values
    are then bitwise those of an eager init."""
    return jax.lax.optimization_barrier(jax.random.normal(key, shape))


def dense_init(key, shape, dtype, scale: float = 1.0):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / (fan_in ** 0.5)
    return (_normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (_normal(key, shape) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms / activations / rope
# --------------------------------------------------------------------------
def rms_norm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def softcap(x, cap: float):
    """Gemma2/grok-style logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


def activation_fn(name: str):
    if name in ("swiglu",):
        return jax.nn.silu
    if name in ("geglu",):
        return functools.partial(jax.nn.gelu, approximate=True)
    if name == "gelu":
        return functools.partial(jax.nn.gelu, approximate=True)
    raise ValueError(name)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(cfg, dim: int):
    """(inverse frequencies (dim/2,), cos/sin factor) of YaRN rope as
    DeepseekV2YarnRotaryEmbedding computes them: half-dims below the
    correction range keep theta's frequencies, those above are divided by
    the factor, and a linear ramp blends the two in between."""
    theta, factor = cfg.rope_theta, cfg.yarn_factor
    orig = cfg.yarn_original_max_position

    def corr_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    extra = rope_freqs(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    return inv, (_yarn_mscale(factor, cfg.yarn_mscale)
                 / _yarn_mscale(factor, cfg.yarn_mscale_all_dim))


def mla_softmax_scale(cfg) -> float:
    """qk_head_dim^-0.5, times mscale(factor, mscale_all_dim)^2 under
    YaRN (DeepseekV2Attention.softmax_scale)."""
    scale = cfg.mla_qk_head_dim ** -0.5
    if cfg.yarn_factor:
        scale *= _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def apply_rope(x, positions, theta: float, freqs=None, mscale: float = 1.0):
    """x: (..., S, H, hd); positions: (..., S).  ``freqs``: the (hd/2,)
    inverse frequencies (default: plain rope at ``theta``); ``mscale``
    multiplies cos and sin."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]  # (..., S, 1, hd/2) broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# blockwise attention (flash-style online softmax, pure jnp + lax.scan)
# --------------------------------------------------------------------------
def _block_mask(q_pos, kv_pos, q_seg, kv_seg, *, causal: bool, window: int):
    """(Bq, Bk) boolean mask for one (query-block, kv-block) pair."""
    m = jnp.ones((q_pos.shape[-1], kv_pos.shape[-1]), bool)
    rel = q_pos[:, None] - kv_pos[None, :]
    if causal:
        m &= rel >= 0
    if window > 0:
        m &= rel < window
    if q_seg is not None:
        m &= q_seg[:, None] == kv_seg[None, :]
    return m


def blockwise_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    logit_softcap: float = 0.0,
    q_positions=None,
    kv_positions=None,
    q_segment_ids=None,
    kv_segment_ids=None,
    block_kv: int = 512,
    scale: Optional[float] = None,
):
    """Attention without materializing (S, T) scores.

    q: (B, S, H, hd); k: (B, T, KH, hd), v: (B, T, KH, hv) with
    H % KH == 0 (GQA); hv may differ from hd (latent attention).
    Scans over KV blocks carrying the online-softmax state (m, l, acc).
    Memory: O(S * block_kv) per head instead of O(S * T).
    """
    B, S, H, hd = q.shape
    T, KH, hv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KH
    if scale is None:
        scale = hd ** -0.5
    if q_positions is None:
        q_positions = jnp.arange(S)[None, :].repeat(B, 0)
    if kv_positions is None:
        kv_positions = jnp.arange(T)[None, :].repeat(B, 0)

    block_kv = min(block_kv, T)
    num_blocks = -(-T // block_kv)
    pad = num_blocks * block_kv - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)), constant_values=-(10 ** 9))
        if kv_segment_ids is not None:
            kv_segment_ids = jnp.pad(kv_segment_ids, ((0, 0), (0, pad)), constant_values=-1)

    # reshape GQA: (B, S, KH, G, hd)
    qg = q.reshape(B, S, KH, G, hd).astype(jnp.float32) * scale
    kb = k.reshape(B, num_blocks, block_kv, KH, hd).astype(jnp.float32)
    vb = v.reshape(B, num_blocks, block_kv, KH, hv).astype(jnp.float32)
    kvp = kv_positions.reshape(B, num_blocks, block_kv)
    kvs = (
        kv_segment_ids.reshape(B, num_blocks, block_kv)
        if kv_segment_ids is not None
        else None
    )

    use_seg = q_segment_ids is not None and kv_segment_ids is not None

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kblk, vblk, pblk, sblk = blk
        # scores: (B, S, KH, G, block_kv)
        s = jnp.einsum("bskgd,bckd->bskgc", qg, kblk)
        if logit_softcap > 0.0:
            s = softcap(s, logit_softcap)
        # mask: (B, S, block_kv)
        rel = q_positions[:, :, None] - pblk[:, None, :]
        mask = jnp.ones_like(rel, bool)
        if causal:
            mask &= rel >= 0
        if not (isinstance(window, int) and window == 0):
            # window may be a traced scalar (mixed local/global layer scans);
            # window <= 0 disables it dynamically.
            w = jnp.asarray(window)
            mask &= rel < jnp.where(w > 0, w, jnp.asarray(2 ** 30))
        if use_seg:
            mask &= q_segment_ids[:, :, None] == sblk[:, None, :]
        mask &= pblk[:, None, :] >= 0  # padding blocks
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bskgc,bckd->bskgd", p, vblk)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, S, KH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, KH, G), jnp.float32)
    acc0 = jnp.zeros((B, S, KH, G, hv), jnp.float32)
    blks = (
        jnp.moveaxis(kb, 1, 0),
        jnp.moveaxis(vb, 1, 0),
        jnp.moveaxis(kvp, 1, 0),
        jnp.moveaxis(kvs, 1, 0) if kvs is not None else jnp.zeros((num_blocks, B, block_kv), jnp.int32),
    )
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), blks)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, S, H, hv).astype(q.dtype)


def reference_attention(q, k, v, **kw):
    """Small-shape oracle: same semantics, materialized scores."""
    return blockwise_attention(q, k, v, block_kv=max(k.shape[1], 1), **kw)


# --------------------------------------------------------------------------
# attention layer (params + apply), GQA + rope + cache
# --------------------------------------------------------------------------
def attn_params(key, cfg, dtype, prefix_shape=()):
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], prefix_shape + (d, qd), dtype),
        "wk": dense_init(ks[1], prefix_shape + (d, kvd), dtype),
        "wv": dense_init(ks[2], prefix_shape + (d, kvd), dtype),
        "wo": dense_init(ks[3], prefix_shape + (qd, d), dtype, scale=1.0 / max(1, cfg.num_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros(prefix_shape + (hd,), dtype)
        p["k_norm"] = jnp.zeros(prefix_shape + (hd,), dtype)
    return p


def attn_apply(
    cfg,
    p,
    x,
    *,
    kind: str = "global",
    window=None,
    positions=None,
    segment_ids=None,
    cache=None,
    cache_index=None,
    cross_kv=None,
    causal: bool = True,
    block_kv: int = 512,
):
    """Self- (or cross-) attention.

    cache: optional dict {"k": (B, T, KH, hd), "v": ...} for decode; the new
    kv is written at ``cache_index`` and attention runs over the cache.
    cross_kv: (k, v) tuple for cross-attention (encoder-decoder).
    Returns (out, updated_cache).
    """
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    H, KH = cfg.num_heads, cfg.num_kv_heads
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(B, S, H, hd)
    q = shard_act(q, "q_heads")
    if cross_kv is None:
        k = jnp.einsum("bsd,dk->bsk", x, p["wk"]).reshape(B, S, KH, hd)
        v = jnp.einsum("bsd,dk->bsk", x, p["wv"]).reshape(B, S, KH, hd)
        k = shard_act(k, "kv_heads")
        v = shard_act(v, "kv_heads")
    else:
        k, v = cross_kv
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        if cross_kv is None:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, 0)
    if cross_kv is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    kv_positions = positions
    kv_segment_ids = segment_ids
    if cross_kv is not None:
        T = k.shape[1]
        kv_positions = jnp.arange(T)[None, :].repeat(B, 0)
        kv_segment_ids = None
    causal = causal and cross_kv is None
    if cache is not None:
        # decode: write new kv into the cache, attend over the whole cache
        cache, kv_positions = _cache_write(cache, {"k": k, "v": v},
                                           cache_index, S)
        k, v = cache["k"], cache["v"]
        kv_segment_ids = None

    if window is None:
        window = cfg.sliding_window if kind == "local" else 0
    attn_fn = _ATTN_IMPL or blockwise_attention
    out = attn_fn(
        q,
        k,
        v,
        causal=causal,
        window=window,
        logit_softcap=cfg.attn_logit_softcap,
        q_positions=positions,
        kv_positions=kv_positions,
        q_segment_ids=segment_ids if cross_kv is None else None,
        kv_segment_ids=kv_segment_ids if cross_kv is None else None,
        block_kv=block_kv,
    )
    out = shard_act(out, "q_heads")
    out = jnp.einsum("bsq,qd->bsd", out.reshape(B, S, H * hd), p["wo"])
    return out, cache


def _cache_write(cache, new, idx, S):
    """Write ``new`` ({name: (B, S, ...)}) into the decode cache ({name:
    (B, T, ...)}) at ``idx``; returns the cache and the (B, T) key
    positions, -1e9 where nothing is written yet.

    ``idx`` a scalar (wave decode) or a (B,) vector (continuous batching).
    Per-row write index: each slot decodes at its own position, so the
    write is a one-hot select per row and the validity mask is per-row
    too.  Rows beyond a slot's cursor hold stale entries from a retired
    request; the mask zeroes their attention weight exactly (blockwise
    softmax underflows the -1e9 positions to 0.0), so stale contents are
    inert."""
    B, T = next(iter(cache.values())).shape[:2]
    kv_positions = jnp.arange(T)[None, :].repeat(B, 0)
    if getattr(idx, "ndim", 0) == 1:
        if S != 1:
            raise ValueError(
                f"vector cache_index requires single-token decode, "
                f"got S={S}")
        hot = jnp.arange(T)[None, :] == idx[:, None]
        cache = {n: jnp.where(hot.reshape(hot.shape + (1,) * (c.ndim - 2)),
                              new[n].astype(c.dtype), c)
                 for n, c in cache.items()}
        return cache, jnp.where(kv_positions <= idx[:, None], kv_positions,
                                -(10 ** 9))
    cache = {n: jax.lax.dynamic_update_slice_in_dim(
        c, new[n].astype(c.dtype), idx, axis=1) for n, c in cache.items()}
    # positions beyond the write index are invalid
    return cache, jnp.where(kv_positions[0] <= idx + S - 1, kv_positions,
                            -(10 ** 9))


# --------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2), params + apply + cache
# --------------------------------------------------------------------------
def mla_params(key, cfg, dtype, prefix_shape=()):
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], prefix_shape + (d, H * (dn + dr)), dtype),
        "wkv_a": dense_init(ks[1], prefix_shape + (d, r + dr), dtype),
        "kv_norm": jnp.zeros(prefix_shape + (r,), dtype),
        "wkv_b": dense_init(ks[2], prefix_shape + (r, H * (dn + dv)), dtype),
        "wo": dense_init(ks[3], prefix_shape + (H * dv, d), dtype,
                         scale=1.0 / max(1, cfg.num_layers) ** 0.5),
    }


def mla_apply(cfg, p, x, *, window=0, positions=None, segment_ids=None,
              cache=None, cache_index=None, block_kv: int = 512):
    """Multi-head latent attention without query compression.

    q = x Wq, per head [q_nope (dn) | q_rope (dr)]; [c | k_rope] = x Wkv_a
    with c the latent (kv_lora_rank), normed, and k_rope one dr-wide key
    shared by every head; [k_nope | v] = c Wkv_b per head; rope (YaRN
    where configured) on q_rope and k_rope; o = softmax(q k^T scale) v Wo.

    cache: {"c": (B, T, r), "k_rope": (B, T, dr)} for decode: the latent
    and the rope key are cached, and the whole cache is expanded per call.
    Returns (out, updated_cache).
    """
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, 0)
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(B, S, H, -1)
    q = shard_act(q, "q_heads")
    with jax.named_scope("latent"):
        ckr = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
        c = rms_norm(ckr[..., :r], p["kv_norm"], cfg.norm_eps)
        k_rope = ckr[..., r:]
    freqs, mscale = (yarn_freqs(cfg, cfg.qk_rope_head_dim) if cfg.yarn_factor
                     else (None, 1.0))
    rope = lambda t: apply_rope(t, positions, cfg.rope_theta, freqs, mscale)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
    k_rope = rope(k_rope[:, :, None, :])[:, :, 0]
    kv_positions, kv_segment_ids = positions, segment_ids
    if cache is not None:
        cache, kv_positions = _cache_write(cache, {"c": c, "k_rope": k_rope},
                                           cache_index, S)
        c, k_rope = cache["c"], cache["k_rope"]
        kv_segment_ids = None
    T = c.shape[1]
    with jax.named_scope("expand"):
        kv = jnp.einsum("btr,rh->bth", c, p["wkv_b"]).reshape(B, T, H, -1)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, k_rope.shape[-1]))],
        axis=-1)
    k = shard_act(k, "kv_heads")
    v = shard_act(kv[..., dn:], "kv_heads")
    attn_fn = _ATTN_IMPL or blockwise_attention
    out = attn_fn(
        q, k, v, causal=True, window=window,
        logit_softcap=cfg.attn_logit_softcap, q_positions=positions,
        kv_positions=kv_positions, q_segment_ids=segment_ids,
        kv_segment_ids=kv_segment_ids, block_kv=block_kv,
        scale=mla_softmax_scale(cfg))
    out = shard_act(out, "q_heads")
    out = jnp.einsum("bsq,qd->bsd", out.reshape(B, S, H * dv), p["wo"])
    return out, cache


# --------------------------------------------------------------------------
# MLP (dense FFN)
# --------------------------------------------------------------------------
def mlp_params(key, cfg, dtype, prefix_shape=(), d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    gated = cfg.activation in ("swiglu", "geglu")
    p = {
        "w_up": dense_init(ks[1], prefix_shape + (d, f), dtype),
        "w_down": dense_init(ks[2], prefix_shape + (f, d), dtype, scale=1.0 / max(1, cfg.num_layers) ** 0.5),
    }
    if gated:
        p["w_gate"] = dense_init(ks[0], prefix_shape + (d, f), dtype)
    return p


def mlp_apply(cfg, p, x):
    act = activation_fn(cfg.activation)
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if "w_gate" in p:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = act(gate) * up
    else:
        h = act(up)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])
