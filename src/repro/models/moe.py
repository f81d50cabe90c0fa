"""Mixture-of-Experts FFN: top-k token-choice routing with capacity.

Dispatch is scatter-based (no (tokens, E, C) one-hot tensors): per group we
compute each token's expert id and its position-in-expert via a cumulative
sum, then scatter tokens into an (E, C, d) buffer and gather results back.
Groups are device-local under data-parallel sharding of the batch dim, so the
dispatch never crosses shards in GSPMD.

Expert-parallel-over-data mode (``set_ep_axis`` — used inside the manual
shard_map engine): expert weights stay SHARDED on the FSDP axis and are
never gathered; the dispatch buffers travel to the experts via
``lax.all_to_all`` instead (weight-stationary MoE).  This replaces the
per-layer FSDP gather of the full expert bank (O(params)) with two
activation-sized all-to-alls (O(tokens·d)) — the decisive traffic reduction
for large-expert-count models (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from repro.models.layers import activation_fn, dense_init

_EP = threading.local()


def set_ep_axis(axis_name):
    """Trace-time hook: inside shard_map, route moe_apply through the
    expert-parallel (weight-stationary, all_to_all) path over this axis."""
    _EP.axis = axis_name


def get_ep_axis():
    return getattr(_EP, "axis", None)


def moe_params(key, cfg, dtype, prefix_shape=()):
    E, d, f = cfg.num_experts, cfg.d_model, cfg.resolved_moe_d_ff
    ks = jax.random.split(key, 4)
    gated = cfg.activation in ("swiglu", "geglu")
    p = {
        "router": dense_init(ks[0], prefix_shape + (d, E), dtype),
        "w_up": dense_init(ks[1], prefix_shape + (E, d, f), dtype),
        "w_down": dense_init(ks[2], prefix_shape + (E, f, d), dtype,
                             scale=1.0 / max(1, cfg.num_layers) ** 0.5),
    }
    if gated:
        p["w_gate"] = dense_init(ks[3], prefix_shape + (E, d, f), dtype)
    return p


def _dispatch_group(x, expert_idx, gate_w, num_experts, capacity,
                    prior_counts=None):
    """x: (N, d); expert_idx, gate_w: (N,). Returns (N, d) expert output terms.

    Tokens beyond an expert's capacity are dropped (standard token-choice
    semantics); the scatter target has one extra overflow slot per expert.

    prior_counts: (E,) tokens already routed to each expert by *earlier*
    forward calls over the same sequence (decode: the prefill's counts).
    The drop decision uses the running position (prior + within-call
    cumsum) so incremental decode reproduces the full forward's drops;
    the buffer slot stays the within-call position.
    """
    N, d = x.shape
    onehot = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.int32)  # (N, E)
    within = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, expert_idx[:, None], 1)[:, 0]
    pos = within if prior_counts is None else within + prior_counts[expert_idx]
    keep = pos < capacity
    slot = jnp.where(keep, within, capacity)  # overflow slot = capacity
    buf = jnp.zeros((num_experts, capacity + 1, d), x.dtype)
    buf = buf.at[expert_idx, slot].add(jnp.where(keep[:, None], x, 0.0))
    return buf, (slot, keep)


def _combine_group(buf_out, expert_idx, slot_keep, gate_w):
    slot, keep = slot_keep
    out = buf_out[expert_idx, slot]
    return out * (gate_w * keep)[:, None]


def _router(cfg, p, toks):
    """toks: (..., N, d) -> (top_w, top_i, aux)."""
    E, k = cfg.num_experts, cfg.experts_per_token
    logits = jnp.einsum("...nd,de->...ne", toks, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e mean_prob_e * frac_routed_e
    red = tuple(range(probs.ndim - 1))
    frac = jnp.mean(jax.nn.one_hot(top_i, E, dtype=jnp.float32),
                    axis=red + (probs.ndim - 1,))
    aux = E * jnp.sum(jnp.mean(probs, axis=red) * frac) * cfg.router_aux_coef
    return top_w, top_i, aux


def _make_expert_ffn(cfg, p):
    act = activation_fn(cfg.activation)
    gated = "w_gate" in p

    def expert_ffn(buf):  # buf: (E_local, C, d) against local expert bank
        up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
        if gated:
            gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
            h = act(gate) * up
        else:
            h = act(up)
        return jnp.einsum("ecf,efd->ecd", h, p["w_down"])

    return expert_ffn


def moe_apply(cfg, p, x, *, capacity_factor: float = 0.0, groups: int = 0,
              router_counts=None, capacity_len: int = 0):
    """x: (B, S, d) -> (B, S, d), plus the router load-balance aux loss.

    groups: number of dispatch groups (0 = one group per batch row). Each
    group dispatches independently with capacity ceil(G_tokens/E * cf * k).

    router_counts / capacity_len (incremental decode): ``router_counts``
    is the (B, k, E) int32 running token-per-expert tally from earlier
    calls over the same sequences, and ``capacity_len`` the fixed
    reference length (the KV-cache budget) the capacity is computed from
    — both together make capacity drops *causally consistent*, so
    prefill + decode reproduces the full forward exactly (for the
    default per-batch-row grouping; multi-row groups are rejected, see
    below).  When provided, groups must be batch rows (so the tally
    survives across calls of different lengths) and the return gains a
    third element, the updated counts.
    """
    cf = capacity_factor or cfg.moe_capacity_factor
    ep_axis = get_ep_axis()
    if ep_axis is not None:
        if router_counts is not None:
            # EP dispatch has no decode tally; refusing beats silently
            # returning a 2-tuple where the caller expects 3
            raise ValueError("incremental decode (router_counts) is not "
                             "supported on the expert-parallel path")
        return _moe_apply_ep(cfg, p, x, ep_axis, cf)

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    if router_counts is not None and groups not in (0, B):
        # multi-row dispatch groups regroup tokens differently at each
        # call length, so a per-row tally cannot reproduce their drops
        raise ValueError(
            f"incremental decode (router_counts) requires per-batch-row "
            f"dispatch groups; got groups={groups} for batch {B}")
    G = B if router_counts is not None else (groups or B)
    toks = x.reshape(G, (B * S) // G, d)
    Ng = toks.shape[1]
    ref_len = capacity_len if router_counts is not None else Ng
    capacity = max(1, int(-(-ref_len * cf * k // E)))

    top_w, top_i, aux = _router(cfg, p, toks)
    expert_ffn = _make_expert_ffn(cfg, p)

    out = jnp.zeros_like(toks)
    new_counts = []
    for slot_k in range(k):
        e_idx = top_i[..., slot_k]  # (G, Ng)
        g_w = top_w[..., slot_k].astype(x.dtype)
        if router_counts is None:
            buf, slot_keep = jax.vmap(
                lambda t, e: _dispatch_group(t, e, None, E, capacity)
            )(toks, e_idx)
        else:
            prior = router_counts[:, slot_k, :]  # (B, E)
            buf, slot_keep = jax.vmap(
                lambda t, e, pc: _dispatch_group(t, e, None, E, capacity, pc)
            )(toks, e_idx, prior)
            routed = jax.nn.one_hot(e_idx, E, dtype=prior.dtype).sum(axis=1)
            new_counts.append(prior + routed)
        buf_out = jax.vmap(expert_ffn)(buf)
        out = out + jax.vmap(_combine_group)(buf_out, e_idx, slot_keep, g_w)
    out = out.reshape(B, S, d)
    if router_counts is not None:
        return out, aux, jnp.stack(new_counts, axis=1)  # (B, k, E)
    return out, aux


def _moe_apply_ep(cfg, p, x, axis_name, cf):
    """Expert-parallel over the FSDP axis: p['w_*'] hold the E_local slice,
    p['router'] is full.  Tokens are dispatched into a global (E, C, d)
    buffer, all_to_all'd so each device receives all tokens for ITS
    experts, processed against the local (stationary) weights, then
    all_to_all'd back and combined."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    n = jax.lax.axis_size(axis_name)
    E_local = p["w_up"].shape[0]
    assert E_local * n == E, (E_local, n, E)

    toks = x.reshape(B * S, d)
    N = toks.shape[0]
    capacity = max(1, int(-(-N * cf * k // E)))

    top_w, top_i, aux = _router(cfg, p, toks)
    expert_ffn = _make_expert_ffn(cfg, p)

    out = jnp.zeros_like(toks)
    for slot_k in range(k):
        e_idx = top_i[..., slot_k]
        g_w = top_w[..., slot_k].astype(x.dtype)
        buf, slot_keep = _dispatch_group(toks, e_idx, None, E, capacity)
        # -> (E_local, n*(C+1), d): every device's contributions for my experts
        buf = jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                 concat_axis=1, tiled=True)
        buf_out = expert_ffn(buf)
        # back: (E, C+1, d) with my tokens' results
        buf_out = jax.lax.all_to_all(buf_out, axis_name, split_axis=1,
                                     concat_axis=0, tiled=True)
        out = out + _combine_group(buf_out, e_idx, slot_keep, g_w)
    return out.reshape(B, S, d), aux
