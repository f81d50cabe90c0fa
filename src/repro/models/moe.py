"""Mixture-of-Experts FFN: top-k token-choice routing, with capacity or
dropless over a held share of the experts.

Two expert layers share the router.  ``moe_apply_grouped`` (configs with
``moe_capacity_factor`` 0 or a held share, ``ModelConfig.moe_grouped``):
the router scores all ``num_experts``, and the layer computes only the
part of the result its ``experts_held`` experts give.  The (token,
expert) pairs are sorted by held expert and the expert matmuls are
grouped matmuls over them (:func:`grouped_matmul`: the megablox Pallas
kernels on a TPU, ``jax.lax.ragged_dot`` elsewhere), one dispatch for all
k slots, no capacity.  ``moe_apply`` below is the capacity dispatch.

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
for large-expert-count models.  What the chip measures of the expert layers
is in ``PERF.md``.
"""
from __future__ import annotations

import functools
import importlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import activation_fn, dense_init

# the kernels' module (the package's ``gmm`` is the function, with its VJP)
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

_EP = threading.local()


def set_ep_axis(axis_name):
    """Trace-time hook: inside shard_map, route moe_apply through the
    expert-parallel (weight-stationary, all_to_all) path over this axis."""
    _EP.axis = axis_name


def get_ep_axis():
    return getattr(_EP, "axis", None)


def moe_params(key, cfg, dtype, prefix_shape=()):
    """The router scores all ``num_experts``; the expert weights are the
    held share's (``experts_held``, by default all)."""
    E, d, f = cfg.resolved_experts_held, cfg.d_model, cfg.resolved_moe_d_ff
    ks = jax.random.split(key, 4)
    gated = cfg.activation in ("swiglu", "geglu")
    p = {
        "router": dense_init(ks[0], prefix_shape + (d, cfg.num_experts), dtype),
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


def _router_probs(cfg, p, toks):
    """toks: (..., N, d) -> (probs over all experts, top_w, top_i): softmax
    routing, greedy top-k, renormalized where the config says so."""
    logits = jnp.einsum("...nd,de->...ne", toks, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.moe_renormalize:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return probs, top_w, top_i


def _switch_balance_loss(cfg, probs, top_i):
    """Switch-style load-balance loss: E * sum_e mean_prob_e *
    frac_routed_e, over every token."""
    E = cfg.num_experts
    red = tuple(range(probs.ndim - 1))
    frac = jnp.mean(jax.nn.one_hot(top_i, E, dtype=jnp.float32),
                    axis=red + (probs.ndim - 1,))
    return E * jnp.sum(jnp.mean(probs, axis=red) * frac) * cfg.router_aux_coef


def _router(cfg, p, toks):
    """toks: (..., N, d) -> (top_w, top_i, aux)."""
    probs, top_w, top_i = _router_probs(cfg, p, toks)
    return top_w, top_i, _switch_balance_loss(cfg, probs, top_i)


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


# ---------------------------------------------------------------------------
# dropless, over a held share of the experts
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _permute(x, perm, inv):
    """Rows of x in the order ``perm`` (``inv`` its inverse).  The transpose
    is the inverse permutation, a gather, where autodiff of ``x[perm]``
    would scatter-add."""
    return x[perm]


def _permute_fwd(x, perm, inv):
    return x[perm], (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def sequence_balance_loss(cfg, probs, top_i, segment_ids, B: int, S: int):
    """DeepSeek-V2's sequence-wise balance loss, each sample (segment id;
    padding, id < 0, left out) weighted by its tokens T_s:

        alpha * sum_s T_s * sum_i f_si P_si,
        f_si = E / (k T_s) * (tokens of s routing to i),
        P_si = mean over s's tokens of the router probability of i.

    Summed, not averaged: the loss adds it to the summed cross-entropy
    before the step's token mean, so each sample counts by its tokens
    however the samples are packed.  probs: (N, E) over all experts;
    segment ids below S (one per sample packed into the row); without
    them each row is one sample."""
    E, k, N = cfg.num_experts, cfg.experts_per_token, B * S
    seg = (jnp.zeros((B, S), jnp.int32) if segment_ids is None
           else segment_ids.reshape(B, S))
    ids = (jnp.arange(B)[:, None] * S + jnp.clip(seg, 0, S - 1)).reshape(-1)
    real = (seg >= 0).reshape(-1).astype(jnp.float32)
    chosen = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32), axis=1)
    count = jax.ops.segment_sum(chosen * real[:, None], ids, num_segments=N)
    psum = jax.ops.segment_sum(probs * real[:, None], ids, num_segments=N)
    tokens = jax.ops.segment_sum(real, ids, num_segments=N)
    per = jnp.sum(count * psum, -1) * (E / k) / jnp.maximum(tokens, 1.0)
    return cfg.router_aux_coef * jnp.sum(per)


def _kernel():
    """How the grouped matmuls run: None for ``jax.lax.ragged_dot`` (off a
    TPU), else the ``interpret`` flag of the megablox Pallas kernels
    (compiled on a TPU).  A Pallas call keeps the ``op_name`` of its scope
    on the chip, where XLA's own kernel for ``ragged_dot`` drops it."""
    return False if jax.default_backend() == "tpu" else None


def _tiling(m: int, k: int, n: int):
    """Kernel tiles (rows, contraction, columns), or None where the rows
    do not split into tiles: 256 rows; 512 of a dimension 512 divides,
    else the whole of it (1,408 = 11 x 128).  At DeepSeek-V2-Lite's
    widths in float32 every tile set stays under 13 MB of the 16 MB of
    scoped VMEM."""
    tm = 256 if m % 256 == 0 else m if m < 256 and m % 8 == 0 else None
    return None if tm is None else (
        tm, 512 if k % 512 == 0 else k, 512 if n % 512 == 0 else n)


def _whole(f, *args):
    """f(*args), inside a shard_map over the mesh axes the context leaves
    to the partitioner, with the operands and the result whole on them:
    a Pallas kernel cannot be partitioned automatically.  Outside a mesh
    context, f(*args) (one device)."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = set(mesh.axis_names) - set(mesh.manual_axes)
    if mesh.empty or not auto:
        return f(*args)
    return jax.shard_map(f, in_specs=(P(),) * len(args), out_specs=P(),
                         axis_names=auto, check_vma=False)(*args)


def _gmm(x, w, sizes, transpose_rhs=False):
    """x (m, k), rows sorted by group, times their group's w (G, k, n), or
    (G, n, k) transposed: (m, n) float32.  Rows past the groups are left
    undefined (the chip's kernel does not write them)."""
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tiling = _tiling(x.shape[0], x.shape[1], n)
    interpret = _kernel()
    if interpret is None or tiling is None:
        return jax.lax.ragged_dot(x, w.swapaxes(1, 2) if transpose_rhs
                                  else w, sizes,
                                  preferred_element_type=jnp.float32)
    return _whole(functools.partial(
        megablox.gmm, preferred_element_type=jnp.float32, tiling=tiling,
        transpose_rhs=transpose_rhs, interpret=interpret), x, w, sizes)


def _tgmm(x, g, sizes):
    """Each group's sum over its rows of x_r^T g_r: x (m, k), g (m, n) ->
    (G, k, n) float32, from the groups' rows alone."""
    tiling = _tiling(*x.shape, g.shape[1])
    interpret = _kernel()
    if interpret is None or tiling is None:
        w = jnp.zeros((sizes.shape[0], x.shape[1], g.shape[1]), jnp.float32)
        _, vjp = jax.vjp(lambda w: jax.lax.ragged_dot(
            x, w, sizes, preferred_element_type=jnp.float32), w)
        return vjp(g.astype(jnp.float32))[0]
    return _whole(functools.partial(
        megablox.tgmm, preferred_element_type=jnp.float32, tiling=tiling,
        interpret=interpret), x.T, g, sizes)


def _groups_only(y, sizes):
    """y with its rows past the groups zeroed."""
    rows = jnp.arange(y.shape[0]) < jnp.sum(sizes)
    return jnp.where(rows[:, None], y, jnp.zeros((), y.dtype))


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """Rows of x sorted by group times their group's matrix: x (m, k), w
    (G, k, n), ``sizes[g]`` rows for group g -> (m, n) in x's dtype.  Rows
    past the groups read zero, in the result and in x's gradient; w's
    gradient reads the groups' rows alone."""
    return _groups_only(_gmm(x, w, sizes), sizes).astype(x.dtype)


def _grouped_matmul_fwd(x, w, sizes):
    return grouped_matmul(x, w, sizes), (x, w, sizes)


def _grouped_matmul_bwd(res, g):
    # g's rows past the groups are zero: the combine weights those pairs
    # by 0, and the layer's inner products come out of this function
    x, w, sizes = res
    dx = _groups_only(_gmm(g, w, sizes, transpose_rhs=True), sizes)
    return dx.astype(x.dtype), _tgmm(x, g, sizes).astype(w.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _grouped_ffn(cfg, p, xs, sizes):
    """The held experts' FFN over rows sorted by expert: ``sizes[e]`` rows
    for held expert e, then rows that no held expert computes (zero)."""
    act = activation_fn(cfg.activation)
    up = grouped_matmul(xs, p["w_up"], sizes)
    if "w_gate" in p:
        h = act(grouped_matmul(xs, p["w_gate"], sizes)) * up
    else:
        h = act(up)
    return grouped_matmul(h, p["w_down"], sizes)


def moe_apply_grouped(cfg, p, x, segment_ids=None):
    """x: (B, S, d) -> (out, aux, pairs held).

    The router scores all ``num_experts``; the layer holds the first
    ``experts_held`` (``p['w_*']`` carry that many) and returns their part
    of the result, sum over the held experts among each token's top-k of
    p_e * FFN_e(x).  The N*k (token, expert) pairs are sorted by held
    expert, the pairs of experts held elsewhere last, and one grouped
    matmul per projection runs over them: its work follows the pairs
    routed here.  Dropless; a ``moe_capacity_factor`` above 0 drops the
    pairs past ceil(N * cf * k / E) of each expert, in token order.
    ``pairs held``: the pairs computed here (float32)."""
    if get_ep_axis() is not None:
        raise ValueError("the held-share expert layer has no expert "
                         "exchange between devices (moe_ep='data')")
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    held, N = cfg.resolved_experts_held, B * S
    toks = x.reshape(N, d)
    with jax.named_scope("router"):
        probs, top_w, top_i = _router_probs(cfg, p, toks)
        if cfg.moe_aux_per_sequence:
            aux = sequence_balance_loss(cfg, probs, top_i, segment_ids, B, S)
        else:
            aux = _switch_balance_loss(cfg, probs, top_i)
    with jax.named_scope("dispatch"):
        e = top_i.reshape(-1)  # pairs, token-major: pair n*k + j
        here = e < held
        key = jnp.where(here, e, held)  # pairs held elsewhere sort last
        order = jnp.argsort(key, stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * k, dtype=order.dtype))
        sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        if cfg.moe_capacity_factor:
            capacity = max(1, int(-(-N * cfg.moe_capacity_factor * k // E)))
            starts = jnp.cumsum(sizes) - sizes
            rank = jnp.arange(N * k) - jnp.append(starts, 0)[key[order]]
            here &= (rank < capacity)[inv]
        w = jnp.where(here, top_w.reshape(-1), 0.0).astype(x.dtype)
        xs = _permute(jnp.repeat(toks, k, axis=0), order, inv)
    with jax.named_scope("experts"):
        ys = _grouped_ffn(cfg, p, xs, sizes)
    with jax.named_scope("combine"):
        y = _permute(ys, inv, order).reshape(N, k, d)
        out = jnp.einsum("nk,nkd->nd", w.reshape(N, k), y)
    return out.reshape(B, S, d), aux, jnp.sum(here).astype(jnp.float32)
