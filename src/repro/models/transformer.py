"""Model assembly for every architecture family in the pool.

Uniform functional API (used by both engines, the serve path and the tests):

    init_params(cfg, key, dtype)            -> params pytree
    apply(cfg, params, batch, ...)          -> (logits, aux_loss, new_caches)
    loss(cfg, params, batch, ...)           -> (scalar, metrics dict)
    init_cache(cfg, batch, max_len, dtype)  -> decode caches pytree

``batch`` is a dict: tokens (B,S) int32, positions (B,S), segment_ids (B,S),
targets (B,S), loss_mask (B,S) float; family extras: ``encoder_embeds``
(audio: precomputed frame embeddings, the stub frontend), ``vision_embeds``
(early-fusion VLM: projected patch embeddings written over the first
``frontend_tokens`` positions).

Layer trunks are ``lax.scan`` over stacked layer params (fast compiles at
40-64 layers).  MoE archs scan over "super-layers" of ``moe_period`` layers
((period-1) dense + 1 MoE), so dense and MoE layers can carry different
parameter structures while the scan stays uniform; ``first_dense_layers``
leading dense layers (DeepSeek-V2) are a stack of their own
(``layers/lead``), run before that scan.  Attention is grouped-query, or
multi-head latent attention where the config sets ``kv_lora_rank``.

``pxform`` is the FSDP hook: a transform applied to parameter subtrees at
materialization points (per-layer inside the scan bodies, or once at the
top for global leaves).  ``prefetch`` (training only) switches the layer
scans to the software-pipelined ``odc.prefetch_scan``: the transform is
applied to layer l+1's slice during layer l's compute — the
``schedule='overlap'`` double-buffered gather/scatter discipline.

``grad_acc`` (training, ``schedule='minibatch'``) folds the running
microbatch gradient sum into the backward of the layer scans
(:func:`_fold_scan`): each layer adds its weight gradient into its slice of
the sum, in place, in the scan iteration that computes it, where XLA fuses
the add into the weight-gradient matmul.  Without it the scan's transpose
writes a fresh zero-filled gradient stack, and the sum reads that stack and
the running sum in a pass of its own after the scan.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig


# ===========================================================================
# parameter init
# ===========================================================================
def _attn_params(key, cfg, dtype, prefix_shape=()):
    if cfg.is_mla:
        return L.mla_params(key, cfg, dtype, prefix_shape)
    return L.attn_params(key, cfg, dtype, prefix_shape)


def _dense_block_params(key, cfg, dtype, prefix_shape=()):
    ks = jax.random.split(key, 2)
    return {
        "attn_norm": jnp.zeros(prefix_shape + (cfg.d_model,), dtype),
        "attn": _attn_params(ks[0], cfg, dtype, prefix_shape),
        "mlp_norm": jnp.zeros(prefix_shape + (cfg.d_model,), dtype),
        "mlp": L.mlp_params(ks[1], cfg, dtype, prefix_shape),
    }


def _moe_block_params(key, cfg, dtype, prefix_shape=()):
    ks = jax.random.split(key, 3)
    p = {
        "attn_norm": jnp.zeros(prefix_shape + (cfg.d_model,), dtype),
        "attn": _attn_params(ks[0], cfg, dtype, prefix_shape),
        "mlp_norm": jnp.zeros(prefix_shape + (cfg.d_model,), dtype),
        "moe": moe_mod.moe_params(ks[1], cfg, dtype, prefix_shape),
    }
    if cfg.moe_shared_expert:
        p["shared_mlp"] = L.mlp_params(ks[2], cfg, dtype, prefix_shape,
                                       d_ff=cfg.resolved_shared_d_ff)
    return p


def _mamba_block_params(key, cfg, dtype, prefix_shape=()):
    return {
        "norm": jnp.zeros(prefix_shape + (cfg.d_model,), dtype),
        "mamba": ssm_mod.mamba2_params(key, cfg, dtype, prefix_shape),
    }


def _encdec_dec_params(key, cfg, dtype, prefix_shape=()):
    ks = jax.random.split(key, 3)
    p = _dense_block_params(ks[0], cfg, dtype, prefix_shape)
    p["cross_norm"] = jnp.zeros(prefix_shape + (cfg.d_model,), dtype)
    p["cross"] = L.attn_params(ks[1], cfg, dtype, prefix_shape)
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.float32):
    keys = jax.random.split(key, 8)
    params = {"embed": L.embed_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[1], (cfg.d_model, cfg.vocab_size), dtype)
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)

    fam = cfg.family
    if fam == "ssm":
        params["layers"] = _mamba_block_params(
            keys[2], cfg, dtype, prefix_shape=(cfg.num_layers,)
        )
    elif fam == "hybrid":
        P = cfg.hybrid_attn_period
        n_super, tail = cfg.num_layers // P, cfg.num_layers % P
        params["mamba"] = _mamba_block_params(keys[2], cfg, dtype, (n_super, P))
        if tail:
            params["mamba_tail"] = _mamba_block_params(keys[3], cfg, dtype, (tail,))
        params["shared_attn"] = _dense_block_params(keys[4], cfg, dtype)
    elif fam == "audio":
        params["enc_layers"] = _dense_block_params(
            keys[2], cfg, dtype, (cfg.num_encoder_layers,)
        )
        params["enc_final_norm"] = jnp.zeros((cfg.d_model,), dtype)
        params["dec_layers"] = _encdec_dec_params(keys[3], cfg, dtype, (cfg.num_layers,))
    elif cfg.num_experts:
        P = cfg.moe_period
        n_super = cfg.num_moe_layers
        blocks = {"moe": _moe_block_params(keys[2], cfg, dtype, (n_super,))}
        if P > 1:
            blocks["dense"] = _dense_block_params(keys[3], cfg, dtype, (n_super, P - 1))
        if cfg.first_dense_layers:
            blocks["lead"] = _dense_block_params(
                keys[4], cfg, dtype, (cfg.first_dense_layers,))
        params["layers"] = blocks
    else:  # dense / vlm
        params["layers"] = _dense_block_params(keys[2], cfg, dtype, (cfg.num_layers,))
    return params


# ===========================================================================
# layer application
# ===========================================================================
def _attention(cfg, p, h, **kw):
    """The layer's self-attention: latent (MLA) or grouped-query."""
    if cfg.is_mla:
        return L.mla_apply(cfg, p, h, **kw)
    return L.attn_apply(cfg, p, h, **kw)


def _apply_dense_block(cfg, lp, x, *, window, positions, segment_ids, cache,
                       cache_index, block_kv, causal=True):
    with jax.named_scope("attention"):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        a, cache = _attention(
            cfg, lp["attn"], h, window=window, positions=positions,
            segment_ids=segment_ids, cache=cache, cache_index=cache_index,
            block_kv=block_kv,
        )
    x = x + a
    with jax.named_scope("mlp"):
        h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return x, cache


def _apply_moe_block(cfg, lp, x, *, window, positions, segment_ids, cache,
                     cache_index, block_kv, moe_groups):
    """Returns (x, cache, aux, pairs): ``pairs`` the (token, expert) pairs
    the held experts computed (``moe_apply_grouped``; 0 for the capacity
    dispatch)."""
    # the decode cache carries the router's per-expert usage tally next to
    # the KV buffers: capacity drops depend on how many earlier tokens hit
    # each expert, state an incremental decode can't otherwise see
    router_counts = None
    attn_cache = cache
    if cache is not None and "router_counts" in cache:
        router_counts = cache["router_counts"]
        attn_cache = {k: v for k, v in cache.items() if k != "router_counts"}
    with jax.named_scope("attention"):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        a, attn_cache = _attention(
            cfg, lp["attn"], h, window=window, positions=positions,
            segment_ids=segment_ids, cache=attn_cache,
            cache_index=cache_index, block_kv=block_kv,
        )
    x = x + a
    pairs = jnp.float32(0.0)
    with jax.named_scope("moe"):
        h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if cfg.moe_grouped:
            ffn, aux, pairs = moe_mod.moe_apply_grouped(
                cfg, lp["moe"], h, segment_ids=segment_ids)
        elif router_counts is not None:
            ffn, aux, router_counts = moe_mod.moe_apply(
                cfg, lp["moe"], h, groups=moe_groups,
                router_counts=router_counts,
                capacity_len=attn_cache["k"].shape[1])
        else:
            ffn, aux = moe_mod.moe_apply(cfg, lp["moe"], h,
                                         groups=moe_groups)
    if "shared_mlp" in lp:
        with jax.named_scope("mlp"):
            ffn = ffn + L.mlp_apply(cfg, lp["shared_mlp"], h)
    x = x + ffn
    new_cache = attn_cache
    if router_counts is not None:
        new_cache = dict(attn_cache)
        new_cache["router_counts"] = router_counts
    return x, new_cache, aux, pairs


def _apply_mamba_block(cfg, lp, x, *, cache):
    h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
    out, cache = ssm_mod.mamba2_apply(cfg, lp["mamba"], h, cache=cache)
    return x + out, cache


# ===========================================================================
# per-family forward
# ===========================================================================
def _window_schedule(cfg):
    """Per-layer sliding-window values (0 = global)."""
    return jnp.asarray(
        [cfg.sliding_window if cfg.layer_kind(i) == "local" else 0
         for i in range(cfg.num_layers)],
        jnp.int32,
    )


def _static_window(cfg):
    """The single static window when every layer shares one (the common
    all-global or all-local case), else None.  A static window is hoisted
    out of the layer scan's carries, which lets window-specialized
    attention impls engage (the Pallas kernel, the cp ring — both need a
    static window; a traced per-layer schedule forces their jnp
    fallbacks)."""
    ws = {cfg.sliding_window if cfg.layer_kind(i) == "local" else 0
          for i in range(cfg.num_layers)}
    return ws.pop() if len(ws) == 1 else None


@jax.named_scope("lm_head")
def _logits(cfg, params, x):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    if cfg.final_logit_softcap > 0:
        logits = L.softcap(logits, cfg.final_logit_softcap)
    return logits


@jax.named_scope("embed")
def _embed(cfg, params, batch):
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.frontend != "none" and cfg.frontend_tokens and "vision_embeds" in batch:
        n = batch["vision_embeds"].shape[1]
        x = jax.lax.dynamic_update_slice_in_dim(
            x, batch["vision_embeds"].astype(x.dtype), 0, axis=1
        )
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fold_scan(step, carry, stack, acc, xs):
    """``jax.lax.scan`` of ``step(carry, layer slice, xs slice) -> carry``
    over ``stack``, checkpointed by layer; its backward adds each layer's
    parameter cotangent into that layer's slice of ``acc``, a running
    gradient sum shaped as ``stack``, in place.  The cotangent of
    ``stack`` leaves as ``acc`` plus the gradient; ``acc`` gets none."""
    return jax.lax.scan(lambda c, s: (step(c, *s), None), carry,
                        (stack, xs))[0]


def _fold_scan_fwd(step, carry, stack, acc, xs):
    # each layer's input is the residual, as the checkpoint keeps it
    out, carries = jax.lax.scan(lambda c, s: (step(c, *s), c), carry,
                                (stack, xs))
    return out, (carries, stack, acc, xs)


def _fold_scan_bwd(step, res, ct):
    carries, stack, acc, xs = res
    remat = jax.checkpoint(step)  # the recompute, named as the checkpoint's

    def body(state, s):
        ct, acc = state
        c, lp, x, i = s
        _, vjp = jax.vjp(lambda c, lp: remat(c, lp, x), c, lp)
        ct, g = vjp(ct)
        # acc[i] + g, the operand order of the sum it replaces.  XLA fuses a
        # one-index scatter-add into the weight-gradient matmul, reading and
        # writing the slice in place; a dynamic-slice + dynamic-update-slice
        # leaves the slice's read as a copy of its own
        return (ct, jax.tree.map(lambda a, g: a.at[i].add(g), acc, g)), None

    n = jax.tree.leaves(stack)[0].shape[0]
    (ct, acc), _ = jax.lax.scan(body, (ct, acc),
                                (carries, stack, xs, jnp.arange(n)),
                                reverse=True)
    return ct, acc, None, None


_fold_scan.defvjp(_fold_scan_fwd, _fold_scan_bwd)


def _folds(stack) -> bool:
    """Whether the scan over ``stack`` folds the gradient sum: only a loop
    of two or more layers.  XLA removes a one-trip loop, and the sum after
    it then fuses with the weight gradient already."""
    return jax.tree.leaves(stack)[0].shape[0] >= 2


def grad_fold_mask(cfg: ModelConfig, params):
    """True on the leaves whose gradient :func:`loss` returns with
    ``grad_acc`` already added: the stacks of the dense and expert trunks
    that :func:`_folds`.  The other leaves' sum is left to the caller."""
    def fill(tree, flag):
        return jax.tree.map(lambda _: flag, tree)

    mask = fill(params, False)
    if cfg.family in ("ssm", "hybrid", "audio"):
        return mask
    layers = params["layers"]
    if cfg.num_experts:  # the lead stack, then the super-layers' one scan
        blocks = {k: v for k, v in layers.items() if k != "lead"}
        mask["layers"] = {k: fill(v, _folds(v if k == "lead" else blocks))
                          for k, v in layers.items()}
    else:
        mask["layers"] = fill(layers, _folds(layers))
    return mask


def _prefetch_scan(*args, **kwargs):
    # lazy: repro.core's __init__ imports this module, so a top-level
    # import of repro.core.odc would be circular
    from repro.core.odc import prefetch_scan
    return prefetch_scan(*args, **kwargs)


def _forward_dense(cfg, params, batch, caches, cache_index, remat, block_kv,
                   pxform, prefetch=None, grad_acc=None):
    x = _embed(cfg, params, batch)
    sw = _static_window(cfg)  # hoisted when uniform across layers
    windows = None if sw is not None else _window_schedule(cfg)
    x, new_caches = _dense_trunk(
        cfg, params["layers"], x, batch, caches, cache_index, remat,
        block_kv, pxform, prefetch, sw, windows,
        acc=None if grad_acc is None else grad_acc["layers"])
    return x, jnp.float32(0.0), new_caches


def _dense_trunk(cfg, stack, x, batch, caches, cache_index, remat, block_kv,
                 pxform, prefetch, sw, windows, acc=None):
    """A stack of dense blocks, scanned; ``sw`` the static window of every
    layer, else ``windows`` the per-layer schedule; ``acc`` the stack's
    gradient sum to fold in where it :func:`_folds`, or None.  Returns
    (x, caches)."""
    positions = batch.get("positions")
    segment_ids = batch.get("segment_ids")

    if prefetch is not None:
        def pbody(x, scanned):
            if sw is not None:  # lp materialized one slot ahead
                (lp,), window = scanned, sw
            else:
                lp, window = scanned
            return _apply_dense_block(
                cfg, lp, x, window=window, positions=positions,
                segment_ids=segment_ids, cache=None,
                cache_index=cache_index, block_kv=block_kv,
            )

        extras = () if sw is not None else (windows,)
        x, _ = _prefetch_scan(pbody, x, stack, extras,
                              prefetch=prefetch, remat=remat)
        return x, None

    def body(x, scanned):
        lp, *rest = scanned
        window = sw if sw is not None else rest.pop(0)
        cache = rest.pop(0) if caches is not None else None
        x, cache = _apply_dense_block(
            cfg, pxform(lp), x, window=window, positions=positions,
            segment_ids=segment_ids, cache=cache, cache_index=cache_index,
            block_kv=block_kv,
        )
        return x, cache

    if acc is not None and _folds(stack):  # training: no caches
        return _fold_scan(
            lambda x, lp, w: body(x, (lp,) if sw is not None else (lp, w))[0],
            x, stack, acc, windows), None
    if remat:
        body = jax.checkpoint(body)
    xs = (stack,)
    if sw is None:
        xs += (windows,)
    if caches is not None:
        xs += (caches,)
    return jax.lax.scan(body, x, xs)


def _forward_moe(cfg, params, batch, caches, cache_index, remat, block_kv,
                 moe_groups, pxform, prefetch=None, grad_acc=None):
    """Returns (x, aux, caches, pairs held)."""
    x = _embed(cfg, params, batch)
    positions = batch.get("positions")
    segment_ids = batch.get("segment_ids")
    P = cfg.moe_period
    blocks = dict(params["layers"])
    lead = blocks.pop("lead", None)
    acc = None if grad_acc is None else dict(grad_acc["layers"])
    lead_acc = None if acc is None else acc.pop("lead", None)
    lead_cache = None
    if caches is not None and "lead" in caches:
        caches = dict(caches)
        lead_cache = caches.pop("lead")
    if lead is not None:
        x, lead_cache = _dense_trunk(
            cfg, lead, x, batch, lead_cache, cache_index, remat, block_kv,
            pxform, prefetch, 0, None, acc=lead_acc)
    zero = jnp.float32(0.0)

    if prefetch is not None:
        def pbody(carry, scanned):
            x, aux, pairs = carry
            (lp,) = scanned  # whole super-layer slice, pre-materialized
            if P > 1:
                for j in range(P - 1):
                    sub = jax.tree.map(lambda a: a[j], lp["dense"])
                    x, _ = _apply_dense_block(
                        cfg, sub, x, window=0, positions=positions,
                        segment_ids=segment_ids, cache=None,
                        cache_index=cache_index, block_kv=block_kv,
                    )
            x, _, aux_l, pairs_l = _apply_moe_block(
                cfg, lp["moe"], x, window=0, positions=positions,
                segment_ids=segment_ids, cache=None,
                cache_index=cache_index, block_kv=block_kv,
                moe_groups=moe_groups,
            )
            return (x, aux + aux_l, pairs + pairs_l), None

        (x, aux, pairs), _ = _prefetch_scan(
            pbody, (x, zero, zero), blocks, (),
            prefetch=prefetch, remat=remat)
        return x, aux, None, pairs

    def body(carry, scanned):
        x, aux, pairs = carry
        if caches is None:
            lp, cache = scanned, None
        else:
            lp, cache = scanned
        new_cache = {}
        if P > 1:
            dense_caches = []
            for j in range(P - 1):
                sub = jax.tree.map(lambda a: a[j], lp["dense"])
                sub_cache = (
                    jax.tree.map(lambda a: a[j], cache["dense"])
                    if cache is not None else None
                )
                x, c = _apply_dense_block(
                    cfg, pxform(sub), x, window=0, positions=positions,
                    segment_ids=segment_ids, cache=sub_cache,
                    cache_index=cache_index, block_kv=block_kv,
                )
                dense_caches.append(c)
            if dense_caches[0] is not None:
                new_cache["dense"] = jax.tree.map(lambda *a: jnp.stack(a), *dense_caches)
        x, moe_cache, aux_l, pairs_l = _apply_moe_block(
            cfg, pxform(lp["moe"]), x, window=0, positions=positions,
            segment_ids=segment_ids, cache=cache["moe"] if cache is not None else None,
            cache_index=cache_index, block_kv=block_kv, moe_groups=moe_groups,
        )
        if moe_cache is not None:
            new_cache["moe"] = moe_cache
        return (x, aux + aux_l, pairs + pairs_l), new_cache

    if acc is not None and _folds(blocks):  # training: no caches
        x, aux, pairs = _fold_scan(lambda c, lp, _: body(c, lp)[0],
                                   (x, zero, zero), blocks, acc, None)
        return x, aux, None, pairs
    if remat:
        body = jax.checkpoint(body)
    xs = blocks if caches is None else (blocks, caches)
    (x, aux, pairs), new_caches = jax.lax.scan(body, (x, zero, zero), xs)
    if lead_cache is not None:
        new_caches = dict(new_caches, lead=lead_cache)
    return x, aux, new_caches, pairs


def _forward_ssm(cfg, params, batch, caches, remat, pxform, prefetch=None):
    x = _embed(cfg, params, batch)

    if prefetch is not None:
        def pbody(x, scanned):
            (lp,) = scanned
            return _apply_mamba_block(cfg, lp, x, cache=None)

        x, _ = _prefetch_scan(pbody, x, params["layers"], (),
                              prefetch=prefetch, remat=remat)
        return x, jnp.float32(0.0), None

    def body(x, scanned):
        if caches is None:
            lp, cache = scanned, None
        else:
            lp, cache = scanned
        x, cache = _apply_mamba_block(cfg, pxform(lp), x, cache=cache)
        return x, cache

    if remat:
        body = jax.checkpoint(body)
    xs = params["layers"] if caches is None else (params["layers"], caches)
    x, new_caches = jax.lax.scan(body, x, xs)
    return x, jnp.float32(0.0), new_caches


def _forward_hybrid(cfg, params, batch, caches, cache_index, remat, block_kv,
                    pxform, prefetch=None):
    x = _embed(cfg, params, batch)
    positions = batch.get("positions")
    segment_ids = batch.get("segment_ids")
    P = cfg.hybrid_attn_period
    shared = params["shared_attn"]
    no_cache = caches is None

    if prefetch is not None:
        def pbody(x, scanned):
            (lp,) = scanned  # (P, ...) super-layer slice, pre-materialized
            for j in range(P):
                sub = jax.tree.map(lambda a: a[j], lp)
                x, _ = _apply_mamba_block(cfg, sub, x, cache=None)
            x, _ = _apply_dense_block(
                cfg, shared, x, window=cfg.sliding_window or 0,
                positions=positions, segment_ids=segment_ids, cache=None,
                cache_index=cache_index, block_kv=block_kv,
            )
            return x, None

        x, _ = _prefetch_scan(pbody, x, params["mamba"], (),
                              prefetch=prefetch, remat=remat)
        # the tail (a short python loop, not a scan) keeps the plain
        # per-layer gather — nothing downstream to overlap it with
        if "mamba_tail" in params:
            tail_n = jax.tree.leaves(params["mamba_tail"])[0].shape[0]
            for j in range(tail_n):
                sub = jax.tree.map(lambda a: a[j], params["mamba_tail"])
                x, _ = _apply_mamba_block(cfg, pxform(sub), x, cache=None)
        return x, jnp.float32(0.0), {"mamba": None, "attn": None,
                                     "tail": None}

    def body(x, scanned):
        if no_cache:
            lp, mcache, acache = scanned, None, None
        else:
            lp, mcache, acache = scanned
        new_m = []
        for j in range(P):
            sub = jax.tree.map(lambda a: a[j], lp)
            sc = jax.tree.map(lambda a: a[j], mcache) if mcache is not None else None
            x, c = _apply_mamba_block(cfg, pxform(sub), x, cache=sc)
            new_m.append(c)
        # long_500k note: the shared attention block runs with the config's
        # sliding window when decoding beyond the attention budget
        x, acache = _apply_dense_block(
            cfg, shared, x, window=cfg.sliding_window or 0, positions=positions,
            segment_ids=segment_ids, cache=acache, cache_index=cache_index,
            block_kv=block_kv,
        )
        if new_m[0] is None:
            return x, acache
        new_mc = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
        return x, (new_mc, acache)

    if remat:
        body = jax.checkpoint(body)
    xs = (
        params["mamba"]
        if no_cache
        else (params["mamba"], caches["mamba"], caches["attn"])
    )
    x, ys = jax.lax.scan(body, x, xs)
    new_mamba, new_attn = (None, None) if no_cache else ys
    new_tail = None
    if "mamba_tail" in params:
        tail_n = jax.tree.leaves(params["mamba_tail"])[0].shape[0]
        new_tail = []
        for j in range(tail_n):
            sub = jax.tree.map(lambda a: a[j], params["mamba_tail"])
            sc = (
                jax.tree.map(lambda a: a[j], caches["tail"])
                if not no_cache and caches["tail"] is not None else None
            )
            x, c = _apply_mamba_block(cfg, pxform(sub), x, cache=sc)
            new_tail.append(c)
        new_tail = (
            jax.tree.map(lambda *a: jnp.stack(a), *new_tail)
            if new_tail and new_tail[0] is not None else None
        )
    new_caches = {"mamba": new_mamba, "attn": new_attn, "tail": new_tail}
    return x, jnp.float32(0.0), new_caches


def _encode(cfg, params, encoder_embeds, enc_positions=None, remat=False,
            block_kv=512, pxform=None, prefetch=None):
    x = encoder_embeds
    B, S, _ = x.shape
    if enc_positions is None:
        enc_positions = jnp.arange(S)[None, :].repeat(B, 0)

    def block(lp, x):
        with jax.named_scope("attention"):
            h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            # encoder self-attention is bidirectional
            a, _ = L.attn_apply(
                cfg, lp["attn"], h, positions=enc_positions, causal=False,
                block_kv=block_kv,
            )
        x = x + a
        with jax.named_scope("mlp"):
            h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            x = x + L.mlp_apply(cfg, lp["mlp"], h)
        return x

    if prefetch is not None:
        def pbody(x, scanned):
            (lp,) = scanned
            return block(lp, x), None

        x, _ = _prefetch_scan(pbody, x, params["enc_layers"], (),
                              prefetch=prefetch, remat=remat)
        return L.rms_norm(x, params["enc_final_norm"], cfg.norm_eps)

    def body(x, lp):
        return block((pxform or (lambda t: t))(lp), x), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def _forward_audio(cfg, params, batch, caches, cache_index, remat, block_kv,
                   pxform, prefetch=None):
    # encoder runs on the stub-frontend frame embeddings
    enc_out = None
    if "encoder_embeds" in batch:
        enc_out = _encode(cfg, params, batch["encoder_embeds"], remat=remat,
                          block_kv=block_kv, pxform=pxform, prefetch=prefetch)
    elif caches is not None and "enc_out" in caches:
        enc_out = caches["enc_out"]
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    positions = batch.get("positions")
    segment_ids = batch.get("segment_ids")
    B = x.shape[0]
    Senc = enc_out.shape[1]
    enc_positions = jnp.arange(Senc)[None, :].repeat(B, 0)

    self_caches = caches["self"] if caches is not None and "self" in caches else None

    def dec_block(lp, x, cache):
        x, cache = _apply_dense_block(
            cfg, lp, x, window=0, positions=positions, segment_ids=segment_ids,
            cache=cache, cache_index=cache_index, block_kv=block_kv,
        )
        # cross attention to the encoder output
        with jax.named_scope("attention"):
            h = L.rms_norm(x, lp["cross_norm"], cfg.norm_eps)
            hd = cfg.resolved_head_dim
            k = jnp.einsum("bsd,dk->bsk", enc_out, lp["cross"]["wk"]).reshape(B, Senc, cfg.num_kv_heads, hd)
            v = jnp.einsum("bsd,dk->bsk", enc_out, lp["cross"]["wv"]).reshape(B, Senc, cfg.num_kv_heads, hd)
            c, _ = L.attn_apply(
                cfg, lp["cross"], h, positions=positions, cross_kv=(k, v), block_kv=block_kv,
            )
        return x + c, cache

    if prefetch is not None:
        def pbody(x, scanned):
            (lp,) = scanned
            return dec_block(lp, x, None)

        x, _ = _prefetch_scan(pbody, x, params["dec_layers"], (),
                              prefetch=prefetch, remat=remat)
        return x, jnp.float32(0.0), {"self": None, "enc_out": enc_out}

    def body(x, scanned):
        if self_caches is None:
            lp, cache = scanned, None
        else:
            lp, cache = scanned
        return dec_block(pxform(lp), x, cache)

    if remat:
        body = jax.checkpoint(body)
    xs = params["dec_layers"] if self_caches is None else (params["dec_layers"], self_caches)
    x, new_self = jax.lax.scan(body, x, xs)
    new_caches = {"self": new_self, "enc_out": enc_out}
    return x, jnp.float32(0.0), new_caches


# ===========================================================================
# public API
# ===========================================================================
def apply(cfg: ModelConfig, params, batch, *, caches=None, cache_index=None,
          remat: bool = False, block_kv: int = 512, moe_groups: int = 0,
          pxform=None, prefetch=None, last_only: bool = False):
    """Forward pass.  last_only=True projects only the final position to
    logits (serve prefill/decode: avoids a (B, S, V) tensor).

    prefetch: FSDP gather transform for whole scan slices — switches the
    layer trunks to the double-buffered ``odc.prefetch_scan``
    (schedule='overlap'); training only, ignored on cached (serve) paths.
    """
    logits, aux, new_caches, _ = _apply(
        cfg, params, batch, caches=caches, cache_index=cache_index,
        remat=remat, block_kv=block_kv, moe_groups=moe_groups,
        pxform=pxform, prefetch=prefetch, last_only=last_only)
    return logits, aux, new_caches


def _apply(cfg, params, batch, *, caches, cache_index, remat, block_kv,
           moe_groups, pxform, prefetch, last_only, grad_acc=None):
    """:func:`apply`, plus the expert pairs computed here (MoE family)."""
    if pxform is None:
        pxform = lambda t: t
        prefetch = None  # prefetch is an FSDP mode; needs pxform for the
        #                  global (non-stacked) leaves
    else:
        # materialize the non-stacked ("global") leaves; stacked layer leaves
        # are materialized per layer inside the scan bodies (FSDP pattern)
        params = pxform(params)
    if caches is not None:
        prefetch = None
    fam = cfg.family
    pairs = None
    if fam == "ssm":
        x, aux, new_caches = _forward_ssm(cfg, params, batch, caches, remat, pxform, prefetch)
    elif fam == "hybrid":
        x, aux, new_caches = _forward_hybrid(cfg, params, batch, caches, cache_index, remat, block_kv, pxform, prefetch)
    elif fam == "audio":
        x, aux, new_caches = _forward_audio(cfg, params, batch, caches, cache_index, remat, block_kv, pxform, prefetch)
    elif cfg.num_experts:
        x, aux, new_caches, pairs = _forward_moe(cfg, params, batch, caches, cache_index, remat, block_kv, moe_groups, pxform, prefetch, grad_acc)
    else:
        x, aux, new_caches = _forward_dense(cfg, params, batch, caches, cache_index, remat, block_kv, pxform, prefetch, grad_acc)
    if last_only:
        x = x[:, -1:]
    return _logits(cfg, params, x), aux, new_caches, pairs


def loss(cfg: ModelConfig, params, batch, *, remat: bool = False,
         block_kv: int = 512, moe_groups: int = 0, pxform=None,
         prefetch=None, reduction: str = "mean", grad_acc=None):
    """Weighted token cross-entropy (weights = loss_mask; supports GRPO-style
    advantage weighting by passing signed weights).

    reduction='sum' returns the un-normalized nll sum (used by the FSDP
    engines to accumulate across microbatches before global normalization).

    The router's balance loss is a per-token mean, added as such, or with
    ``moe_aux_per_sequence`` a sum over samples (``moe.
    sequence_balance_loss``), added to the summed cross-entropy.  MoE
    configs add ``moe_pairs_held`` to the metrics: the (token, expert)
    pairs the held experts computed, summed over layers.

    grad_acc: None, or a running gradient sum shaped as ``params``; the
    gradient of each leaf :func:`grad_fold_mask` marks then comes back
    with its sum added, inside the layer scan's backward."""
    if grad_acc is not None and (not remat or prefetch is not None):
        raise ValueError("grad_acc folds into the checkpointed layer scans; "
                         "it needs remat=True and no prefetch")
    logits, aux, _, pairs = _apply(
        cfg, params, batch, caches=None, cache_index=None, remat=remat,
        block_kv=block_kv, moe_groups=moe_groups, pxform=pxform,
        prefetch=prefetch, last_only=False, grad_acc=grad_acc)
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    with jax.named_scope("cross_entropy"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt_logit = jnp.take_along_axis(logits, targets[..., None],
                                        axis=-1)[..., 0]
        nll = (logz - tgt_logit) * mask
    tokens = jnp.sum(jnp.abs(mask))
    extra = {} if pairs is None else {"moe_pairs_held": pairs}
    if reduction == "sum":
        total = jnp.sum(nll) + (aux if cfg.moe_aux_per_sequence
                                else aux * jnp.maximum(tokens, 1.0))
        return total, {"ce_sum": jnp.sum(nll), "aux": aux, "tokens": tokens,
                       **extra}
    denom = jnp.maximum(tokens, 1.0)
    ce = jnp.sum(nll) / denom
    total = ce + (aux / denom if cfg.moe_aux_per_sequence else aux)
    return total, {"ce": ce, "aux": aux, "tokens": tokens, **extra}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32,
               enc_len: int = 0):
    """Decode caches matching the parameter layout.  enc_len > 0 (audio):
    allocate the encoder-output cache for decode-without-encoder steps."""
    hd, KH = cfg.resolved_head_dim, cfg.num_kv_heads

    def attn_cache(prefix=()):
        if cfg.is_mla:  # the latent and the shared rope key, per token
            return {
                "c": jnp.zeros(prefix + (batch, max_len, cfg.kv_lora_rank),
                               dtype),
                "k_rope": jnp.zeros(
                    prefix + (batch, max_len, cfg.qk_rope_head_dim), dtype),
            }
        return {
            "k": jnp.zeros(prefix + (batch, max_len, KH, hd), dtype),
            "v": jnp.zeros(prefix + (batch, max_len, KH, hd), dtype),
        }

    fam = cfg.family
    if fam == "ssm":
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape),
            ssm_mod.init_ssm_cache(cfg, batch, dtype),
        )
    if fam == "hybrid":
        P = cfg.hybrid_attn_period
        n_super, tail = cfg.num_layers // P, cfg.num_layers % P
        base = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        caches = {
            "mamba": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_super, P) + x.shape), base
            ),
            "attn": attn_cache((n_super,)),
            "tail": (
                jax.tree.map(lambda x: jnp.broadcast_to(x, (tail,) + x.shape), base)
                if tail else None
            ),
        }
        return caches
    if fam == "audio":
        enc_out = (jnp.zeros((batch, enc_len, cfg.d_model), dtype)
                   if enc_len else None)
        return {"self": attn_cache((cfg.num_layers,)), "enc_out": enc_out}
    if cfg.num_experts:
        P = cfg.moe_period
        n_super = cfg.num_moe_layers
        moe_c = attn_cache((n_super,))
        if not cfg.moe_grouped:
            # router usage tally: makes capacity-drop decisions causally
            # consistent between prefill and decode (see moe.moe_apply)
            moe_c["router_counts"] = jnp.zeros(
                (n_super, batch, cfg.experts_per_token, cfg.num_experts),
                jnp.int32)
        c = {"moe": moe_c}
        if P > 1:
            c["dense"] = attn_cache((n_super, P - 1))
        if cfg.first_dense_layers:
            c["lead"] = attn_cache((cfg.first_dense_layers,))
        return c
    return attn_cache((cfg.num_layers,))
