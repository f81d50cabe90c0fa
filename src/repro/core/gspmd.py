"""GSPMD engine: pjit + NamedSharding realization of FSDP±ODC.

This is the production path used by the multi-pod dry-run and the roofline
analysis.  The mesh is (data, model) or (pod, data, model):

  * ``model`` — tensor parallelism (Megatron): attention q/o heads and FFN
    hidden sharded; MoE experts expert-parallel over ``model`` when the
    expert count divides the axis, else tensor-parallel inside each expert.
  * ``data``  — FSDP/ZeRO-3: every parameter additionally sharded over
    ``data``; the batch is sharded over ``data``.  This is the axis the
    paper's technique acts on.
  * ``pod``   — pure data parallelism across pods (gradient psum over
    ``pod`` once per minibatch, inserted by AD).

The paper's contribution appears as the **schedule** knob, which controls
where parameter-gather / gradient-scatter collectives are placed:

  schedule='layer'      FSDP baseline — parameters are materialized
                        (``data`` axis gathered) *inside* the layer scan via
                        a sharding constraint, so the lowered HLO carries an
                        all-gather per layer per microbatch and the
                        transposed reduce-scatter per layer per microbatch:
                        2·L·M sync points per minibatch (paper Fig. 1).

  schedule='minibatch'  ODC — parameters are materialized once before the
                        microbatch scan; AD accumulates full gradients
                        locally across microbatches and emits exactly one
                        reduce-scatter per parameter at the minibatch end
                        (paper Fig. 2).  Collective *count* drops from
                        2·L·M to 2·L; the synchronization barrier moves to
                        the minibatch boundary.

  schedule='overlap'    Overlapped ODC — per-layer gathers like 'layer',
                        but software-pipelined: the layer scan carries a
                        one-slot-ahead prefetch (``odc.prefetch_scan``),
                        so layer l+1's p2p gather chain is issued before
                        layer l's matmuls and has no data dependence on
                        them; the backward mirrors it (layer l+1's
                        scatter-accumulate is issued during layer l's
                        backward).  Values are identical to 'minibatch' /
                        'layer' (same gathers and scatter-accumulates,
                        different issue order); what changes is the HLO
                        schedule the latency-hiding scheduler sees.
                        ``repro.sim`` (scheme='overlap') charges the
                        timing: comm only where it exceeds compute.

  comm=<backend>        how each gather / scatter-accumulate moves bytes —
                        a ``repro.core.backend`` registry name:
                        'collective' (fused AG/RS), 'odc' (p2p ring),
                        'odc-overlap' (odc + implied overlap schedule), or
                        'hier' (params sharded over a (node, device) 2D
                        mesh: intra-node collective all-gather + inter-node
                        profile-ordered p2p ring — needs
                        ``ShardingRules(data=('node', 'device'))``).

  hybrid_pod=True       ZeRO++-style hybrid sharding (paper §6.1/App. E) on
                        the multi-pod mesh: parameter gather/scatter stays
                        *intra-pod* (params never sharded over ``pod``), and
                        only optimizer states are sharded over ``pod``.

Under XLA's bulk-synchronous SPMD model the wall-clock *asynchrony* of ODC
cannot be realized inside one program — ``repro.sim`` models that (and
reproduces the paper's timing tables); what this engine realizes is the
communication schedule itself, which is visible in the lowered HLO and is
what the roofline's collective term measures.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update


# ===========================================================================
# sharding rules
# ===========================================================================
@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Mesh-axis names (None disables the axis)."""

    data: Any = "data"  # FSDP axis (str or tuple of axes)
    model: Optional[str] = "model"  # tensor/expert parallel axis
    pod: Optional[str] = None  # pure-DP pod axis

    @property
    def dp_axes(self):
        """Batch-sharding axes: pod-major then data."""
        d = self.data if isinstance(self.data, tuple) else (self.data,)
        return tuple(a for a in ((self.pod,) + d if self.pod else d) if a)


def _moe_expert_parallel(num_experts: int, mesh: Mesh, model_axis) -> bool:
    if not model_axis or model_axis not in mesh.shape:
        return False
    return num_experts % mesh.shape[model_axis] == 0


def leaf_pspec(name: str, ndim: int, rules: ShardingRules, *,
               expert_parallel: bool = False,
               ep_data_axis=None) -> P:
    """PartitionSpec for the *unstacked* (logical) dims of one parameter.

    ``name`` is the final pytree key; stacking prefixes are handled by the
    caller (prepended None entries).
    """
    da, mo = rules.data, rules.model
    if name == "embed":  # (V, d): vocab-parallel + FSDP
        return P(mo, da)
    if name == "lm_head":  # (d, V)
        return P(da, mo)
    if name in ("wq", "wk", "wv"):  # (d, heads*hd)
        return P(da, mo)
    if name == "wo":  # (q_dim, d)
        return P(mo, da)
    if name == "wkv_a":  # MLA (d, latent + rope): the latent is normed whole
        return P(da, None)
    if name == "wkv_b":  # MLA (latent, heads*(nope+v))
        return P(da, mo)
    if name in ("w_up", "w_gate"):
        if ndim == 3:  # MoE (E, d, f)
            if ep_data_axis is not None:
                # weight-stationary EP: experts sharded over the FSDP axis,
                # never gathered — tokens move instead (all_to_all)
                return P(ep_data_axis, None, mo)
            return P(mo, da, None) if expert_parallel else P(None, da, mo)
        return P(da, mo)  # (d, f)
    if name == "w_down":
        if ndim == 3:  # MoE (E, f, d)
            if ep_data_axis is not None:
                return P(ep_data_axis, mo, None)
            return P(mo, None, da) if expert_parallel else P(None, mo, da)
        return P(mo, da)  # (f, d)
    if name == "router":  # (d, E)
        return P(da, None)
    if name == "in_proj":  # mamba (d, 2di+2gn+nh)
        return P(da, mo)
    if name == "out_proj":  # mamba (di, d)
        return P(mo, da)
    if name == "conv_w":  # (W, conv_dim)
        return P(None, da)
    # 1-D leaves: norms, biases, A_log, D, dt_bias, gate_norm ... ZeRO-3
    # shards everything; these are small.  Shard over the innermost data
    # axis only (some are not divisible by a flattened pod×data axis,
    # e.g. mamba2's 80 ssm heads over 32).
    da1 = da[-1] if isinstance(da, tuple) else da
    return P(*([None] * (ndim - 1) + [da1]))


_STACK_KEYS = {"layers", "enc_layers", "dec_layers", "mamba", "mamba_tail",
               "moe_blocks", "dense"}


def _stack_rank_for_path(path) -> int:
    """Number of leading stacked-layer dims for a leaf at ``path`` of the
    full params pytree (mirrors init_params's prefix_shape choices)."""
    keys = [k.key for k in path if hasattr(k, "key")]
    if not keys:
        return 0
    if keys[0] == "mamba" and len(keys) > 1:
        return 2  # hybrid: (n_super, P)
    if keys[0] == "mamba_tail":
        return 1
    if keys[0] == "shared_attn":
        return 0
    if keys[0] in ("layers", "enc_layers", "dec_layers"):
        if len(keys) > 1 and keys[1] == "dense":
            return 2  # moe super-layer: (n_super, P-1)
        if len(keys) > 1 and keys[1] == "moe":
            return 1
        return 1
    return 0


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on any dim the mesh axes cannot divide evenly (input
    shardings require exact divisibility; e.g. mamba2's vocab 50280 is not
    divisible by a 16-wide model axis — replicate that dim instead)."""
    out = []
    for i, e in enumerate(spec):
        if e is None:
            out.append(None)
            continue
        axes = e if isinstance(e, tuple) else (e,)
        n = 1
        for a in axes:
            n *= mesh.shape.get(a, 1)
        if i < len(shape) and shape[i] % n == 0 and shape[i] >= n:
            out.append(e)
        else:
            out.append(None)
    return P(*out)


def moe_ep_data_axis(cfg: ModelConfig, rules: ShardingRules, mesh: Mesh,
                     moe_ep: str):
    """The FSDP axis (or axis tuple) to expert-shard over, if requested and
    divisible; None otherwise (fall back to the FSDP-gather baseline)."""
    if moe_ep != "data" or not cfg.num_experts:
        return None
    da = rules.data if isinstance(rules.data, tuple) else (rules.data,)
    size = 1
    for a in da:
        size *= mesh.shape.get(a, 1)
    if cfg.num_experts % size == 0 and cfg.num_experts >= size:
        return da if len(da) > 1 else da[0]
    inner = da[-1]
    if cfg.num_experts % mesh.shape.get(inner, 1) == 0 \
            and cfg.num_experts >= mesh.shape.get(inner, 1):
        return inner
    return None


def param_pspecs(cfg: ModelConfig, params, rules: ShardingRules, mesh: Mesh,
                 moe_ep: str = "none"):
    """PartitionSpec pytree matching ``params`` (full model, stacked)."""
    ep = _moe_expert_parallel(cfg.num_experts, mesh, rules.model)
    ep_da = moe_ep_data_axis(cfg, rules, mesh, moe_ep)

    def spec(path, leaf):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = keys[-1] if keys else ""
        r = _stack_rank_for_path(path)
        logical_ndim = leaf.ndim - r
        s = leaf_pspec(name, logical_ndim, rules, expert_parallel=ep,
                       ep_data_axis=ep_da)
        return sanitize_spec(P(*([None] * r + list(s))), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec, params)


def _drop_axis(spec: P, axes) -> P:
    """Remove the given mesh axes from a PartitionSpec (gather them)."""
    axes = set(axes if isinstance(axes, (tuple, list, set)) else [axes])

    def clean(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a not in axes)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return None if e in axes else e

    return P(*[clean(e) for e in spec])


def gather_pspecs(pspecs, rules: ShardingRules):
    """Specs with the FSDP (data) axis gathered — the materialized params."""
    da = rules.data if isinstance(rules.data, tuple) else (rules.data,)
    return jax.tree.map(lambda s: _drop_axis(s, da), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


# Logical (unstacked) rank of each named parameter; used to tell a sliced
# per-layer leaf (gather it) from a still-stacked leaf (skip — the scan body
# gathers it after slicing).  MoE w_up/w_gate/w_down/router live under a
# "moe" parent and carry the extra expert dim.
_LOGICAL_RANK = {
    "embed": 2, "lm_head": 2,
    "wq": 2, "wk": 2, "wv": 2, "wo": 2,
    "w_up": 2, "w_gate": 2, "w_down": 2,
    "router": 2, "in_proj": 2, "out_proj": 2, "conv_w": 2,
}


def _logical_rank(keys) -> int:
    name = keys[-1] if keys else ""
    r = _LOGICAL_RANK.get(name, 1)
    if name in ("w_up", "w_gate", "w_down") and len(keys) >= 2 and keys[-2] == "moe":
        r = 3
    return r


def _axes_in_spec(spec: P):
    out = set()
    for e in spec:
        if e is None:
            continue
        for a in (e if isinstance(e, tuple) else (e,)):
            out.add(a)
    return out


def _keep_axes(spec: P, keep) -> P:
    """Spec restricted to the given axes (the manual part for shard_map)."""
    keep = set(keep)

    def f(e):
        if e is None:
            return None
        es = tuple(a for a in (e if isinstance(e, tuple) else (e,)) if a in keep)
        return es if len(es) > 1 else (es[0] if es else None)

    return P(*[f(e) for e in spec])


def _data_dims(spec: P, da_axes) -> list:
    """[(dim, axes_tuple)] positions sharded over the FSDP axes."""
    da = set(da_axes)
    out = []
    for i, e in enumerate(spec):
        if e is None:
            continue
        hit = tuple(a for a in (e if isinstance(e, tuple) else (e,)) if a in da)
        if hit:
            out.append((i, hit))
    return out


# ===========================================================================
# batch / cache specs
# ===========================================================================
def batch_pspecs(batch, rules: ShardingRules, *, microbatched: bool = True,
                 cp_axis=None):
    """tokens/targets/masks: (M, B, S) or (B, S); embeds: (..., S, d).

    With ``cp_axis`` (context parallelism), the sequence dim of the
    token-shaped leaves is sharded over that axis and the batch dim over
    the remaining dp axes."""
    dp = rules.dp_axes
    if cp_axis is not None:
        dp = tuple(a for a in dp if a != cp_axis)
    lead = (None,) if microbatched else ()

    def spec(path, x):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = keys[-1] if keys else ""
        nd = x.ndim - len(lead)
        if name in ("encoder_embeds", "vision_embeds"):
            return P(*lead, dp, *([None] * (nd - 2)))
        if cp_axis is not None and nd >= 2:
            return P(*lead, dp, cp_axis, *([None] * (nd - 2)))
        return P(*lead, dp, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(spec, batch)


def cache_pspecs(cache, rules: ShardingRules, mesh: Mesh, *,
                 batch_size: int, shard_seq: bool = False):
    """Decode-cache specs.  k/v: (stack..., B, T, KH, hd).  When the request
    batch covers the dp axes, shard B; for single-request long-context
    (B=1), shard the cache sequence dim instead (sequence-parallel KV).
    The model axis shards KV heads when divisible; otherwise it shards the
    cache sequence dim (flash-decode-style parallel KV read)."""
    dp, mo = rules.dp_axes, rules.model
    mo_size = mesh.shape.get(mo, 1) if mo else 1

    def div(n):  # can the model axis shard a dim of size n?
        return mo if (mo and n % mo_size == 0 and n >= mo_size) else None

    def spec(path, x):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = keys[-1] if keys else ""
        if name in ("k", "v"):
            r = x.ndim - 4  # stack prefix
            B, T, KH, hd = x.shape[r:]
            if div(KH):
                head_s, seq_extra = mo, None
            else:
                head_s, seq_extra = None, mo
            if shard_seq:
                seq = (dp if seq_extra is None
                       else tuple(list(dp) + [seq_extra]))
                return P(*([None] * r), None, seq, head_s, None)
            return P(*([None] * r), dp, seq_extra, head_s, None)
        if name == "conv":  # (stack..., B, W-1, conv_dim)
            r = x.ndim - 3
            b = None if shard_seq else dp
            return P(*([None] * r), b, None, div(x.shape[-1]))
        if name == "ssm":  # (stack..., B, nh, hd, n)
            r = x.ndim - 4
            b = None if shard_seq else dp
            return P(*([None] * r), b, div(x.shape[r + 1]), None, None)
        if name == "enc_out":  # (B, S_enc, d)
            b = None if shard_seq else dp
            return P(b, None, div(x.shape[-1]))
        if name in ("c", "k_rope"):  # MLA latent cache (stack..., B, T, r)
            r = x.ndim - 3
            if shard_seq:
                return P(*([None] * r), None, dp, None)
            return P(*([None] * r), dp, None, None)
        if name == "router_counts":  # (stack..., B, k, E)
            r = x.ndim - 3
            b = None if shard_seq else dp
            return P(*([None] * r), b, None, None)
        return P(*([None] * x.ndim))

    return jax.tree_util.tree_map_with_path(spec, cache)


# ===========================================================================
# train step
# ===========================================================================
@dataclasses.dataclass(frozen=True)
class GSPMDConfig:
    rules: ShardingRules = ShardingRules()
    schedule: str = "minibatch"  # 'layer' (FSDP baseline) | 'minibatch'
    #                              (ODC) | 'overlap' (ODC + double-buffered
    #                              prefetch: gather l+1 under layer l's
    #                              compute, scatter l under l-1's backward)
    comm: str = "collective"  # repro.core.backend registry name:
    #                           'collective' (fused AG/RS) | 'odc' (p2p
    #                           ring) | 'odc-overlap' (odc + implied
    #                           overlap schedule) | 'hier' (intra-node
    #                           collective + inter-node ring; needs a
    #                           2-axis data tuple) | 'pipe'/'pipe-int8'
    #                           (stage-partitioned 1F1B over a
    #                           ('pipe', 'data') 2-axis tuple; -int8 rides
    #                           the chunked-int8 cross-stage wire) —
    #                           legacy aliases resolve through the registry
    pipe_stages: int = 0  # comm='pipe': 1F1B pipeline depth; 0 = the size
    #                       of the leading data axis (the pipe mesh axis)
    pipe_interleave: bool = False  # halved-warmup interleaved 1F1B variant
    hybrid_pod: bool = False  # ZeRO++-style: params not sharded over pod
    moe_ep: str = "none"  # 'none' (FSDP gather, baseline) | 'data'
    #                       (weight-stationary EP: experts sharded over the
    #                       FSDP axis, dispatched via all_to_all — §Perf)
    remat: bool = True
    block_kv: int = 512
    moe_groups: int = 0
    param_dtype: Any = jnp.float32
    device_profile: Any = None  # balance.cost.DeviceProfile: with
    #                             comm='odc', p2p chains walk the profile's
    #                             ring order (stragglers adjacent); values
    #                             and lowered comm volume are unchanged


def train_param_pspecs(cfg, params, gcfg: GSPMDConfig, mesh: Mesh):
    specs = param_pspecs(cfg, params, gcfg.rules, mesh)
    # pod axis: params replicated over pod (pure DP); in hybrid_pod mode this
    # is exactly ZeRO++ (gather never crosses the pod boundary).
    return specs


def opt_pspecs(param_specs, gcfg: GSPMDConfig):
    """Optimizer moments follow the params; in hybrid_pod mode they are
    *additionally* sharded over pod on the last already-data-sharded dim
    (optimizer states global, params intra-pod — paper §6.1)."""
    rules = gcfg.rules
    if not (gcfg.hybrid_pod and rules.pod):
        m = jax.tree.map(lambda s: s, param_specs,
                         is_leaf=lambda x: isinstance(x, P))
        return {"m": m, "v": m, "step": P()}

    def widen(s: P) -> P:
        da = rules.data if isinstance(rules.data, tuple) else (rules.data,)
        out = []
        done = False
        for e in s:
            if not done and e is not None:
                cur = e if isinstance(e, tuple) else (e,)
                if any(a in da for a in cur):
                    out.append(tuple([rules.pod] + list(cur)))
                    done = True
                    continue
            out.append(e)
        return P(*out)

    m = jax.tree.map(widen, param_specs, is_leaf=lambda x: isinstance(x, P))
    return {"m": m, "v": m, "step": P()}


def make_train_step(cfg: ModelConfig, mesh: Mesh, gcfg: GSPMDConfig,
                    opt_cfg: AdamWConfig = AdamWConfig(),
                    lr_schedule=None):
    """Returns step_fn(params, opt_state, batch) -> (params, opt, metrics);
    batch leaves are (M, B_global, S...).

    The FSDP axis (``data``, plus ``pod`` when the mesh has one) is handled
    *manually* inside ``shard_map`` — parameter gathers and gradient
    scatter-accumulates are explicit, with the (comm, schedule) knobs of the
    paper resolved through the ``repro.core.backend`` registry.  The
    ``model`` axis stays automatic (GSPMD tensor parallelism).
    """
    rules = gcfg.rules
    from repro.core import backend as B

    comm_backend, schedule = B.resolve(gcfg.comm, gcfg.schedule)

    da = rules.data if isinstance(rules.data, tuple) else (rules.data,)
    if comm_backend.name == "hier" and len(da) < 2:
        raise ValueError(
            "comm='hier' shards parameters over a (node, device) 2D mesh — "
            "set ShardingRules(data=('node', 'device')) (or any 2-axis "
            f"tuple); got data={rules.data!r}")
    if comm_backend.name.startswith("pipe") and len(da) < 2:
        raise ValueError(
            "comm='pipe' stage-partitions the layer stack over a "
            "(pipe, data) 2D mesh — set ShardingRules(data=('pipe', "
            f"'data')) (or any 2-axis tuple); got data={rules.data!r}")
    if comm_backend.name == "cp" and len(da) < 2:
        raise ValueError(
            "comm='cp' shards the batch sequence dim over the trailing "
            "data axis — set ShardingRules(data=('data', 'cp')) (or any "
            f"2-axis tuple, cp minor); got data={rules.data!r}")
    if comm_backend.name.startswith("pipe"):
        pipe_stages = gcfg.pipe_stages or mesh.shape[da[0]]
    else:
        pipe_stages = 1
    # context parallelism: params stay ZeRO-sharded over the FLAT data
    # tuple (identical bytes to flat ODC); what changes is the batch layout
    # (sequence dim over the cp axis) and the attention impl (KV ring).
    cp_axis = da[-1] if comm_backend.name == "cp" else None
    manual = tuple(da) + ((rules.pod,) if rules.pod else ())
    ep = _moe_expert_parallel(cfg.num_experts, mesh, rules.model)

    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, gcfg.param_dtype), jax.random.PRNGKey(0))
    pspecs = param_pspecs(cfg, params_shape, rules, mesh, moe_ep=gcfg.moe_ep)
    manual_pspecs = jax.tree.map(lambda s: _keep_axes(s, manual), pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
    ep_da = moe_ep_data_axis(cfg, rules, mesh, gcfg.moe_ep)

    def _is_stationary_expert(keys) -> bool:
        """Expert FFN weights under weight-stationary EP are never
        gathered — tokens travel to them instead."""
        return (ep_da is not None and len(keys) >= 2 and keys[-2] == "moe"
                and keys[-1] in ("w_up", "w_gate", "w_down"))

    # (parent, name, logical_ndim) -> sanitized logical spec, keyed by the
    # *relative* path the pxform hook will see on sliced subtrees (leading
    # stack containers stripped), so per-layer gathers exactly mirror the
    # storage sharding.  A bare (name, ndim) key is ambiguous: a stacked
    # shared-expert w_up (ndim 3) would collide with the sliced MoE expert
    # w_up (logical ndim 3).
    logical_specs = {}
    # Partially-sliced keys for super-layer subtrees (stack rank >= 2, e.g.
    # a MoE period block's dense sub-stack or a hybrid super-layer): the
    # overlap prefetch materializes a WHOLE scan slice one iteration ahead,
    # so its leaves still carry the inner stack dim.  Kept separate from
    # logical_specs — merging them would make the top-level pxform gather
    # fully-stacked rank-1 leaves that happen to share (parent, name, ndim)
    # with a once-sliced rank-2 leaf (e.g. the stacked MoE-block attn wq).
    sliced_specs = {}

    def _relative_keys(keys):
        ks = list(keys)
        if ks and ks[0] in ("layers", "enc_layers", "dec_layers",
                            "mamba", "mamba_tail"):
            first = ks.pop(0)
            if (first == "layers" and ks and ks[0] in ("moe", "dense", "lead")
                    and len(ks) > 1):
                ks.pop(0)  # moe super-layer block container
        return ks

    def _register(path, leaf, spec):
        keys = _relative_keys([k.key for k in path if hasattr(k, "key")])
        r = _stack_rank_for_path(path)
        parent = keys[-2] if len(keys) >= 2 else ""
        logical_specs[(parent, keys[-1], leaf.ndim - r)] = P(*list(spec)[r:])
        for d in range(1, r):  # stack dims carry no sharding (spec prefix
            sliced_specs[(parent, keys[-1], leaf.ndim - d)] = \
                P(*list(spec)[d:])  # is None), so dropping entries is exact

    jax.tree_util.tree_map_with_path(_register, params_shape, pspecs)

    def _gather_leaf(leaf, spec):
        """Materialize over the FSDP axes (custom VJP → bwd is the matching
        scatter-accumulate), then anchor the tensor-parallel sharding."""
        dd = _data_dims(spec, da)
        if dd:
            dim, axes = dd[0]
            ax = axes if len(axes) > 1 else axes[0]
            leaf = comm_backend.param_gather(
                ax, dim=dim,
                device_profile=gcfg.device_profile)(leaf)
        auto = _drop_axis(spec, manual)
        if _axes_in_spec(auto):
            # use the context (abstract) mesh: inside shard_map the data
            # axes are Manual and a concrete-mesh NamedSharding would not
            # match the tracing context
            leaf = jax.lax.with_sharding_constraint(
                leaf, NamedSharding(jax.sharding.get_abstract_mesh(), auto))
        return leaf

    def _constrain_auto(leaf, spec):
        auto = _drop_axis(spec, manual)
        if _axes_in_spec(auto):
            leaf = jax.lax.with_sharding_constraint(
                leaf, NamedSharding(jax.sharding.get_abstract_mesh(), auto))
        return leaf

    def gather_full(params_local):
        def g(path, leaf, spec):
            keys = [k.key for k in path if hasattr(k, "key")]
            if _is_stationary_expert(keys):
                return _constrain_auto(leaf, spec)
            return _gather_leaf(leaf, spec)

        return jax.tree_util.tree_map_with_path(g, params_local, pspecs)

    def pxform(tree):
        """schedule='layer' hook: gather only leaves at their logical rank
        (i.e. global leaves at the top level, sliced leaves inside the layer
        scans); still-stacked leaves pass through untouched."""

        def mat(path, leaf):
            raw = [k.key for k in path if hasattr(k, "key")]
            if not raw:
                return leaf
            for keys in (raw, _relative_keys(raw)):
                if not keys:
                    continue
                parent = keys[-2] if len(keys) >= 2 else ""
                spec = logical_specs.get((parent, keys[-1], leaf.ndim))
                if spec is not None:
                    if _is_stationary_expert(keys):
                        return _constrain_auto(leaf, spec)
                    return _gather_leaf(leaf, spec)
            return leaf  # stacked — gathered after slicing in the scan

        return jax.tree_util.tree_map_with_path(mat, tree)

    def pxform_overlap(tree):
        """schedule='overlap' prefetch hook: materialize EVERY leaf of a
        one-iteration scan slice (``odc.prefetch_scan`` applies this to
        layer l+1's shards while layer l computes).  Unlike the 'layer'
        hook it must also gather leaves that still carry an inner stack
        dim (super-layer sub-stacks), via ``sliced_specs``."""

        def candidates(raw):
            out = [raw, _relative_keys(raw)]
            if len(raw) > 1 and raw[0] in ("dense", "moe", "lead"):
                # slice-rooted paths keep the super-layer block container
                # that registration (rooted at the full tree) stripped
                out.append(raw[1:])
            return out

        def mat(path, leaf):
            raw = [k.key for k in path if hasattr(k, "key")]
            if not raw:
                return leaf
            for keys in candidates(raw):
                if not keys:
                    continue
                parent = keys[-2] if len(keys) >= 2 else ""
                spec = logical_specs.get((parent, keys[-1], leaf.ndim))
                if spec is None:
                    spec = sliced_specs.get((parent, keys[-1], leaf.ndim))
                if spec is not None:
                    if _is_stationary_expert(keys):
                        return _constrain_auto(leaf, spec)
                    return _gather_leaf(leaf, spec)
            return leaf

        return jax.tree_util.tree_map_with_path(mat, tree)

    # what each microbatch counts beside its loss: its tokens, and for an
    # expert model the (token, expert) pairs its held experts computed
    counted = ("tokens",) + (("moe_pairs_held",) if cfg.num_experts else ())

    def loss_sum(p, mb, px, prefetch=None):
        val, metrics = T.loss(
            cfg, p, mb, remat=gcfg.remat, block_kv=gcfg.block_kv,
            moe_groups=gcfg.moe_groups, pxform=px, prefetch=prefetch,
            reduction="sum",
        )
        return val, {k: metrics[k] for k in counted}

    # the schedule loop (gather placement) is the shared seam with the flat
    # engine — repro.core.backend.build_schedule_grad — fed this engine's
    # gather/prefetch hooks.  The minibatch schedule runs each microbatch's
    # forward once, right before its backward, so only the model's own
    # per-layer remat recomputes; the checkpoint flag remats the forwards
    # that 1f1b keeps in flight (full-model gradient residency is the ODC
    # trade, not activations)
    grad_core = B.build_schedule_grad(
        schedule,
        loss_sum=loss_sum,
        gather_all=gather_full,
        pxform=pxform,
        prefetch=pxform_overlap,
        checkpoint_minibatch=True,
        pipe_stages=pipe_stages,
        pipe_interleave=gcfg.pipe_interleave,
        zero_counts={k: jnp.float32(0.0) for k in counted},
    )

    def grad_minibatch(params_local, batch_local):
        from repro.models import moe as moe_mod
        moe_mod.set_ep_axis(ep_da)  # trace-time: weight-stationary dispatch
        if cp_axis is not None:
            from repro.core import cp as cp_mod
            from repro.models import layers as L
            # trace-time: every attention inside this shard_map region runs
            # the cp KV ring (static window) or the all_gather fallback
            # (traced window); step() restores the impl in its finally
            L.set_attention_impl(cp_mod.cp_attention_impl(
                cp_axis, blk_q=min(128, gcfg.block_kv) or 128,
                blk_k=min(128, gcfg.block_kv) or 128))
        return _grad_minibatch(params_local, batch_local)

    def _grad_minibatch(params_local, batch_local):
        lsum, counts, grads = grad_core(params_local, batch_local)

        lsum = jax.lax.psum(lsum, manual)
        counts = jax.lax.psum(counts, manual)
        denom = jnp.maximum(counts["tokens"], 1.0)

        def finalize(g, spec):
            leftover = tuple(a for a in manual
                             if a not in _axes_in_spec(spec))
            if leftover:
                g = jax.lax.psum(g, leftover)
            return g / denom.astype(g.dtype)

        grads = jax.tree.map(finalize, grads, manual_pspecs)
        return grads, {"loss": lsum / denom, **counts}

    # batch leaves carrying a sequence dim at position 2 of (M, B, S, ...)
    # — under cp their S dim is sharded over the cp axis (the host
    # pre-interleaves S so each contiguous shard is a head+tail chunk pair)
    _SEQ_LEAVES = ("tokens", "targets", "positions", "segment_ids",
                   "loss_mask")

    def batch_manual_specs(batch):
        if cp_axis is None:
            return jax.tree.map(
                lambda x: P(None, manual, *([None] * (x.ndim - 2))), batch)
        bman = tuple(a for a in manual if a != cp_axis)

        def spec(path, x):
            keys = [k.key for k in path if hasattr(k, "key")]
            name = keys[-1] if keys else ""
            if name in _SEQ_LEAVES and x.ndim >= 3:
                return P(None, bman, cp_axis, *([None] * (x.ndim - 3)))
            return P(None, bman, *([None] * (x.ndim - 2)))

        return jax.tree_util.tree_map_with_path(spec, batch)

    def step(params, opt_state, batch):
        from repro.models import layers as L
        from repro.models import moe as moe_mod
        sharded = jax.shard_map(
            grad_minibatch,
            mesh=mesh,
            in_specs=(manual_pspecs, batch_manual_specs(batch)),
            out_specs=(manual_pspecs, P()),
            check_vma=False,
            axis_names=set(manual),
        )
        prev_impl = L.get_attention_impl()
        try:
            grads, metrics = sharded(params, batch)
        finally:
            moe_mod.set_ep_axis(None)
            if cp_axis is not None:
                L.set_attention_impl(prev_impl)
        scale = lr_schedule(opt_state["step"]) if lr_schedule else 1.0
        new_params, new_opt = adamw_update(opt_cfg, params, grads, opt_state,
                                           lr_scale=scale)
        return new_params, new_opt, metrics

    return step


def train_state_shardings(cfg: ModelConfig, mesh: Mesh, gcfg: GSPMDConfig):
    """(param shardings, AdamW-state shardings): the FSDP layout of the
    training state, as NamedSharding pytrees."""
    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, gcfg.param_dtype), jax.random.PRNGKey(0))
    pspecs = train_param_pspecs(cfg, params_shape, gcfg, mesh)
    opt_shape = jax.eval_shape(adamw_init, params_shape)
    ospecs = opt_pspecs(pspecs, gcfg)
    # hybrid_pod widening can exceed a small dim (e.g. mamba2's 80 ssm
    # heads over pod×data=32) — sanitize against the actual shapes
    ospecs = jax.tree.map(
        lambda s, sp: sanitize_spec(sp, s.shape, mesh), opt_shape, ospecs)
    named = lambda sp: NamedSharding(mesh, sp)
    return jax.tree.map(named, pspecs), jax.tree.map(named, ospecs)


def train_batch_shardings(batch, mesh: Mesh, gcfg: GSPMDConfig):
    """NamedSharding pytree for a (M, B_global, S...) batch: rows over the
    dp axes, and the sequence dim over the cp axis under comm='cp'."""
    from repro.core import backend as B
    cb, _ = B.resolve(gcfg.comm, gcfg.schedule)
    rules = gcfg.rules
    da = rules.data if isinstance(rules.data, tuple) else (rules.data,)
    cp_ax = da[-1] if (cb.name == "cp" and len(da) > 1) else None
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                        batch_pspecs(batch, rules, cp_axis=cp_ax))


def jit_train_step(cfg: ModelConfig, mesh: Mesh, gcfg: GSPMDConfig,
                   opt_cfg: AdamWConfig = AdamWConfig(), lr_schedule=None):
    """``make_train_step`` jitted with the training state pinned to its FSDP
    shardings on the way in and out, and donated: parameters and AdamW
    moments are updated in place, never held twice."""
    p_sh, o_sh = train_state_shardings(cfg, mesh, gcfg)
    step = make_train_step(cfg, mesh, gcfg, opt_cfg, lr_schedule=lr_schedule)
    return jax.jit(step, in_shardings=(p_sh, o_sh, None),
                   out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))


def init_train_state(cfg: ModelConfig, mesh: Mesh, gcfg: GSPMDConfig, key):
    """(params, AdamW state) created inside jit straight into their FSDP
    shardings: no device ever holds an unsharded copy.  The values are
    those of eager ``T.init_params`` / ``adamw_init`` with the same key."""
    p_sh, o_sh = train_state_shardings(cfg, mesh, gcfg)

    def init(k):
        params = T.init_params(cfg, k, gcfg.param_dtype)
        return params, adamw_init(params)

    return jax.jit(init, out_shardings=(p_sh, o_sh))(key)


def build_train_artifacts(cfg: ModelConfig, mesh: Mesh, gcfg: GSPMDConfig,
                          batch_shapes, opt_cfg: AdamWConfig = AdamWConfig()):
    """ShapeDtypeStruct stand-ins + jitted step ready to .lower() — no
    device allocation (the dry-run path)."""
    p_sh, o_sh = train_state_shardings(cfg, mesh, gcfg)
    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, gcfg.param_dtype), jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(adamw_init, params_shape)
    b_sh = train_batch_shardings(batch_shapes, mesh, gcfg)
    stand_in = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                  sharding=sh)
    args = tuple(jax.tree.map(stand_in, shapes, sh) for shapes, sh in (
        (params_shape, p_sh), (opt_shape, o_sh), (batch_shapes, b_sh)))
    return jit_train_step(cfg, mesh, gcfg, opt_cfg), args


# ===========================================================================
# serve steps (prefill / decode)
# ===========================================================================
def _serve_act_sharder(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules,
                       *, shard_seq: bool):
    """Anchor the attention intermediates: batch over the dp axes (or the
    sequence dim for single-request long-context), heads over the model
    axis.  Without this GSPMD is free to shard the head_dim contraction,
    which turns every QK^T into a giant partial-sum all-reduce (observed:
    34 GB ARs in the gemma3 prefill baseline — see EXPERIMENTS.md §Perf)."""
    from repro.models import layers as L

    dp, mo = rules.dp_axes, rules.model
    mo_size = mesh.shape.get(mo, 1) if mo else 1

    def sharder(x, kind):
        if x.ndim != 4:
            return x
        heads = x.shape[2]
        # uneven head sharding is fine for intermediates (llama4: 40 heads
        # over a 16-wide axis).  heads < axis: leave the tensor entirely
        # unconstrained — forcing replication blocks GSPMD's (benign)
        # head_dim sharding and multiplies attention compute (measured on
        # qwen prefill: compute 0.53 → 1.95 s)
        if not (mo and heads >= mo_size):
            return x
        h = mo
        if shard_seq:
            spec = P(None, dp, h, None)
        else:
            spec = P(dp, None, h, None)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return sharder


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, gcfg: GSPMDConfig,
                      *, shard_seq: bool = False):
    """prefill(params, batch, cache) -> (last_logits, cache)."""
    from repro.models import layers as L

    sharder = _serve_act_sharder(cfg, mesh, gcfg.rules, shard_seq=shard_seq)

    def prefill(params, batch, cache):
        L.set_activation_sharder(sharder)
        try:
            logits, _, new_cache = T.apply(
                cfg, params, batch, caches=cache, cache_index=0,
                remat=False, block_kv=gcfg.block_kv,
                moe_groups=gcfg.moe_groups, last_only=True,
            )
        finally:
            L.set_activation_sharder(None)
        return logits, new_cache

    return prefill


def make_decode_step(cfg: ModelConfig, mesh: Mesh, gcfg: GSPMDConfig,
                     *, shard_seq: bool = False):
    """decode(params, cache, tokens, index) -> (logits, cache).  tokens:
    (B, 1); index: scalar position of the new token."""
    from repro.models import layers as L

    sharder = _serve_act_sharder(cfg, mesh, gcfg.rules, shard_seq=shard_seq)

    def decode(params, cache, tokens, index):
        B = tokens.shape[0]
        batch = {"tokens": tokens,
                 "positions": jnp.full((B, 1), index, jnp.int32)}
        L.set_activation_sharder(sharder)
        try:
            logits, _, new_cache = T.apply(
                cfg, params, batch, caches=cache, cache_index=index,
                remat=False, block_kv=gcfg.block_kv,
                moe_groups=gcfg.moe_groups, last_only=True,
            )
        finally:
            L.set_activation_sharder(None)
        return logits, new_cache

    return decode


def make_continuous_decode_step(cfg: ModelConfig, mesh: Mesh,
                                gcfg: GSPMDConfig, *,
                                shard_seq: bool = False):
    """decode(params, cache, tokens, index) -> (logits, cache).  tokens:
    (B, 1); index: (B,) int32 vector — slot b's new token is written at
    ``index[b]``, so the batch rows decode at unrelated positions
    (continuous batching).  With a uniform index vector this computes
    exactly what ``make_decode_step`` computes (bit-identical on the host
    backend; property-tested in tests/test_continuous_batching.py)."""
    from repro.models import layers as L

    sharder = _serve_act_sharder(cfg, mesh, gcfg.rules, shard_seq=shard_seq)

    def decode(params, cache, tokens, index):
        index = index.astype(jnp.int32)
        batch = {"tokens": tokens, "positions": index[:, None]}
        L.set_activation_sharder(sharder)
        try:
            logits, _, new_cache = T.apply(
                cfg, params, batch, caches=cache, cache_index=index,
                remat=False, block_kv=gcfg.block_kv,
                moe_groups=gcfg.moe_groups, last_only=True,
            )
        finally:
            L.set_activation_sharder(None)
        return logits, new_cache

    return decode


def build_serve_artifacts(cfg: ModelConfig, mesh: Mesh, gcfg: GSPMDConfig,
                          *, kind: str, batch: int, seq_len: int,
                          cache_dtype=jnp.float32):
    """ShapeDtypeStruct inputs + jitted fn for prefill/decode dry-runs."""
    rules = gcfg.rules
    dp_size = 1
    for a in rules.dp_axes:
        dp_size *= mesh.shape[a]
    shard_seq = batch < dp_size

    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, gcfg.param_dtype), jax.random.PRNGKey(0))
    pspecs = param_pspecs(cfg, params_shape, rules, mesh)
    params_in = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        params_shape, pspecs)

    enc_len = seq_len if (cfg.family == "audio" and kind == "decode") else 0
    cache_shape = jax.eval_shape(
        functools.partial(T.init_cache, cfg, batch, seq_len, cache_dtype,
                          enc_len=enc_len))
    cspecs = cache_pspecs(cache_shape, rules, mesh, batch_size=batch,
                          shard_seq=shard_seq)
    cache_in = jax.tree.map(
        lambda s, sp: (jax.ShapeDtypeStruct(s.shape, s.dtype,
                                            sharding=NamedSharding(mesh, sp))
                       if s is not None else None),
        cache_shape, cspecs,
        is_leaf=lambda x: x is None or isinstance(x, jax.ShapeDtypeStruct))

    bsh = None if shard_seq else rules.dp_axes
    if kind == "prefill":
        step = make_prefill_step(cfg, mesh, gcfg, shard_seq=shard_seq)
        batch_shapes = {
            "tokens": jax.ShapeDtypeStruct(
                (batch, seq_len), jnp.int32,
                sharding=NamedSharding(mesh, P(bsh, None))),
            "positions": jax.ShapeDtypeStruct(
                (batch, seq_len), jnp.int32,
                sharding=NamedSharding(mesh, P(bsh, None))),
        }
        if cfg.family == "audio":
            batch_shapes["encoder_embeds"] = jax.ShapeDtypeStruct(
                (batch, seq_len, cfg.d_model), cache_dtype,
                sharding=NamedSharding(mesh, P(bsh, None, None)))
        if cfg.frontend == "vision" and cfg.frontend_tokens:
            batch_shapes["vision_embeds"] = jax.ShapeDtypeStruct(
                (batch, cfg.frontend_tokens, cfg.d_model), cache_dtype,
                sharding=NamedSharding(mesh, P(bsh, None, None)))
        jitted = jax.jit(step, donate_argnums=(2,))
        args = (params_in, batch_shapes, cache_in)
    elif kind == "decode":
        step = make_decode_step(cfg, mesh, gcfg, shard_seq=shard_seq)
        tokens_in = jax.ShapeDtypeStruct(
            (batch, 1), jnp.int32, sharding=NamedSharding(mesh, P(bsh, None)))
        index_in = jax.ShapeDtypeStruct((), jnp.int32)
        jitted = jax.jit(step, donate_argnums=(1,))
        args = (params_in, cache_in, tokens_in, index_in)
    else:
        raise ValueError(kind)
    return jitted, args
