"""schedule='overlap' (double-buffered ODC prefetch) — semantics + timing.

The overlap schedule reorders communication issue (gather layer l+1 under
layer l's compute; scatter layer l under layer l-1's backward) but runs
the SAME gathers and scatter-accumulates as the other schedules, so:

  * loss and updated params must match schedule='minibatch' step for step
    (within fp reordering tolerance) on every architecture family — dense,
    MoE super-layers, SSM, hybrid and audio exercise every prefetch-slice
    shape the spec registry has to resolve;
  * the lowered HLO must show the ODC comm pattern (p2p permutes, no fused
    all-gather/reduce-scatter) when comm='odc';
  * the simulator's overlap makespan is never worse than plain ODC and
    never better than pure compute, on imbalanced LB-Mini plans.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import pytest

from repro.balance import STRATEGIES
from repro.configs import get_reduced
from repro.core.gspmd import GSPMDConfig, ShardingRules, make_train_step
from repro.core.gspmd import build_train_artifacts
from repro.data import sample_lengths
from repro.launch import hlo as H
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.sim import SimConfig, simulate_minibatch

KEY = jax.random.PRNGKey(0)


def _mesh():
    return make_host_mesh(data=4, model=2)


def _batch(cfg, M=2, Bm=8, S=32):
    kb = jax.random.PRNGKey(1)
    b = {
        "tokens": jax.random.randint(kb, (M, Bm, S), 0, cfg.vocab_size),
        "positions": jnp.tile(jnp.arange(S)[None, None], (M, Bm, 1)),
        "segment_ids": jnp.zeros((M, Bm, S), jnp.int32),
        "targets": jax.random.randint(kb, (M, Bm, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((M, Bm, S), jnp.float32),
    }
    if cfg.family == "audio":
        b["encoder_embeds"] = jax.random.normal(kb, (M, Bm, 16, cfg.d_model))
    if cfg.frontend == "vision" and cfg.frontend_tokens:
        b["vision_embeds"] = jax.random.normal(
            kb, (M, Bm, cfg.frontend_tokens, cfg.d_model))
    return b


def _run_mode(cfg, mesh, params, batch, sched, comm):
    gcfg = GSPMDConfig(rules=ShardingRules(), schedule=sched, comm=comm,
                       block_kv=64)
    step = make_train_step(cfg, mesh, gcfg, AdamWConfig(lr=1e-2))
    with mesh:
        newp, _, metrics = jax.jit(step)(params, adamw_init(params), batch)
    return newp, metrics


def _max_param_delta(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# one arch per architecture family: every prefetch-slice shape (flat layer,
# MoE super-layer with dense sub-stack + experts, mamba stack, hybrid
# (n_super, P) super-layer + tail, enc/dec with cross-attention)
# tier-1 keeps the dense representative; the other four families run in
# the CI full job
FAMILY_ARCHS = ["qwen-1.5b", "llama4-maverick-400b-a17b", "mamba2-2.7b",
                "zamba2-1.2b", "seamless-m4t-medium"]
_PARAMS = [a if a == "qwen-1.5b" else pytest.param(a, marks=pytest.mark.slow)
           for a in FAMILY_ARCHS]


@pytest.mark.parametrize("arch", _PARAMS)
def test_overlap_matches_minibatch(arch):
    cfg = get_reduced(arch)
    mesh = _mesh()
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg)
    base_p, base_m = _run_mode(cfg, mesh, params, batch,
                               "minibatch", "collective")
    for comm in ("collective", "odc"):
        newp, metrics = _run_mode(cfg, mesh, params, batch, "overlap", comm)
        assert abs(float(metrics["loss"]) - float(base_m["loss"])) < 1e-5, \
            (arch, comm)
        dp = _max_param_delta(newp, base_p)
        assert dp < 1e-3, (arch, comm, dp)


def test_overlap_odc_hlo_structure():
    """overlap + odc: pure p2p comm — permute chains, no fused AG/RS."""
    cfg = get_reduced("qwen-1.5b")
    mesh = _mesh()
    gcfg = GSPMDConfig(rules=ShardingRules(), schedule="overlap", comm="odc",
                       block_kv=64)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in _batch(cfg).items()}
    jitted, args = build_train_artifacts(cfg, mesh, gcfg, batch)
    cost = H.analyze_hlo_text(jitted.lower(*args).compile().as_text())
    assert cost.coll_count["all-gather"] == 0
    assert cost.coll_count["reduce-scatter"] == 0
    assert cost.coll_count["collective-permute"] > 0


def test_sim_overlap_dominates_odc_on_imbalanced_plans():
    """On every imbalanced LB-Mini plan: busy <= overlap <= odc <=
    collective(LB-Micro) with fully-exposed comm."""
    cfg = SimConfig(overlap=0.0)
    world, max_tokens = 8, 65_536
    checked = 0
    for ds in ("longalign", "swesmith"):
        for seed in range(10):
            lens = [min(l, max_tokens)
                    for l in sample_lengths(ds, world * 8, seed).tolist()]
            plan = STRATEGIES["lb_mini"](lens, world, max_tokens)
            if plan.uniform_microbatches():
                continue  # only imbalanced plans are interesting
            ov = simulate_minibatch(plan, lens, scheme="overlap", cfg=cfg)
            od = simulate_minibatch(plan, lens, scheme="odc", cfg=cfg)
            assert ov.makespan <= od.makespan * (1 + 1e-12), (ds, seed)
            assert ov.makespan >= max(ov.device_busy) - 1e-12, (ds, seed)
            checked += 1
    assert checked > 0, "no imbalanced plans sampled — widen the sweep"


def test_sim_overlap_ties_odc_without_exposed_comm():
    """With the exogenous hidden fraction at 1.0 (default config) there is
    no exposed comm left to hide — the schedules must tie exactly."""
    lens = [min(l, 65_536)
            for l in sample_lengths("longalign", 64, 0).tolist()]
    plan = STRATEGIES["lb_mini"](lens, 8, 65_536)
    cfg = SimConfig()
    ov = simulate_minibatch(plan, lens, scheme="overlap", cfg=cfg)
    od = simulate_minibatch(plan, lens, scheme="odc", cfg=cfg)
    assert ov.makespan == od.makespan
