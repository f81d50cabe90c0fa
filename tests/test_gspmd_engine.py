"""GSPMD/shard_map production-engine tests (8 host devices).

Key semantic claims tested (paper §3, Appendix F):
  * ODC (p2p comm / minibatch schedule) produces bit-comparable training
    steps to the collective FSDP baseline — the communication scheme does
    not change training semantics.
  * Dense-family distributed steps match a single-device reference.
  * The collective schedules differ exactly as designed: per-layer
    all-gather/reduce-scatter vs p2p permute chains vs once-per-minibatch.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_reduced
from repro.core.gspmd import GSPMDConfig, ShardingRules, make_train_step
from repro.core.gspmd import build_serve_artifacts, build_train_artifacts
from repro.launch import hlo as H
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init, adamw_update

KEY = jax.random.PRNGKey(0)
MODES = [("layer", "collective"), ("layer", "odc"),
         ("minibatch", "collective"), ("minibatch", "odc")]


def _mesh():
    # TP + FSDP: the schedule/comm semantics under test live entirely on
    # the data axis; the model axis checks they compose with GSPMD TP
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


# tier-1 keeps one dense family (gemma2); the rest run in the CI full job
@pytest.mark.parametrize("arch", [
    "gemma2-9b",
    pytest.param("mamba2-2.7b", marks=pytest.mark.slow),
    pytest.param("zamba2-1.2b", marks=pytest.mark.slow),
    pytest.param("seamless-m4t-medium", marks=pytest.mark.slow),
])
def test_dense_families_match_single_device_reference(arch):
    cfg = get_reduced(arch)
    mesh = _mesh()
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg)
    M = batch["tokens"].shape[0]

    def ref_loss(p):
        tot, tok = jnp.float32(0), jnp.float32(0)
        for m in range(M):
            mb = jax.tree.map(lambda x: x[m], batch)
            l, met = T.loss(cfg, p, mb, reduction="sum", block_kv=64)
            tot, tok = tot + l, tok + met["tokens"]
        return tot / tok

    ref_l = ref_loss(params)
    ref_g = jax.grad(ref_loss)(params)
    ref_p, _ = adamw_update(AdamWConfig(lr=1e-2), params, ref_g,
                            adamw_init(params))
    for sched, comm in MODES:
        newp, metrics = _run_mode(cfg, mesh, params, batch, sched, comm)
        assert abs(float(metrics["loss"]) - float(ref_l)) < 1e-4, (sched, comm)
        dp = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(newp),
                                 jax.tree.leaves(ref_p)))
        assert dp < 2e-3, (sched, comm, dp)


@pytest.mark.parametrize("arch", [
    pytest.param("llama4-maverick-400b-a17b", marks=pytest.mark.slow),
    "grok-1-314b",
])
def test_odc_matches_collective_baseline_moe(arch):
    """The paper's semantic claim: ODC == collective FSDP, step for step.
    (MoE capacity dropping depends on the device-local dispatch groups, so
    the distributed runs are compared against each other, not against an
    8-way-batched single-device run.)"""
    cfg = get_reduced(arch)
    mesh = _mesh()
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg)
    base_p, base_m = _run_mode(cfg, mesh, params, batch, "layer", "collective")
    for sched, comm in MODES[1:]:
        newp, metrics = _run_mode(cfg, mesh, params, batch, sched, comm)
        assert abs(float(metrics["loss"]) - float(base_m["loss"])) < 1e-5
        dp = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(newp),
                                 jax.tree.leaves(base_p)))
        assert dp < 1e-3, (sched, comm, dp)


def test_collective_schedule_structure():
    """Lowered HLO must show the designed communication schedules."""
    cfg = get_reduced("gemma2-9b")
    mesh = _mesh()

    def counts(sched, comm):
        gcfg = GSPMDConfig(rules=ShardingRules(), schedule=sched, comm=comm,
                           block_kv=64)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in _batch(cfg).items()}
        jitted, args = build_train_artifacts(cfg, mesh, gcfg, batch)
        cost = H.analyze_hlo_text(jitted.lower(*args).compile().as_text())
        return cost

    lc = counts("layer", "collective")
    lo = counts("layer", "odc")
    mc = counts("minibatch", "collective")
    # baseline: all-gathers + reduce-scatters present
    assert lc.coll_count["all-gather"] > 0
    assert lc.coll_count["reduce-scatter"] > 0
    # ODC comm: p2p permutes replace the fused collectives entirely
    assert lo.coll_count["all-gather"] == 0
    assert lo.coll_count["reduce-scatter"] == 0
    assert lo.coll_count["collective-permute"] > 0
    # minibatch schedule: strictly fewer sync points than per-layer
    assert (mc.coll_count["all-gather"] + mc.coll_count["reduce-scatter"]
            < lc.coll_count["all-gather"] + lc.coll_count["reduce-scatter"])
    # identical total p2p volume claim (paper Table 2): ODC moves the same
    # order of bytes as the collective it replaces (ring AG == p2p chain).
    # HLO cost accounting counts each of the n-1 ring hops separately while
    # the fused op is counted once, so the bound is mesh-width-dependent:
    # ~1.1x at data=4, up to ~2x at data=8 (pure-FSDP fallback mesh).
    assert lo.total_coll_bytes <= 2.2 * lc.total_coll_bytes


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serve_artifacts_lower(arch):
    cfg = get_reduced(arch)
    mesh = _mesh()
    gcfg = GSPMDConfig(rules=ShardingRules(), block_kv=64)
    for kind, B, S in [("prefill", 8, 128), ("decode", 8, 128),
                       ("decode", 1, 256)]:
        jitted, args = build_serve_artifacts(cfg, mesh, gcfg, kind=kind,
                                             batch=B, seq_len=S)
        assert jitted.lower(*args).compile() is not None


def test_multipod_flat_and_hybrid_lower():
    cfg = get_reduced("gemma2-9b")
    mesh = make_host_mesh(data=2, model=2, pod=2)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in _batch(cfg).items()}
    for rules, hyb in [
        (ShardingRules(data=("pod", "data"), model="model", pod=None), False),
        (ShardingRules(data="data", model="model", pod="pod"), True),
    ]:
        gcfg = GSPMDConfig(rules=rules, schedule="minibatch", comm="odc",
                           hybrid_pod=hyb, block_kv=64)
        jitted, args = build_train_artifacts(cfg, mesh, gcfg, batch)
        assert jitted.lower(*args).compile() is not None


# ===========================================================================
# the training driver's state: sharded init + donated steps
# ===========================================================================
def test_sharded_init_and_donated_steps_match_eager():
    """``launch.train`` creates the state inside jit straight into its FSDP
    shardings and donates it to every step: the parameters must equal
    eager ``T.init_params`` bitwise, and two donated steps must give the
    losses of the former path (eager init, plain jit, no donation)."""
    from repro.core.gspmd import (init_train_state, jit_train_step,
                                  train_batch_shardings,
                                  train_state_shardings)

    cfg = get_reduced("qwen-1.5b")
    mesh = make_host_mesh(data=8, model=1)
    gcfg = GSPMDConfig(rules=ShardingRules(), schedule="minibatch",
                       comm="odc", block_kv=64)
    opt_cfg = AdamWConfig(lr=1e-3)

    params, opt = init_train_state(cfg, mesh, gcfg, KEY)
    eager = T.init_params(cfg, KEY)
    p_sh, o_sh = train_state_shardings(cfg, mesh, gcfg)
    for got, want, sh in zip(jax.tree.leaves(params), jax.tree.leaves(eager),
                             jax.tree.leaves(p_sh)):
        assert got.sharding == sh
        assert bool((got == want).all())
    embed = params["embed"]  # sharded: each device holds 1/8 of it
    assert {s.data.shape for s in embed.addressable_shards} == \
        {(embed.shape[0], embed.shape[1] // 8)}
    for got, want in zip(jax.tree.leaves(opt),
                         jax.tree.leaves(adamw_init(eager))):
        assert bool((got == want).all())

    old_step = jax.jit(make_train_step(cfg, mesh, gcfg, opt_cfg))
    new_step = jit_train_step(cfg, mesh, gcfg, opt_cfg)
    old = (eager, adamw_init(eager))
    new = (params, opt)
    for i in range(2):
        batch = _batch(cfg, M=2, Bm=8, S=32)
        batch["tokens"] = (batch["tokens"] + i) % cfg.vocab_size
        with mesh:
            *old, m_old = old_step(*old, batch)
            *new, m_new = new_step(
                *new, jax.device_put(
                    batch, train_batch_shardings(batch, mesh, gcfg)))
        assert float(m_new["loss"]) == float(m_old["loss"]), i
    assert embed.is_deleted()  # donated to the first step
    for got, sh in zip(jax.tree.leaves(new[1]), jax.tree.leaves(o_sh)):
        assert got.sharding == sh


def test_roofline_peaks_are_keyed_by_device_kind():
    assert H.peak_rates("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(ValueError, match="no published peak rates"):
        H.peak_rates("cpu")  # an unknown device is an error, not a default
