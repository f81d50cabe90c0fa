"""Weight-stationary expert parallelism (moe_ep='data') — the §Perf
beyond-paper optimization: expert weights stay sharded on the FSDP axis,
tokens all_to_all to them.  Must be numerically identical to the FSDP
gather baseline and must replace expert all-gathers with all-to-alls."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.core.gspmd import (GSPMDConfig, ShardingRules, make_train_step,
                              moe_ep_data_axis, param_pspecs)
from repro.launch import hlo as H
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init

KEY = jax.random.PRNGKey(0)


def _setup(arch):
    # big capacity factor: no token drops, so dispatch layouts can't change
    # numerics between the baseline and EP paths
    cfg = dataclasses.replace(get_reduced(arch), moe_capacity_factor=8.0)
    mesh = make_host_mesh(data=4, model=2)
    params = T.init_params(cfg, KEY)
    M, Bm, S = 2, 8, 32
    kb = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(kb, (M, Bm, S), 0, cfg.vocab_size),
        "positions": jnp.tile(jnp.arange(S)[None, None], (M, Bm, 1)),
        "segment_ids": jnp.zeros((M, Bm, S), jnp.int32),
        "targets": jax.random.randint(kb, (M, Bm, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((M, Bm, S), jnp.float32),
    }
    if cfg.frontend == "vision" and cfg.frontend_tokens:
        batch["vision_embeds"] = jax.random.normal(
            kb, (M, Bm, cfg.frontend_tokens, cfg.d_model))
    return cfg, mesh, params, batch


def _run(cfg, mesh, params, batch, moe_ep, schedule="layer"):
    gcfg = GSPMDConfig(rules=ShardingRules(), schedule=schedule,
                       comm="collective", moe_ep=moe_ep, block_kv=64)
    step = make_train_step(cfg, mesh, gcfg, AdamWConfig(lr=1e-2))
    with mesh:
        jstep = jax.jit(step)
        newp, _, metrics = jstep(params, adamw_init(params), batch)
        hlo = jstep.lower(params, adamw_init(params), batch).compile().as_text()
    return newp, float(metrics["loss"]), H.analyze_hlo_text(hlo)


# tier-1 keeps one (schedule, arch) cell; the rest run in the CI full job
@pytest.mark.parametrize("schedule,arch", [
    ("layer", "grok-1-314b"),
    pytest.param("minibatch", "grok-1-314b", marks=pytest.mark.slow),
    pytest.param("layer", "llama4-maverick-400b-a17b",
                 marks=pytest.mark.slow),
    pytest.param("minibatch", "llama4-maverick-400b-a17b",
                 marks=pytest.mark.slow),
])
def test_ep_data_matches_baseline(arch, schedule):
    cfg, mesh, params, batch = _setup(arch)
    p0, l0, _ = _run(cfg, mesh, params, batch, "none", schedule)
    p1, l1, c1 = _run(cfg, mesh, params, batch, "data", schedule)
    assert abs(l0 - l1) < 1e-5
    dp = max(float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)))
    assert dp < 2e-3, dp
    # EP dispatch must appear in the HLO
    assert c1.coll_count["all-to-all"] > 0


def test_ep_data_axis_resolution():
    """E=4 divides data=4 on the host mesh; production grok (E=8, data=16)
    must fall back to None."""
    mesh = make_host_mesh(data=4, model=2)
    cfg = get_reduced("llama4-maverick-400b-a17b")  # reduced E=4
    assert moe_ep_data_axis(cfg, ShardingRules(), mesh, "data") == "data"
    assert moe_ep_data_axis(cfg, ShardingRules(), mesh, "none") is None
    big = get_reduced("llama4-maverick-400b-a17b", num_experts=6)
    assert moe_ep_data_axis(big, ShardingRules(), mesh, "data") is None


def test_ep_specs_keep_experts_sharded_on_data():
    mesh = make_host_mesh(data=4, model=2)
    cfg = get_reduced("llama4-maverick-400b-a17b")
    params = jax.eval_shape(lambda k: T.init_params(cfg, k), KEY)
    specs = param_pspecs(cfg, params, ShardingRules(), mesh, moe_ep="data")
    flat = {"/".join(str(k.key) for k in p if hasattr(k, "key")): s
            for p, s in jax.tree_util.tree_flatten_with_path(specs)[0]}
    s = flat["layers/moe/moe/w_up"]
    assert s[1] == "data"  # stacked: (layer, E, d, f) -> E over data
