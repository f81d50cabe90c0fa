"""CommBackend registry (core/backend.py): alias resolution, backend
parity, and the hierarchical (node × device) backend.

Key claims:
  * legacy string flags (``comm='collective'|'odc'``, schedule knobs, sim
    ``scheme='overlap'``) resolve through the registry onto EXACTLY the ops
    the old string ladders selected — bit-identical numerics;
  * the registry's ``param_gather`` primitives match the raw odc.py
    primitives bit for bit (fwd and VJP) on every backend;
  * ``hier`` on a 2×4 (node, device) host mesh trains step-for-step
    compatibly with the flat pure-FSDP engine, and its lowered HLO shows
    the two-tier comm pattern (intra-node fused collectives + inter-node
    permute chains);
  * the simulator resolves schemes through the same registry: 'overlap'
    is an exact alias of 'odc-overlap', and 'hier' degenerates to flat
    ODC on a single node.
"""
import os
import re

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.balance import STRATEGIES
from repro.balance.cost import DeviceProfile, make_straggler_profile
from repro.configs import get_reduced
from repro.core import backend as B
from repro.core import odc
from repro.core.gspmd import GSPMDConfig, ShardingRules, make_train_step
from repro.core.gspmd import build_train_artifacts
from repro.data import sample_lengths
from repro.launch import hlo as H
from repro.launch.mesh import make_hier_mesh, make_host_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.sim import CommModel, SimConfig, simulate_minibatch

KEY = jax.random.PRNGKey(0)


# ===========================================================================
# registry resolution
# ===========================================================================
def test_registry_names_and_aliases():
    assert B.backend_names() == ("collective", "cp", "hier", "odc",
                                 "odc-overlap", "pipe", "pipe-int8")
    assert "overlap" in B.backend_names(include_aliases=True)
    assert B.get_backend("overlap") is B.get_backend("odc-overlap")
    assert B.get_backend("cp-ring") is B.get_backend("cp")
    assert B.get_backend(B.ODC) is B.ODC  # instances pass through
    with pytest.raises(ValueError, match="unknown comm backend"):
        B.get_backend("nvlink")


def test_resolve_schedule_implication():
    # legacy spelling and canonical spelling land on the same resolution
    assert B.resolve("odc", "overlap") == (B.ODC, "overlap")
    assert B.resolve("odc-overlap", "minibatch") == (B.ODC_OVERLAP, "overlap")
    assert B.resolve("overlap", "layer") == (B.ODC_OVERLAP, "overlap")
    assert B.resolve("collective", "layer") == (B.COLLECTIVE, "layer")
    assert B.resolve("pipe", "minibatch") == (B.PIPE, "1f1b")
    assert B.resolve("pipe-int8", "layer") == (B.PIPE_INT8, "1f1b")
    with pytest.raises(ValueError, match="unknown schedule"):
        B.resolve("odc", "epoch")


def test_build_schedule_grad_validation():
    with pytest.raises(ValueError, match="unknown schedule"):
        B.build_schedule_grad("epoch", loss_sum=lambda *a: (0.0, 0.0))
    with pytest.raises(ValueError, match="gather_all"):
        B.build_schedule_grad("minibatch", loss_sum=lambda *a: (0.0, 0.0))


def _mlp_loss(p, mb, px, prefetch=None):
    h = jnp.tanh(mb["x"] @ p["w1"]) @ p["w2"]
    return jnp.sum(mb["m"][:, None] * h ** 2), jnp.sum(mb["m"])


def _stack_gather(pl):
    """A stand-in for a parameter gather: a linear map onto bigger leaves,
    so the gradient has to be pulled back through it."""
    return jax.tree.map(lambda v: jnp.concatenate([v, 2.0 * v]), pl)


@pytest.mark.parametrize("M", [0, 1, 3])
def test_minibatch_schedule_is_the_gradient_of_the_summed_loss(M):
    """The minibatch loop (each microbatch's value_and_grad once, summed
    in a carry, pulled back through the gather once) gives what jax.grad
    of the plain sum of the per-microbatch losses gives."""
    rng = np.random.default_rng(M)
    params = {"w1": jnp.asarray(rng.normal(size=(4, 16)), jnp.float32),
              "w2": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)}
    mbs = {"x": jnp.asarray(rng.normal(size=(M, 5, 8)), jnp.float32),
           "m": jnp.asarray(rng.integers(0, 2, size=(M, 5)), jnp.float32)}

    def plain(pl):
        full = _stack_gather(pl)
        return jnp.float32(0.0) + sum(
            _mlp_loss(full, jax.tree.map(lambda x: x[j], mbs), None)[0]
            for j in range(M))

    lsum, tok, grads = jax.jit(B.build_schedule_grad(
        "minibatch", loss_sum=_mlp_loss, gather_all=_stack_gather,
        checkpoint_minibatch=True))(params, mbs)
    ref_l, ref_g = jax.value_and_grad(plain)(params)
    np.testing.assert_allclose(float(lsum), float(ref_l), rtol=1e-6)
    assert float(tok) == float(mbs["m"].sum())
    for k in params:
        assert grads[k].shape == params[k].shape
        ref = np.asarray(ref_g[k])  # float32 sums in another order
        np.testing.assert_allclose(np.asarray(grads[k]), ref, rtol=1e-5,
                                   atol=1e-6 * np.abs(ref).max())


@pytest.mark.parametrize("M", [1, 3])
def test_odc_minibatch_step_moves_each_parameter_once(M):
    """A 4-device ``odc`` minibatch step gathers every parameter leaf once
    and scatter-accumulates its gradient once, both outside the
    microbatch loop: one ring of world - 1 hops each, 2 x 3 x 11 = 66
    collective-permutes a step whatever M."""
    cfg = get_reduced("qwen-1.5b")
    world = 4
    mesh = Mesh(np.asarray(jax.devices()[:world]).reshape(world, 1),
                ("data", "model"))
    gcfg = GSPMDConfig(rules=ShardingRules(), schedule="minibatch",
                       comm="odc", block_kv=32)
    shapes = {k: jax.ShapeDtypeStruct((M, world, 64), dt) for k, dt in (
        ("tokens", jnp.int32), ("targets", jnp.int32),
        ("positions", jnp.int32), ("segment_ids", jnp.int32),
        ("loss_mask", jnp.float32))}
    jitted, args = build_train_artifacts(cfg, mesh, gcfg, shapes)
    text = jitted.lower(*args).compile().as_text()
    leaves = len(jax.tree.leaves(args[0]))
    assert leaves == 11
    permutes = {"comm.gather": 0, "comm.scatter": 0}
    for line in text.splitlines():
        if re.search(r" collective-permute(-start)?\(", line):
            op = re.search(r'op_name="([^"]*)"', line).group(1)
            (kind,) = [k for k in permutes if k in op]
            permutes[kind] += 1
    assert permutes == {"comm.gather": leaves, "comm.scatter": leaves}
    executed = H.analyze_hlo_text(text).coll_count["collective-permute"]
    assert executed == 2 * (world - 1) * leaves == 66


def test_sim_discipline_vocabulary():
    assert B.COLLECTIVE.discipline == "lockstep"
    assert B.ODC.discipline == "independent"
    assert B.ODC_OVERLAP.discipline == "pipelined"
    assert B.HIER.discipline == "independent"
    assert B.PIPE.discipline == "1f1b"
    assert B.PIPE_INT8.discipline == "1f1b"


# ===========================================================================
# primitive parity: registry backends run the exact pre-refactor ops
# ===========================================================================
def _shard_run(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=set(a for a in mesh.axis_names))


def test_param_gather_matches_raw_primitives_bitwise():
    """backend.param_gather == the raw odc.py primitive the old string
    ladder selected, bit for bit, fwd and VJP."""
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    x = jnp.arange(32.0) * 1.7
    prof = DeviceProfile.one_slow(8, 2.0, slow_rank=3)

    cases = [
        ("collective", lambda s: odc.collective_gather(s, "data"),
         lambda y: odc.collective_scatter(y, "data"), None),
        ("odc", lambda s: odc.ring_gather(s, "data"),
         lambda y: odc.ring_scatter_accumulate(y, "data"), None),
        ("odc", lambda s: odc.ring_gather(s, "data", device_profile=prof),
         lambda y: odc.ring_scatter_accumulate(y, "data",
                                               device_profile=prof), prof),
        ("odc-overlap", lambda s: odc.ring_gather(s, "data"),
         lambda y: odc.ring_scatter_accumulate(y, "data"), None),
    ]
    for name, raw_g, raw_s, profile in cases:
        def f(xs):
            g = B.get_backend(name).param_gather("data",
                                                 device_profile=profile)
            full, ct = g(xs), jax.grad(lambda s: (g(s) ** 2).sum() / 2)(xs)
            raw_full = raw_g(xs)
            # loss = sum(G s)^2/2 with G linear ⇒ grad = Gᵀ(G s): the
            # backward of the custom VJP must be the raw scatter of `full`
            raw_ct = raw_s(raw_full)
            return full, ct, raw_full, raw_ct

        full, ct, raw_full, raw_ct = _shard_run(
            f, mesh, (P("data"),), (P(), P("data"), P(), P("data")))(x)
        assert (full == raw_full).all(), name
        assert (ct == raw_ct).all(), name


def test_hier_gather_two_tier_semantics():
    """hier = intra collective AG + inter ring; reconstruction and VJP are
    exact on a (node=2, device=4) mesh, profile-ordered or not."""
    devs = np.asarray(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("node", "device"))
    x = jnp.arange(64.0).reshape(32, 2)
    prof = make_straggler_profile("one_slow", 8, slow_factor=3.0)

    for profile in (None, prof):
        def f(xs):
            g = B.HIER.param_gather(("node", "device"),
                                    device_profile=profile)
            full = g(xs)
            ct = jax.grad(lambda s: (g(s) ** 2).sum() / 2)(xs)
            return full, ct

        full, ct = _shard_run(f, mesh, (P(("node", "device")),),
                              (P(), P(("node", "device"))))(x)
        assert (full == x).all()
        # sum over the 8 identical per-device contributions of x_shard
        assert (ct == 8.0 * x).all()

    # single trailing axis: falls back to that tier's native collective
    def f1(xs):
        g = B.HIER.param_gather("device")
        return g(xs)

    out = _shard_run(f1, mesh, (P("device"),), P())(jnp.arange(8.0))
    assert (out == jnp.arange(8.0)).all()


def test_node_collapse():
    p = DeviceProfile(speeds=(1.0, 0.25, 1.0, 1.0, 0.5, 1.0, 1.0, 0.125),
                      comm_scale=(1, 2, 1, 1, 1, 1, 3, 1), jitter=0.5,
                      seed=7)
    n = p.node_collapse(4)
    assert n.speeds == (0.25, 0.125)
    assert n.comm_scale == (2, 3)
    assert (n.jitter, n.seed) == (0.5, 7)
    with pytest.raises(ValueError):
        p.node_collapse(3)


# ===========================================================================
# engine parity: alias spellings are bit-identical; hier matches pure FSDP
# ===========================================================================
def _mesh():
    return make_host_mesh(data=4, model=2)


def _batch(cfg, M=2, Bm=8, S=32):
    kb = jax.random.PRNGKey(1)
    return {
        "tokens": jax.random.randint(kb, (M, Bm, S), 0, cfg.vocab_size),
        "positions": jnp.tile(jnp.arange(S)[None, None], (M, Bm, 1)),
        "segment_ids": jnp.zeros((M, Bm, S), jnp.int32),
        "targets": jax.random.randint(kb, (M, Bm, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((M, Bm, S), jnp.float32),
    }


def _run_gcfg(cfg, mesh, params, batch, gcfg):
    step = make_train_step(cfg, mesh, gcfg, AdamWConfig(lr=1e-2))
    with mesh:
        newp, _, metrics = jax.jit(step)(params, adamw_init(params), batch)
    return newp, metrics


def _max_param_delta(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_alias_configs_bit_identical():
    """(comm='odc', schedule='overlap'), (comm='odc-overlap', any schedule)
    and the legacy 'overlap' spelling resolve to the same program — loss
    and updated params must be bit-identical, not merely close."""
    cfg = get_reduced("qwen-1.5b")
    mesh = _mesh()
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg)
    rules = ShardingRules()

    ref_p, ref_m = _run_gcfg(cfg, mesh, params, batch,
                             GSPMDConfig(rules=rules, schedule="overlap",
                                         comm="odc", block_kv=64))
    for gcfg in (GSPMDConfig(rules=rules, comm="odc-overlap", block_kv=64),
                 GSPMDConfig(rules=rules, schedule="layer", comm="overlap",
                             block_kv=64)):
        newp, metrics = _run_gcfg(cfg, mesh, params, batch, gcfg)
        assert float(metrics["loss"]) == float(ref_m["loss"]), gcfg.comm
        assert _max_param_delta(newp, ref_p) == 0.0, gcfg.comm


def test_hier_matches_pure_fsdp():
    """hier on a 2×4 (node, device) host mesh: same loss/params as the flat
    pure-FSDP collective baseline (fp-reordering tolerance — the two-stage
    reduction sums in a different order)."""
    cfg = get_reduced("qwen-1.5b")
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg)

    base_p, base_m = _run_gcfg(
        cfg, make_host_mesh(data=8, model=1), params, batch,
        GSPMDConfig(rules=ShardingRules(), schedule="minibatch",
                    comm="collective", block_kv=64))

    hier_mesh = make_hier_mesh(nodes=2, model=1)
    rules = ShardingRules(data=("node", "device"))
    for sched in ("minibatch", "layer"):
        newp, metrics = _run_gcfg(
            cfg, hier_mesh, params, batch,
            GSPMDConfig(rules=rules, schedule=sched, comm="hier",
                        block_kv=64))
        assert abs(float(metrics["loss"]) - float(base_m["loss"])) < 1e-5, \
            sched
        dp = _max_param_delta(newp, base_p)
        assert dp < 1e-3, (sched, dp)


def test_hier_requires_two_axes():
    cfg = get_reduced("qwen-1.5b")
    mesh = make_host_mesh(data=8, model=1)
    with pytest.raises(ValueError, match="2D mesh"):
        make_train_step(cfg, mesh,
                        GSPMDConfig(rules=ShardingRules(), comm="hier"))


def test_hier_hlo_structure():
    """Lowered hier HLO shows both tiers: fused intra-node collectives AND
    inter-node permute chains."""
    cfg = get_reduced("qwen-1.5b")
    mesh = make_hier_mesh(nodes=2, model=1)
    gcfg = GSPMDConfig(rules=ShardingRules(data=("node", "device")),
                       schedule="minibatch", comm="hier", block_kv=64)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in _batch(cfg).items()}
    jitted, args = build_train_artifacts(cfg, mesh, gcfg, batch)
    cost = H.analyze_hlo_text(jitted.lower(*args).compile().as_text())
    assert cost.coll_count["all-gather"] > 0  # intra-node tier
    assert cost.coll_count["collective-permute"] > 0  # inter-node ring
    assert cost.coll_count["reduce-scatter"] > 0  # intra-node grad tier


# ===========================================================================
# sim: scheme resolution through the same registry
# ===========================================================================
def _plan_and_lens(world, seed=0, minibs=4, max_tokens=65_536):
    lens = [min(l, max_tokens)
            for l in sample_lengths("longalign", world * minibs, seed).tolist()]
    return STRATEGIES["lb_mini"](lens, world, max_tokens), lens


def test_sim_scheme_alias_exact():
    plan, lens = _plan_and_lens(8)
    cfg = SimConfig(overlap=0.0)
    a = simulate_minibatch(plan, lens, scheme="overlap", cfg=cfg)
    b = simulate_minibatch(plan, lens, scheme="odc-overlap", cfg=cfg)
    assert a.makespan == b.makespan
    assert a.device_finish == b.device_finish


def test_sim_hier_single_node_degenerates_to_odc():
    """With the whole axis inside one node the inter ring is empty — hier
    and flat ODC are the same simulation, bit for bit."""
    plan, lens = _plan_and_lens(8)
    cfg = SimConfig(overlap=0.0, comm=CommModel(devices_per_node=8))
    h = simulate_minibatch(plan, lens, scheme="hier", cfg=cfg)
    o = simulate_minibatch(plan, lens, scheme="odc", cfg=cfg)
    assert h.makespan == o.makespan


def test_sim_hier_comm_time_bounds():
    """Multi-node per-layer comm: collective < hier < flat ODC (hier drops
    both ODC's cross-node efficiency penalty and most of its intra volume,
    but still moves whole node chunks where the hierarchical collective
    rides aggregated bandwidth)."""
    cm = CommModel()
    for d in (16, 32, 64):
        coll = B.COLLECTIVE.layer_comm_time(cm, d)
        hier = B.HIER.layer_comm_time(cm, d)
        flat = B.ODC.layer_comm_time(cm, d)
        assert coll < hier < flat, d
    # single node: all intra formulas coincide
    assert B.HIER.layer_comm_time(cm, 8) == B.ODC.layer_comm_time(cm, 8) \
        == B.COLLECTIVE.layer_comm_time(cm, 8)


def test_sim_hier_beats_collective_under_skew():
    """The acceptance cell: 4 nodes × 8 devices, one straggler at 2x —
    hier (profile-aware balancer) beats the flat collective, and matches
    flat ODC within 5% at skew 1.0."""
    world = 32
    cfg = SimConfig(overlap=0.0, comm=CommModel(devices_per_node=8))
    for f, seed in ((1.0, 0), (2.0, 0), (4.0, 1)):
        profile = make_straggler_profile("one_slow", world, slow_factor=f)
        lens = [min(l, 65_536)
                for l in sample_lengths("longalign", world * 4, seed).tolist()]
        het = STRATEGIES["lb_mini_het"](lens, world, 65_536, profile=profile)
        micro = STRATEGIES["lb_micro"](lens, world, 65_536)
        hier = simulate_minibatch(het, lens, scheme="hier", cfg=cfg,
                                  profile=profile)
        coll = simulate_minibatch(micro, lens, scheme="collective", cfg=cfg,
                                  profile=profile)
        odc_r = simulate_minibatch(het, lens, scheme="odc", cfg=cfg,
                                   profile=profile)
        assert hier.makespan <= odc_r.makespan * (1 + 1e-9), f
        if f == 1.0:
            assert abs(hier.makespan - odc_r.makespan) \
                <= 0.05 * odc_r.makespan
        if f >= 2.0:
            assert hier.makespan < coll.makespan, f


# ===========================================================================
# launcher regression: --steps 0 exits cleanly (no NameError on `loss`)
# ===========================================================================
def test_train_cli_zero_steps():
    from repro.launch.train import main
    assert main(["--arch", "qwen-1.5b", "--reduced", "--steps", "0"]) == 0
