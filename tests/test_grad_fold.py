"""The minibatch schedule's gradient sum over microbatches, folded into the
backward of the layer scans (``transformer._fold_scan``): each layer adds
its weight gradient into its slice of the running sum, in place, in the
scan iteration that computes it.  The sum equals the outer sum it replaces,
and only the schedule that gathers the parameters once folds it."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_reduced
from repro.core import backend as B
from repro.core import gspmd
from repro.data.packing import pack_plan_to_batches
from repro.models import transformer as T
from repro.obs import metrics as obs_metrics

S = 32
# microbatches of sample indices; M = 4 holds one that is all padding
PLANS = {1: [[0, 1]], 2: [[0], [1, 2]], 4: [[0], [1, 2], [], [3]]}


def config(arch):
    """The reduced config; DeepSeek-V2-Lite with a leading dense layer and
    two expert layers (two of four experts held), so that the expert
    super-layers' scan keeps its loop."""
    if arch == "deepseek-v2-lite":
        return get_reduced(arch, num_layers=3, experts_held=2)
    return get_reduced(arch)


def microbatches(cfg, M, seed=0):
    rng = np.random.default_rng(seed)
    samples = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (14, 9, 11, 20)]
    batch = pack_plan_to_batches(PLANS[M], samples, S)
    return {k: jnp.asarray(v) for k, v in batch.items()}


def grad_core(cfg, fold_mask):
    def loss_sum(p, mb, px, pf, grad_acc=None):
        val, metrics = T.loss(cfg, p, mb, remat=True, block_kv=16,
                              pxform=px, prefetch=pf, reduction="sum",
                              grad_acc=grad_acc)
        return val, metrics["tokens"]

    return jax.jit(B.build_schedule_grad(
        "minibatch", loss_sum=loss_sum, gather_all=lambda p: p,
        fold_mask=fold_mask))


@pytest.mark.parametrize("M", [1, 2, 4])
@pytest.mark.parametrize("arch", ["qwen-1.5b", "deepseek-v2-lite"])
def test_the_folded_sum_is_the_outer_sum(arch, M):
    cfg = config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(M))
    mbs = microbatches(cfg, M)
    if M == 4:
        assert float(mbs["loss_mask"][2].sum()) == 0.0
    l0, t0, g0 = grad_core(cfg, None)(params, mbs)
    l1, t1, g1 = grad_core(cfg, lambda p: T.grad_fold_mask(cfg, p))(
        params, mbs)
    assert float(l1) == float(l0) and float(t1) == float(t0)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g0),
                            jax.tree.leaves(g1)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a).max() > 0, jax.tree_util.keystr(path)
        assert np.abs(a - b).max() <= 1e-6 * np.abs(a).max(), \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("arch,folded", [
    ("qwen-1.5b", {"layers"}),
    ("deepseek-v2-lite", {"moe"}),  # the one-layer lead stack keeps no loop
    ("mamba2-2.7b", set()),
])
def test_the_fold_takes_the_scanned_stacks(arch, folded):
    cfg = config(arch)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    mask = T.grad_fold_mask(cfg, params)
    got = set()
    for path, f in jax.tree_util.tree_leaves_with_path(mask):
        keys = [k.key for k in path]
        if f:
            got.add(keys[1] if keys[0] == "layers" and cfg.num_experts
                    else keys[0])
    assert got == folded


def test_a_one_layer_trunk_does_not_fold():
    """The compiler drops a one-trip loop, and the sum after it then fuses
    with the weight gradient already (as in the one-layer phi3m cell)."""
    cfg = get_reduced("qwen-1.5b", num_layers=1)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    assert not any(jax.tree.leaves(T.grad_fold_mask(cfg, params)))


def step_gauges(schedule, M):
    cfg = get_reduced("qwen-1.5b")
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    gcfg = gspmd.GSPMDConfig(rules=gspmd.ShardingRules(), schedule=schedule,
                             comm="odc", block_kv=32)
    shapes = {k: jax.ShapeDtypeStruct((M, 1, 64), dt) for k, dt in (
        ("tokens", jnp.int32), ("targets", jnp.int32),
        ("positions", jnp.int32), ("segment_ids", jnp.int32),
        ("loss_mask", jnp.float32))}
    jitted, args = gspmd.build_train_artifacts(cfg, mesh, gcfg, shapes)
    reg = obs_metrics.MetricsRegistry()
    with obs_metrics.recording(reg), mesh:
        jitted.lower(*args)
    layers = sum(x.size for x in jax.tree.leaves(args[0]["layers"]))
    total = sum(x.size for x in jax.tree.leaves(args[0]))
    return (reg.gauge("grad.folded_params").value,
            reg.gauge("grad.outer_summed_params").value, layers, total)


def test_the_minibatch_step_folds_every_layer_leaf():
    folded, outer, layers, total = step_gauges("minibatch", 2)
    assert folded == layers > 0
    assert outer == total - layers > 0
    # one microbatch has no sum: its gradient is the sum
    assert step_gauges("minibatch", 1)[:2] == (0, total)


@pytest.mark.parametrize("schedule", ["layer", "overlap"])
def test_the_per_layer_schedules_never_fold(schedule, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError(f"schedule={schedule!r} reached the fold")

    monkeypatch.setattr(T, "grad_fold_mask", refuse)
    monkeypatch.setattr(T, "_fold_scan", refuse)
    folded, outer, _, total = step_gauges(schedule, 2)
    assert (folded, outer) == (0, total)


def test_launch_train_streams_the_fold_gauges(tmp_path):
    """The gauges in the ``launch.train --metrics`` stream, beside
    ``data.token_slots``: on ``minibatch`` every layer leaf is folded
    (both steps run two or more microbatches), on ``layer`` none."""
    from repro.launch import train as train_mod

    cfg = get_reduced("qwen-1.5b")
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    layers = sum(x.size for x in jax.tree.leaves(params["layers"]))
    total = sum(x.size for x in jax.tree.leaves(params))
    for schedule, folded in (("minibatch", layers), ("layer", 0)):
        path = tmp_path / f"{schedule}.jsonl"
        rc = train_mod.main(["--arch", "qwen-1.5b", "--reduced",
                             "--steps", "2", "--schedule", schedule,
                             "--data-axis", "1", "--minibatch-per-device",
                             "4", "--max-tokens", "64", "--max-len", "48",
                             "--metrics", str(path)])
        assert rc == 0
        steps = [x for x in map(json.loads, path.read_text().splitlines())
                 if "metrics" in x]
        assert len(steps) == 2
        slots = 0.0
        for s in steps:
            rows = {r["name"]: r for r in s["metrics"]}
            assert rows["grad.folded_params"]["kind"] == "gauge"
            assert rows["grad.folded_params"]["value"] == folded
            assert rows["grad.outer_summed_params"]["value"] == total - folded
            # two or more microbatches of 64 token slots
            assert rows["data.token_slots"]["value"] - slots >= 2 * 64
            slots = rows["data.token_slots"]["value"]
