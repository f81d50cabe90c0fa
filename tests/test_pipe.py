"""Pipeline-parallel ``pipe`` backend: 1F1B schedule, compressed chunked-
int8 p2p wire, and executable/sim parity.

Key claims:

  * ``instructions_1f1b`` / ``stage_partition`` / ``PipelineStagePolicy``
    reproduce the textbook 1F1B shape: uniform zero-comm makespan is
    exactly ``(M + S - 1) * (f + b)`` and S=1 degenerates to the serial
    sum;
  * the executable '1f1b' gradient schedule computes the SAME gradients
    as the 'minibatch' schedule (the in-flight window only reorders
    work), for any stage count and the interleaved variant;
  * with compression OFF the pipe transports are bit-exact equal to the
    hier transports they compose (the fp32 fallback contract), and a
    pipe training step matches the flat collective baseline to fp
    reordering;
  * the chunked-int8 wire: per-element error ≤ absmax(chunk)/254 (the
    documented bound), zeros round-trip exactly, the local shard lands
    exactly, and the Pallas q8 kernels match the jnp oracles;
  * the quantized loss trajectory stays within the documented bound of
    fp32 (|Δloss| < 1e-2 on the reduced config);
  * ``scheme='pipe'`` reads off the shared timeline engine with
    lockstep-shaped blocks, and int8 strictly shrinks both the modeled
    per-layer wire time and the end-to-end makespan whenever comm is
    exposed.
"""
import math
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.balance import STRATEGIES
from repro.configs import get_reduced
from repro.core import backend as B
from repro.core import odc
from repro.core.gspmd import GSPMDConfig, ShardingRules, make_train_step
from repro.data import sample_lengths
from repro.kernels import ops
from repro.launch.mesh import make_hier_mesh, make_host_mesh, make_pipe_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.sim import (
    CommModel,
    PIPE_1F1B,
    SimConfig,
    get_policy,
    instructions_1f1b,
    simulate_minibatch,
    stage_partition,
)

KEY = jax.random.PRNGKey(0)


def _shard_run(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=set(mesh.axis_names))


# ===========================================================================
# 1F1B schedule primitives
# ===========================================================================
def test_stage_partition():
    assert stage_partition(24, 5) == [5, 5, 5, 5, 4]
    assert stage_partition(8, 2) == [4, 4]
    assert stage_partition(3, 5) == [1, 1, 1, 0, 0]
    assert stage_partition(0, 3) == [0, 0, 0]
    with pytest.raises(ValueError):
        stage_partition(4, 0)
    with pytest.raises(ValueError):
        stage_partition(-1, 2)


@pytest.mark.parametrize("S,M", [(1, 4), (2, 2), (3, 4), (4, 3), (4, 1)])
def test_instructions_1f1b_structure(S, M):
    for s in range(S):
        order = instructions_1f1b(M, S, stage=s)
        fwd = [j for op, j in order if op == "F"]
        bwd = [j for op, j in order if op == "B"]
        assert fwd == list(range(M)) and bwd == list(range(M))
        # every backward is preceded by its own forward
        seen = set()
        for op, j in order:
            if op == "F":
                seen.add(j)
            else:
                assert j in seen
        # warmup depth: S-1-s forwards before the first backward
        # (capped at M when the pipeline never fills)
        w = min(S - 1 - s, M)
        head = [op for op, _ in order[:w]]
        assert head == ["F"] * w
        if M > w:
            assert order[w][0] == "F" and order[w + 1][0] == "B"


def test_instructions_1f1b_interleave_halves_warmup():
    plain = instructions_1f1b(6, 4, stage=0)
    inter = instructions_1f1b(6, 4, stage=0, interleave=True)
    depth = lambda o: next(i for i, (op, _) in enumerate(o) if op == "B")
    assert depth(plain) == 3 + 1  # w forwards, first B at index w...
    assert depth(inter) < depth(plain)
    with pytest.raises(ValueError):
        instructions_1f1b(4, 2, stage=2)
    with pytest.raises(ValueError):
        instructions_1f1b(4, 0)


def test_1f1b_policy_registered():
    assert get_policy("1f1b") is PIPE_1F1B
    assert B.PIPE.policy is PIPE_1F1B
    assert B.PIPE_INT8.policy is PIPE_1F1B


def test_1f1b_uniform_makespan_analytic():
    """Uniform microbatches, zero comm: makespan = (M + S - 1)(f + b)."""
    t, L = 3.0, 8
    for S, per_dev in ((2, 2), (4, 1), (4, 3)):
        times = [[t] * per_dev for _ in range(S)]
        M = S * per_dev
        mk, blocks = PIPE_1F1B.step_blocks(times, [0.0] * S, L)
        per_mb = t / S  # f + b of one stage's slice (f = 1/3, b = 2/3)
        assert mk == pytest.approx((M + S - 1) * per_mb)
        assert len(blocks) == S
        for total, segs in blocks:  # lockstep-shaped: all lanes span mk
            assert total == pytest.approx(mk)


def test_1f1b_single_stage_is_serial():
    mk, blocks = PIPE_1F1B.step_blocks([[2.0, 4.0]], [0.0], 4)
    assert mk == pytest.approx(6.0)  # no pipeline: plain serial sum
    assert all(kind != "barrier" for kind, _, _ in blocks[0][1])


# ===========================================================================
# simulator integration
# ===========================================================================
def _plan(world=8, n=64, seed=0):
    lens = sample_lengths("longalign", n, seed=seed)
    return STRATEGIES["lb_mini"](lens, world, 65_536), lens


def test_sim_pipe_scheme_lockstep_shaped():
    plan, lens = _plan()
    r = simulate_minibatch(plan, lens, scheme="pipe", cfg=SimConfig())
    assert r.makespan > 0
    # the 1F1B drain barrier squares every lane off at the makespan
    assert max(r.device_finish) == pytest.approx(min(r.device_finish))
    assert max(r.device_finish) == pytest.approx(r.makespan)


def test_sim_pipe_int8_strictly_faster_when_comm_exposed():
    plan, lens = _plan()
    for overlap in (0.0, 0.5):
        cfg = SimConfig(overlap=overlap)
        fp = simulate_minibatch(plan, lens, scheme="pipe", cfg=cfg)
        q8 = simulate_minibatch(plan, lens, scheme="pipe-int8", cfg=cfg)
        assert q8.makespan < fp.makespan, overlap
    # fully-hidden comm: compression cannot help, the schemes tie
    cfg = SimConfig(overlap=1.0)
    fp = simulate_minibatch(plan, lens, scheme="pipe", cfg=cfg)
    q8 = simulate_minibatch(plan, lens, scheme="pipe-int8", cfg=cfg)
    assert q8.makespan == fp.makespan


def test_layer_comm_time_int8_strictly_smaller():
    cm = CommModel()
    for d in (2, 4, 8, 64):
        fp = B.PIPE.layer_comm_time(cm, d)
        q8 = B.PIPE_INT8.layer_comm_time(cm, d)
        assert 0.0 < q8 < fp, d
    assert B.PIPE.layer_comm_time(cm, 1) == 0.0
    assert B.PIPE_INT8.layer_comm_time(cm, 1) == 0.0


def test_weight_push_time_int8_wins_multi_node():
    cm = CommModel()
    assert B.PIPE.weight_push_time(cm, 16, 0) == 0.0
    g = cm.devices_per_node
    # single node: no inter wire, nothing to compress
    assert (B.PIPE_INT8.weight_push_time(cm, g, 24)
            == B.PIPE.weight_push_time(cm, g, 24))
    for d in (2 * g, 8 * g):
        fp = B.PIPE.weight_push_time(cm, d, 24)
        q8 = B.PIPE_INT8.weight_push_time(cm, d, 24)
        assert 0.0 < q8 < fp, d


# ===========================================================================
# chunked-int8 wire: error bound + transports + kernels
# ===========================================================================
def test_quantization_error_bound():
    """Per element: |x - dequant(quantize(x))| <= absmax(chunk) / 254."""
    rng = np.random.default_rng(0)
    for shape in ((7,), (3, 97), (2, 256), (5, 4, 33)):
        x = jnp.asarray((rng.normal(size=shape) * 10).astype(np.float32))
        q, s = odc.quantize_chunked(x)
        y = odc.dequantize_chunked(q, s, x.shape)
        flat = x.reshape(-1)
        pad = (-flat.size) % odc.INT8_CHUNK
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, odc.INT8_CHUNK)
        bound = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 254.0
        err = jnp.abs(jnp.pad((y - x).reshape(-1), (0, pad))
                      ).reshape(-1, odc.INT8_CHUNK)
        assert bool((err <= bound + 1e-7).all()), shape


def test_quantization_zeros_round_trip_exactly():
    z = jnp.zeros((300,), jnp.float32)
    q, s = odc.quantize_chunked(z)
    assert bool((s == 1.0).all())
    assert bool((odc.dequantize_chunked(q, s, z.shape) == z).all())


def test_codec_kernels_bit_exact_vs_oracle():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(3, 97)).astype(np.float32))
    q, s = ops.quantize_int8(x)
    qr, sr = odc.quantize_chunked(x)
    assert bool((q == qr).all()) and bool((s == sr).all())
    y = ops.dequantize_int8(q, s, x.shape)
    yr = odc.dequantize_chunked(qr, sr, x.shape)
    assert bool((y == yr).all())


def test_ring_gather_q8_own_shard_exact_and_bounded():
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    n = len(jax.devices())
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.normal(size=(2 * n, 5)).astype(np.float32))

    def f(x):
        full = odc.ring_gather_q8(x, "data")
        me = jax.lax.axis_index("data")
        own = jax.lax.dynamic_slice_in_dim(full, me * x.shape[0],
                                           x.shape[0], 0)
        return full, (own == x).all()[None]

    full, own_ok = _shard_run(f, mesh, (P("data"),), (P("data"), P("data")))(xs)
    assert bool(own_ok.all())  # the local shard is never quantized
    ref = _shard_run(lambda x: odc.ring_gather(x, "data"), mesh,
                     (P("data"),), P("data"))(xs)
    bound = float(jnp.max(jnp.abs(xs))) / 254.0
    assert float(jnp.max(jnp.abs(full - ref))) <= bound + 1e-7


def test_ring_scatter_q8_error_compounds_at_most_n_hops():
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    n = len(jax.devices())
    rng = np.random.default_rng(3)
    ys = jnp.asarray(rng.normal(size=(4 * n, 6)).astype(np.float32))
    q8 = _shard_run(lambda y: odc.ring_scatter_accumulate_q8(y, "data"),
                    mesh, (P(None),), P("data"))(ys)
    fp = _shard_run(lambda y: odc.ring_scatter_accumulate(y, "data"),
                    mesh, (P(None),), P("data"))(ys)
    # each of the n-1 hops requantizes a partial sum whose magnitude is at
    # most the sum of |y| over devices — a loose but airtight bound
    per_hop = float(jnp.max(jnp.abs(ys))) * n / 254.0
    assert float(jnp.max(jnp.abs(q8 - fp))) <= (n - 1) * per_hop


def test_q8_kernels_match_jnp_oracles():
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    rng = np.random.default_rng(4)
    xs = jnp.asarray(rng.normal(size=(16, 5, 7)).astype(np.float32))
    k = _shard_run(lambda t: ops.odc_gather_q8(t, "data"), mesh,
                   (P("data"),), P("data"))(xs)
    r = _shard_run(lambda t: odc.ring_gather_q8(t, "data"), mesh,
                   (P("data"),), P("data"))(xs)
    np.testing.assert_allclose(np.asarray(k), np.asarray(r), atol=1e-6)

    ys = jnp.asarray(rng.normal(size=(16, 6)).astype(np.float32))
    k2 = _shard_run(lambda t: ops.odc_scatter_accumulate_q8(t, "data"),
                    mesh, (P(None),), P("data"))(ys)
    r2 = _shard_run(lambda t: odc.ring_scatter_accumulate_q8(t, "data"),
                    mesh, (P(None),), P("data"))(ys)
    assert bool((k2 == r2).all())  # same hop order, same adds: bit-exact


def test_backend_kernel_hooks_route_by_compression():
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    rng = np.random.default_rng(5)
    xs = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
    kq = _shard_run(lambda t: B.PIPE_INT8.kernel_gather(t, "data"), mesh,
                    (P("data"),), P("data"))(xs)
    rq = _shard_run(lambda t: odc.ring_gather_q8(t, "data"), mesh,
                    (P("data"),), P("data"))(xs)
    np.testing.assert_allclose(np.asarray(kq), np.asarray(rq), atol=1e-6)
    kf = _shard_run(lambda t: B.PIPE.kernel_gather(t, "data"), mesh,
                    (P("data"),), P("data"))(xs)
    rf = _shard_run(lambda t: odc.ring_gather(t, "data"), mesh,
                    (P("data"),), P("data"))(xs)
    assert bool((kf == rf).all())


def test_pipe_transports_bit_exact_vs_hier_when_uncompressed():
    """Compression off ⇒ the pipe gather/scatter are byte-for-byte the
    hier two-tier transports (the fp32 fallback contract)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("pipe", "data"))
    xs = jnp.arange(16.0).reshape(8, 2) * 1.3

    def g(x):
        a = B.PIPE.gather(x, ("pipe", "data"))
        b = B.HIER.gather(x, ("pipe", "data"))
        return a, b

    a, b = _shard_run(g, mesh, (P(("pipe", "data")),), (P(), P()))(xs)
    assert bool((a == b).all())

    ys = jnp.arange(32.0).reshape(16, 2)

    def s(y):
        a = B.PIPE.scatter_accumulate(y, ("pipe", "data"))
        b = B.HIER.scatter_accumulate(y, ("pipe", "data"))
        return a, b

    a, b = _shard_run(s, mesh, (P(None),),
                      (P(("pipe", "data")), P(("pipe", "data"))))(ys)
    assert bool((a == b).all())


# ===========================================================================
# executable 1F1B gradient schedule
# ===========================================================================
def _toy_loss(p, mb, px, prefetch=None):
    v = jnp.sum((p["w"] * mb["x"]) ** 2)
    return v, jnp.float32(mb["x"].size)


def test_build_schedule_grad_1f1b_validation():
    with pytest.raises(ValueError, match="gather_all"):
        B.build_schedule_grad("1f1b", loss_sum=_toy_loss)
    with pytest.raises(ValueError, match="pipe_stages"):
        B.build_schedule_grad("1f1b", loss_sum=_toy_loss,
                              gather_all=lambda p: p, pipe_stages=0)


@pytest.mark.parametrize("stages,interleave",
                         [(1, False), (2, False), (3, False), (8, False),
                          (2, True), (4, True)])
def test_1f1b_grads_match_minibatch_schedule(stages, interleave):
    """The in-flight 1F1B window only reorders the per-microbatch VJPs —
    loss, token count, and gradients must match the minibatch schedule."""
    params = {"w": jnp.asarray([1.0, -2.0, 0.5])}
    mbs = {"x": jnp.asarray(np.random.default_rng(6).normal(
        size=(4, 3)).astype(np.float32))}
    ref = B.build_schedule_grad("minibatch", loss_sum=_toy_loss,
                                gather_all=lambda p: p)(params, mbs)
    got = B.build_schedule_grad("1f1b", loss_sum=_toy_loss,
                                gather_all=lambda p: p,
                                pipe_stages=stages,
                                pipe_interleave=interleave)(params, mbs)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_1f1b_zero_microbatches_yields_zero_grads():
    params = {"w": jnp.asarray([1.0, 2.0])}
    mbs = {"x": jnp.zeros((0, 2), jnp.float32)}
    lsum, tok, grads = B.build_schedule_grad(
        "1f1b", loss_sum=_toy_loss, gather_all=lambda p: p,
        pipe_stages=2)(params, mbs)
    assert float(lsum) == 0.0 and float(tok) == 0.0
    assert bool((grads["w"] == 0.0).all())


# ===========================================================================
# end-to-end GSPMD engine
# ===========================================================================
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


def test_pipe_requires_two_axes():
    cfg = get_reduced("qwen-1.5b")
    mesh = make_host_mesh(data=8, model=1)
    with pytest.raises(ValueError, match="2D mesh"):
        make_train_step(cfg, mesh,
                        GSPMDConfig(rules=ShardingRules(), comm="pipe"))


def test_pipe_matches_collective_and_int8_within_bound():
    """fp32 pipe matches the flat collective baseline to fp reordering;
    pipe-int8's loss stays within the DOCUMENTED quantization bound
    (|Δloss| < 1e-2 on the reduced config); the interleaved variant sums
    the same terms."""
    cfg = get_reduced("qwen-1.5b")
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg)

    base_p, base_m = _run_gcfg(
        cfg, make_host_mesh(data=8, model=1), params, batch,
        GSPMDConfig(rules=ShardingRules(), schedule="minibatch",
                    comm="collective", block_kv=64))

    mesh = make_pipe_mesh(stages=2, model=1)
    rules = ShardingRules(data=("pipe", "data"))
    pipe_p, pipe_m = _run_gcfg(
        cfg, mesh, params, batch,
        GSPMDConfig(rules=rules, comm="pipe", block_kv=64))
    assert abs(float(pipe_m["loss"]) - float(base_m["loss"])) < 1e-5
    assert _max_param_delta(pipe_p, base_p) < 1e-3

    q8_p, q8_m = _run_gcfg(
        cfg, mesh, params, batch,
        GSPMDConfig(rules=rules, comm="pipe-int8", block_kv=64))
    assert abs(float(q8_m["loss"]) - float(pipe_m["loss"])) < 1e-2

    il_p, il_m = _run_gcfg(
        cfg, mesh, params, batch,
        GSPMDConfig(rules=rules, comm="pipe", pipe_interleave=True,
                    block_kv=64))
    assert abs(float(il_m["loss"]) - float(pipe_m["loss"])) < 1e-6


@pytest.mark.slow
def test_pipe_int8_loss_trajectory_within_bound():
    """Two training steps with the quantized wire track fp32 within the
    documented bound at every step."""
    cfg = get_reduced("qwen-1.5b")
    params = T.init_params(cfg, KEY)
    mesh = make_pipe_mesh(stages=2, model=1)
    rules = ShardingRules(data=("pipe", "data"))

    def run(comm):
        gcfg = GSPMDConfig(rules=rules, comm=comm, block_kv=64)
        step = jax.jit(make_train_step(cfg, mesh, gcfg, AdamWConfig(lr=1e-2)))
        p, opt = params, adamw_init(params)
        losses = []
        for i in range(2):
            with mesh:
                p, opt, m = step(p, opt, _batch(cfg))
            losses.append(float(m["loss"]))
        return losses

    fp = run("pipe")
    q8 = run("pipe-int8")
    assert all(abs(a - b) < 1e-2 for a, b in zip(fp, q8)), (fp, q8)
