"""Context-parallel ring attention + token-level chunk balancing.

Key claims:

  * GOLDEN BIT-IDENTITY: ``core.cp.ring_attention`` under a 4-way
    shard_map ring — forward AND the VJP cotangents (dq, dk, dv) — is
    bitwise equal to the monolithic ``flash_attention_diff`` on the
    gathered global sequence, for both the head+tail interleaved and the
    contiguous layout, with packed segments and GQA;
  * the two gather transports ('jnp' ring, 'kernel' remote-DMA ring)
    produce identical results;
  * the head+tail interleave permutations and the gathered-buffer
    unshuffle/reshuffle helpers are exact inverses;
  * ``allgather_attention`` (the differentiable traced-window fallback)
    matches the single-device blockwise oracle and is reverse-mode
    differentiable;
  * ``lb_token`` plans: full sample coverage, over-budget sequences are
    always cp-split, per-rank cells respect the token budget, and cp=1
    degenerates to LB-Mini's exact assignments;
  * ``build_minibatch`` on a cp plan emits (M, G, cp·S) rows whose
    sequence dim un-interleaves back to a valid packed buffer;
  * the ``context-ring`` policy at cp=1 is float-exactly
    ``IndependentPolicy`` (and the simulated cp=1 makespan equals flat
    ODC's), while cp>1 with ``lb_token`` beats ODC on a
    single-long-sequence straggler minibatch;
  * an end-to-end cp train step (qwen reduced, cp=2) matches the flat
    ODC baseline's loss/params and restores the attention impl.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.balance.strategies import STRATEGIES, lb_mini, lb_token, make_plan
from repro.configs import get_reduced
from repro.core import backend as B
from repro.core import cp
from repro.core.gspmd import GSPMDConfig, ShardingRules, make_train_step
from repro.data.packing import build_minibatch
from repro.kernels.flash_attention import flash_attention_diff
from repro.launch.mesh import make_cp_mesh, make_host_mesh
from repro.models import layers as L
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.sim import (
    CONTEXT_RING,
    CommModel,
    ContextRingPolicy,
    INDEPENDENT,
    SimConfig,
    get_policy,
    simulate_minibatch,
)

KEY = jax.random.PRNGKey(0)


def _shard_run(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=set(mesh.axis_names))


# ===========================================================================
# layout permutations
# ===========================================================================
@pytest.mark.parametrize("total,n", [(8, 2), (64, 4), (96, 3)])
def test_interleave_round_trip(total, n):
    perm = cp.interleave_indices(total, n)
    inv = cp.unshuffle_indices(total, n)
    assert sorted(perm) == list(range(total))
    np.testing.assert_array_equal(perm[inv], np.arange(total))
    np.testing.assert_array_equal(inv[perm], np.arange(total))
    # device r holds chunks (r, 2n-1-r): one head, one tail
    chunk = total // (2 * n)
    for r in range(n):
        shard = perm[r * 2 * chunk: (r + 1) * 2 * chunk]
        assert shard[0] == r * chunk
        assert shard[chunk] == (2 * n - 1 - r) * chunk


@pytest.mark.parametrize("n", [2, 4])
def test_gathered_unshuffle_reshuffle_inverse(n):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8 * n, 3, 2)))
    g = cp._unshuffle_gathered(x, n)
    assert bool((cp._reshuffle_global(g, n) == x).all())
    # the unshuffle really is unshuffle_indices applied along the lead axis
    ref = jnp.take(x, jnp.asarray(cp.unshuffle_indices(x.shape[0], n)), 0)
    # device-order concat == global[interleave] — so the two agree
    assert bool((g == ref).all())


# ===========================================================================
# golden bit-identity: ring == monolithic flash attention
# ===========================================================================
def _packed_inputs(B_=2, S=256, H=4, KH=2, hd=32, seed=0):
    """Packed multi-segment global arrays with a masked-out padding tail."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B_, S, H, hd)).astype(np.float32)
    k = rng.normal(size=(B_, S, KH, hd)).astype(np.float32)
    v = rng.normal(size=(B_, S, KH, hd)).astype(np.float32)
    g = rng.normal(size=(B_, S, H, hd)).astype(np.float32)
    pos = np.zeros((B_, S), np.int32)
    seg = np.full((B_, S), -1, np.int32)
    for b in range(B_):
        bounds = [0, S // 3, S // 3 + S // 4, S - S // 8, S]
        for s, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
            if s == len(bounds) - 2:
                pos[b, lo:hi] = -(10 ** 9)  # padding tail
            else:
                pos[b, lo:hi] = np.arange(hi - lo)
                seg[b, lo:hi] = s
    return tuple(jnp.asarray(x) for x in (q, k, v, pos, seg, g))


@pytest.mark.parametrize("interleave", [True, False])
@pytest.mark.parametrize("window", [0, 96])
def test_ring_attention_bitwise_golden(interleave, window):
    """The tentpole contract: fwd and VJP bitwise equal to the monolithic
    kernel on the gathered sequence (packed segments, GQA, causal,
    optionally sliding-window)."""
    n = 4
    if len(jax.devices()) < n:
        pytest.skip("needs 4 host devices")
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("cp",))
    q, k, v, pos, seg, g = _packed_inputs()
    S = q.shape[1]

    # The reference runs under jit, like the ring: op-by-op dispatch rounds
    # the backward's `einsum(...) * scale` differently from the compiled
    # program (1 ulp in dk), so "bitwise" compares compiled to compiled.
    @jax.jit
    def reference(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention_diff(
                q, k, v, causal=True, window=window, q_positions=pos,
                kv_positions=pos, q_segment_ids=seg, kv_segment_ids=seg,
                blk_q=32, blk_k=32, interpret=True),
            q, k, v)
        return (out,) + vjp(g)

    ref, dq_ref, dk_ref, dv_ref = reference(q, k, v, g)

    perm = (cp.interleave_indices(S, n) if interleave
            else np.arange(S))
    dev = lambda x: jnp.take(x, jnp.asarray(perm), axis=1)

    def f(q, k, v, qp, ks, g):
        out, vjpf = jax.vjp(
            lambda q, k, v: cp.ring_attention(
                q, k, v, axis_name="cp", causal=True, window=window,
                q_positions=qp, kv_positions=qp, q_segment_ids=ks,
                kv_segment_ids=ks, blk_q=32, blk_k=32, interpret=True,
                interleave=interleave),
            q, k, v)
        return (out,) + vjpf(g)

    sp = P(None, "cp")
    out, dq, dk, dv = jax.jit(_shard_run(
        f, mesh, (sp,) * 6, (sp,) * 4))(
        dev(q), dev(k), dev(v), dev(pos), dev(seg), dev(g))

    for got, want in ((out, ref), (dq, dq_ref), (dk, dk_ref), (dv, dv_ref)):
        assert bool((got == dev(want)).all())  # BITWISE


def test_ring_gather_impls_agree():
    """'kernel' (remote-DMA ring) and 'jnp' (odc.ring_gather) transports
    move the same bytes — identical attention output."""
    n = 4
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("cp",))
    q, k, v, pos, seg, _ = _packed_inputs(seed=1)

    def run(gi):
        def f(q, k, v, qp, ks):
            return cp.ring_attention(
                q, k, v, axis_name="cp", causal=True, q_positions=qp,
                kv_positions=qp, q_segment_ids=ks, kv_segment_ids=ks,
                blk_q=32, blk_k=32, interpret=True, gather_impl=gi)
        sp = P(None, "cp")
        perm = jnp.asarray(cp.interleave_indices(q.shape[1], n))
        dev = lambda x: jnp.take(x, perm, axis=1)
        return jax.jit(_shard_run(f, mesh, (sp,) * 5, sp))(
            dev(q), dev(k), dev(v), dev(pos), dev(seg))

    assert bool((run("jnp") == run("kernel")).all())


def test_allgather_attention_matches_blockwise_and_differentiates():
    """The traced-window fallback: matches the single-device blockwise
    oracle on the gathered sequence and has working reverse-mode AD."""
    n = 4
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("cp",))
    q, k, v, pos, seg, g = _packed_inputs(seed=2)
    S = q.shape[1]
    ref = L.blockwise_attention(q, k, v, causal=True, q_positions=pos,
                                kv_positions=pos, q_segment_ids=seg,
                                kv_segment_ids=seg, block_kv=S)

    def f(q, k, v, qp, ks, g):
        def attn(q, k, v):
            return cp.allgather_attention(
                q, k, v, axis_name="cp", causal=True, q_positions=qp,
                kv_positions=qp, q_segment_ids=ks, kv_segment_ids=ks)
        out, vjpf = jax.vjp(attn, q, k, v)
        return (out,) + vjpf(g)

    sp = P(None, "cp")
    perm = jnp.asarray(cp.interleave_indices(S, n))
    dev = lambda x: jnp.take(x, perm, axis=1)
    out, dq, dk, dv = jax.jit(_shard_run(f, mesh, (sp,) * 6, (sp,) * 4))(
        dev(q), dev(k), dev(v), dev(pos), dev(seg), dev(g))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dev(ref)),
                               rtol=1e-6, atol=1e-6)
    for d in (dq, dk, dv):
        assert bool(jnp.isfinite(d).all())


def test_cp_impl_rejects_decode_layout():
    impl = cp.cp_attention_impl("cp")
    q = jnp.zeros((1, 4, 2, 8))
    kv = jnp.zeros((1, 8, 2, 8))
    with pytest.raises(NotImplementedError, match="decode"):
        impl(q, kv, kv)


# ===========================================================================
# lb_token plans
# ===========================================================================
def test_lb_token_cp1_degenerates_to_lb_mini():
    lens = list(np.random.default_rng(0).integers(16, 2000, size=64))
    a = lb_token(lens, 8, 2048, cp=1)
    b = lb_mini(lens, 8, 2048)
    assert a.assignments == b.assignments
    assert a.cp == 1 and a.strategy == "LB-Token"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lb_token_plan_invariants(seed):
    rng = np.random.default_rng(seed)
    lens = list(rng.integers(16, 1500, size=48)) + [6000, 4000]
    W, MT, CP = 8, 2048, 4
    plan = lb_token(lens, W, MT, cp=CP)
    plan.validate(len(lens))
    assert plan.world_size == W // CP and plan.cp == CP
    # anything over the per-rank budget MUST be split
    for i, l in enumerate(lens):
        if l > MT:
            assert i in plan.cp_split
    for g, (mbs, cells) in enumerate(zip(plan.assignments, plan.cp_cells)):
        assert len(mbs) == len(cells)
        for mb, wave in zip(mbs, cells):
            assert len(wave) == CP
            # the union row is exactly the wave's cells
            assert sorted(mb) == sorted({i for c in wave for i in c})
            for cell in wave:
                tok = sum(max(1, lens[i] // CP) if i in plan.cp_split
                          else lens[i] for i in cell)
                assert tok <= MT, (g, cell, tok)


def test_lb_token_requires_divisible_world():
    with pytest.raises(ValueError, match="not divisible"):
        lb_token([10, 20], 6, 100, cp=4)


def test_make_plan_threads_cp():
    lens = [100] * 14 + [4000, 900]
    plan = make_plan(lens, 8, 2048, strategy="lb_token", cp=4)
    assert plan.cp == 4 and plan.world_size == 2
    assert "lb_token" in STRATEGIES


# ===========================================================================
# packing
# ===========================================================================
def test_build_minibatch_cp_rows_uninterleave_to_packed_buffers():
    lens = [48] * 14 + [1000, 300]
    MT, CP = 512, 2
    plan = lb_token(lens, 8, MT, cp=CP)
    rng = np.random.default_rng(0)
    toks = [rng.integers(1, 100, size=l).astype(np.int32) for l in lens]
    batch = build_minibatch(plan, toks, MT)
    G = plan.world_size
    row_len = CP * MT
    assert batch["tokens"].shape == (plan.max_microbatches, G, row_len)
    inv = cp.unshuffle_indices(row_len, CP)
    seg = np.asarray(batch["segment_ids"])[..., inv]
    pos = np.asarray(batch["positions"])[..., inv]
    for m in range(seg.shape[0]):
        for gi in range(G):
            row = seg[m, gi]
            real = row >= 0
            # un-interleaved row is a packed buffer: segments ascend in
            # contiguous runs, padding only in the tail
            if real.any():
                last = np.flatnonzero(real)[-1]
                assert (row[:last + 1] >= 0).all()
                assert (np.diff(row[:last + 1]) >= 0).all()
                # positions restart at 0 within each segment
                for s in np.unique(row[:last + 1]):
                    span = pos[m, gi][:last + 1][row[:last + 1] == s]
                    np.testing.assert_array_equal(span,
                                                  np.arange(len(span)))
    # total real tokens preserved
    assert int((seg >= 0).sum()) == sum(lens)


# ===========================================================================
# simulator: policy + engine
# ===========================================================================
def test_context_ring_policy_cp1_is_independent_float_exact():
    times = [[1.5, 2.25], [3.0], []]
    cl = [0.125, 0.25, 0.0]
    for pol in (ContextRingPolicy(cp=1, hop_s=0.5),
                ContextRingPolicy(cp=4, hop_s=0.0)):
        assert pol.step_blocks(times, cl, 8) == \
            INDEPENDENT.step_blocks(times, cl, 8)


def test_context_ring_policy_charges_hops():
    times = [[2.0, 2.0]]
    mk0, _ = INDEPENDENT.step_blocks(times, [0.0], 8)
    mk, blocks = ContextRingPolicy(cp=4, hop_s=0.01).step_blocks(
        times, [0.0], 8)
    assert mk == pytest.approx(mk0 + 8 * 3 * 0.01 * 2)
    assert any(lbl == "cp kv ring" for _, _, lbl in blocks[0][1])
    assert get_policy("context-ring") is CONTEXT_RING


def test_cp_backend_registered_with_hop_model():
    cb = B.get_backend("cp")
    assert B.get_backend("cp-ring") is cb
    cm = CommModel()
    assert cb.ring_hop_time(cm, 1) == 0.0
    h2, h4 = cb.ring_hop_time(cm, 2), cb.ring_hop_time(cm, 4)
    assert 0.0 < h4 < h2  # deeper ring moves smaller chunks per hop
    assert cb.ring_policy(cm, 1) is CONTEXT_RING
    p4 = cb.ring_policy(cm, 4)
    assert isinstance(p4, ContextRingPolicy) and p4.cp == 4
    # parameter transport is flat ODC's, unchanged
    assert cb.layer_comm_time(cm, 8) == B.ODC.layer_comm_time(cm, 8)


def test_sim_cp1_makespan_equals_flat_odc_exactly():
    lens = list(np.random.default_rng(3).integers(32, 1800, size=64))
    odc = simulate_minibatch(lb_mini(lens, 8, 2048), lens, scheme="odc",
                             cfg=SimConfig())
    cp1 = simulate_minibatch(lb_token(lens, 8, 2048, cp=1), lens,
                             scheme="cp", cfg=SimConfig())
    assert cp1.makespan == odc.makespan  # float-exact degeneration


def test_sim_cp_kills_single_long_sequence_straggler():
    """One 4x-median sequence dominates a device under every non-cp plan;
    lb_token + the cp ring divides it across the ring group."""
    lens = [64] * 14 + [2048, 512]
    cfg = SimConfig(overlap=0.0)
    odc = simulate_minibatch(lb_mini(lens, 8, 2048), lens, scheme="odc",
                             cfg=cfg)
    ring = simulate_minibatch(lb_token(lens, 8, 2048, cp=4), lens,
                              scheme="cp", cfg=cfg)
    assert ring.makespan < odc.makespan
    assert odc.makespan / ring.makespan > 1.5  # a real straggler kill


# ===========================================================================
# end-to-end GSPMD engine
# ===========================================================================
def _synth_batch(cfg, M=1, Bm=8, S=64, cp_degree=0):
    kb = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(kb, (M, Bm, S), 0, cfg.vocab_size),
        "positions": jnp.tile(jnp.arange(S)[None, None], (M, Bm, 1)),
        "segment_ids": jnp.zeros((M, Bm, S), jnp.int32),
        "targets": jax.random.randint(kb, (M, Bm, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((M, Bm, S), jnp.float32),
    }
    if cp_degree:  # host-side head+tail interleave of the sequence dim
        perm = jnp.asarray(cp.interleave_indices(S, cp_degree))
        batch = {k: jnp.take(v, perm, axis=-1) for k, v in batch.items()}
    return batch


def test_cp_requires_two_data_axes():
    cfg = get_reduced("qwen-1.5b")
    mesh = make_host_mesh(data=8, model=1)
    with pytest.raises(ValueError, match="trailing data axis"):
        make_train_step(cfg, mesh,
                        GSPMDConfig(rules=ShardingRules(), comm="cp"))


def test_cp_train_step_matches_flat_odc():
    """cp=2 training step: loss/params match the flat ODC world (same
    global batch, sequence-sharded + ring attention) and the attention
    impl is restored after the step."""
    cfg = get_reduced("qwen-1.5b")
    params = T.init_params(cfg, KEY)

    def run(mesh, rules, comm, batch):
        gcfg = GSPMDConfig(rules=rules, schedule="minibatch", comm=comm,
                           block_kv=64)
        step = jax.jit(make_train_step(cfg, mesh, gcfg, AdamWConfig(lr=1e-2)))
        with mesh:
            p, _, m = step(params, adamw_init(params), batch)
        return p, m

    base_p, base_m = run(make_host_mesh(data=8, model=1), ShardingRules(),
                         "odc", _synth_batch(cfg))
    assert L.get_attention_impl() is None
    cp_p, cp_m = run(make_cp_mesh(cp=2, model=1),
                     ShardingRules(data=("data", "cp")), "cp",
                     _synth_batch(cfg, cp_degree=2))
    assert L.get_attention_impl() is None  # restored by the finally
    assert abs(float(cp_m["loss"]) - float(base_m["loss"])) < 1e-4
    assert float(cp_m["tokens"]) == float(base_m["tokens"])
    # the baseline runs the jnp blockwise kernel, cp the pallas ring:
    # AdamW's normalized update amplifies the fp reordering noise, so the
    # bound here matches test_pipe's cross-kernel tolerance
    delta = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(cp_p), jax.tree.leaves(base_p)))
    assert delta < 2e-3
