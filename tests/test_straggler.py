"""Property-based invariants for the heterogeneity-aware balancer
(LB-Mini-Het) and its end-to-end plumbing.

Fault model: seeded straggler profiles ('uniform' | 'one_slow' |
'bimodal', see tests/conftest.py::straggler_profiles) with slowdown
factors up to 4x — the regime where PS-style decoupled progress is
supposed to shine (paper §1; Zeppelin arXiv:2509.21841).
"""
import json
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import pytest

try:  # only the @given tests need hypothesis; the rest run without it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.balance import (
    DeviceProfile,
    get_compute_costs,
    lb_mini,
    lb_mini_het,
    make_straggler_profile,
)
from repro.sim import SimConfig, simulate_minibatch, simulate_training

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="property tests need the 'test' extra: pip install -e .[test]")
KINDS = ("uniform", "one_slow", "bimodal")

if HAVE_HYPOTHESIS:
    SETTINGS = dict(max_examples=40, deadline=None)
    profiles = st.builds(
        make_straggler_profile,
        st.sampled_from(KINDS),
        st.sampled_from([2, 4, 8]),
        slow_factor=st.floats(1.0, 4.0),
        seed=st.integers(0, 5),
    )
else:  # pragma: no cover - placeholders so the module imports (the @given
    #                        tests themselves are skipped via the mark)
    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

    def given(**kw):
        return lambda f: f

    def settings(**kw):
        return lambda f: f

    SETTINGS = {}
    profiles = None


def _plan_pair(lens, world, max_tokens, profile):
    het = lb_mini_het(lens, world, max_tokens, profile=profile)
    base = lb_mini(lens, world, max_tokens)
    return het, base


# ===========================================================================
# LB-Mini-Het invariants
# ===========================================================================
@needs_hypothesis
@settings(**SETTINGS)
@given(
    lens=st.lists(st.integers(16, 8192), min_size=8, max_size=48),
    profile=profiles,
)
def test_het_plan_covers_and_respects_memory(lens, profile):
    """Every sample assigned exactly once; no microbatch over the token
    budget, on any device — stragglers included."""
    max_tokens = 8192
    plan = lb_mini_het(lens, profile.world_size, max_tokens, profile=profile)
    plan.validate(len(lens))
    for dev in plan.assignments:
        for mb in dev:
            assert sum(lens[i] for i in mb) <= max_tokens
    assert plan.profile is profile
    assert plan.strategy == "LB-Mini-Het"


@needs_hypothesis
@settings(**SETTINGS)
@given(
    lens=st.lists(st.integers(64, 16384), min_size=8, max_size=40),
    profile=profiles,
)
def test_het_normalized_spread_never_worse_than_lb_mini(lens, profile):
    """Peak normalized load (work ÷ device speed — the ODC makespan lower
    bound) of LB-Mini-Het never exceeds speed-oblivious LB-Mini's under
    the same skew."""
    max_tokens = 16384
    het, base = _plan_pair(lens, profile.world_size, max_tokens, profile)
    costs = get_compute_costs(lens)
    peak_het = max(het.normalized_loads(costs, profile))
    peak_base = max(base.normalized_loads(costs, profile))
    assert peak_het <= peak_base + 1e-6 * max(peak_base, 1.0)


@needs_hypothesis
@settings(**SETTINGS)
@given(
    lens=st.lists(st.integers(16, 8192), min_size=4, max_size=32),
    world=st.sampled_from([2, 4, 8]),
)
def test_het_homogeneous_is_byte_identical_to_lb_mini(lens, world):
    """Acceptance criterion: with a homogeneous DeviceProfile the emitted
    assignments are byte-identical to LB-Mini's."""
    max_tokens = 8192
    het = lb_mini_het(lens, world, max_tokens,
                      profile=DeviceProfile.homogeneous(world))
    base = lb_mini(lens, world, max_tokens)
    assert json.dumps(het.assignments) == json.dumps(base.assignments)
    # ... and so is passing no profile at all
    het_none = lb_mini_het(lens, world, max_tokens)
    assert json.dumps(het_none.assignments) == json.dumps(base.assignments)


@needs_hypothesis
@settings(**SETTINGS)
@given(
    lens=st.lists(st.integers(64, 16384), min_size=8, max_size=32),
    profile=profiles,
    scheme=st.sampled_from(["collective", "odc", "overlap"]),
)
def test_het_plan_roundtrips_simulator_deterministically(lens, profile, scheme):
    """A Plan carrying its profile simulates to the same result every time
    (the plan's own profile is picked up implicitly), including with
    seeded jitter."""
    max_tokens = 16384
    jittered = DeviceProfile(speeds=profile.speeds, jitter=0.05,
                             seed=profile.seed)
    plan = lb_mini_het(lens, jittered.world_size, max_tokens,
                       profile=jittered)
    a = simulate_minibatch(plan, lens, scheme=scheme, step=3)
    b = simulate_minibatch(plan, lens, scheme=scheme, step=3)
    assert a.makespan == b.makespan
    assert a.device_finish == b.device_finish
    # implicit (plan-carried) profile == explicit profile
    c = simulate_minibatch(plan, lens, scheme=scheme, profile=jittered,
                           step=3)
    assert a.makespan == c.makespan


# ===========================================================================
# fixture-driven end-to-end checks (fault kinds from conftest)
# ===========================================================================
@pytest.mark.parametrize("kind", KINDS)
def test_fixture_profiles_are_seeded_and_reproducible(straggler_profiles,
                                                      kind):
    p1 = straggler_profiles(kind, slow_factor=2.5, seed=7)
    p2 = straggler_profiles(kind, slow_factor=2.5, seed=7)
    assert p1 == p2
    assert p1.world_size == 8
    assert min(p1.speeds) >= 1.0 / 2.5 - 1e-9
    assert max(p1.speeds) <= 1.0 + 1e-9
    if kind != "uniform":
        assert min(p1.speeds) == pytest.approx(1.0 / 2.5)


@pytest.mark.parametrize("kind", KINDS)
def test_training_under_faults_gap_widens(straggler_profiles, kind):
    """Multi-minibatch: the collective-vs-ODC wall-clock gap grows with
    straggler severity once the balancer knows the profile."""
    from repro.data import sample_lengths
    world, max_tokens = 8, 16384
    cfg = SimConfig(overlap=0.0)
    gaps = []
    for factor in (1.0, 2.0, 4.0):
        profile = straggler_profiles(kind, slow_factor=factor, seed=1)
        steps_c, steps_o = [], []
        for t in range(4):
            lens = [min(l, max_tokens)
                    for l in sample_lengths("longalign", 32, t).tolist()]
            from repro.balance import lb_micro
            steps_c.append((lb_micro(lens, world, max_tokens), lens))
            steps_o.append((lb_mini_het(lens, world, max_tokens,
                                        profile=profile), lens))
        tc = simulate_training(steps_c, scheme="collective", cfg=cfg,
                               profile=profile)
        to = simulate_training(steps_o, scheme="odc", cfg=cfg)
        assert to <= tc + 1e-9
        gaps.append(tc - to)
    assert gaps[0] <= gaps[1] <= gaps[2] + 1e-9
    assert gaps[2] > gaps[0] + 1e-9


def test_get_compute_costs_is_device_aware():
    """Listing 1 costs normalized by a profile + device: a device at half
    speed sees every sample cost doubled; nominal devices see raw costs."""
    prof = make_straggler_profile("one_slow", 4, slow_factor=2.0)
    lens = [128, 1024, 4096]
    raw = get_compute_costs(lens)
    slow = get_compute_costs(lens, profile=prof, device=0)
    fast = get_compute_costs(lens, profile=prof, device=1)
    assert fast == raw
    assert slow == pytest.approx([2 * c for c in raw])
    # a profile without a device is not a normalization request
    assert get_compute_costs(lens, profile=prof) == raw


def test_ring_order_groups_stragglers():
    p = make_straggler_profile("one_slow", 8, slow_factor=3.0)
    order = p.ring_order()
    assert sorted(order) == list(range(8))
    assert order[-1] == 0  # the slow device sorts last (lowest speed)
    assert DeviceProfile.homogeneous(8).ring_order() == list(range(8))


def test_profile_ring_preserves_gather_scatter_semantics(straggler_profiles):
    """The DeviceProfile-ordered p2p ring must reconstruct/reduce exactly
    what the fused collectives do — heterogeneous plans change only which
    peer each hop talks to."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import odc

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    prof = straggler_profiles("bimodal", slow_factor=2.0, seed=1)
    assert prof.ring_order() != list(range(8))  # actually exercises reorder

    x = jnp.arange(8 * 4 * 3, dtype=jnp.float32).reshape(32, 3)

    def run(fn, arr):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data")))(arr)

    g_ord = run(lambda s: odc.ring_gather(s, "data", device_profile=prof)[None], x)
    g_col = run(lambda s: odc.collective_gather(s, "data")[None], x)
    assert bool(jnp.all(g_ord == g_col))

    y = jnp.arange(8 * 32 * 3, dtype=jnp.float32).reshape(8 * 32, 3)
    s_ord = run(lambda s: odc.ring_scatter_accumulate(
        s, "data", device_profile=prof), y)
    s_col = run(lambda s: odc.collective_scatter(s, "data"), y)
    assert bool(jnp.allclose(s_ord, s_col))
