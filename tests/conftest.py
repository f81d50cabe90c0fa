"""Test-session setup.

jax locks the device count at first init, and pytest imports test modules
in file order — so the 8-host-device flag the distributed tests need must
be set before ANY module imports jax.  (This is deliberately 8, not the
dry-run's 512: only `repro.launch.dryrun` builds the production mesh, in
its own process.)
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# fault injection: seeded straggler profiles
# ---------------------------------------------------------------------------
STRAGGLER_KINDS = ("uniform", "one_slow", "bimodal")


@pytest.fixture(scope="session")
def straggler_profiles():
    """Factory for seeded fault-injection device profiles.

    The canonical vocabulary ('uniform' | 'one_slow' | 'bimodal', plus
    'homogeneous' as the control) lives in
    ``repro.balance.cost.make_straggler_profile`` so
    ``benchmarks/straggler_sweep.py`` injects the *same* faults the tests
    assert against.  Session-scoped so hypothesis tests may use it.
    """
    from repro.balance import make_straggler_profile

    def make(kind, world=8, *, slow_factor=2.0, seed=0, jitter=0.0):
        return make_straggler_profile(kind, world, slow_factor=slow_factor,
                                      seed=seed, jitter=jitter)

    return make
