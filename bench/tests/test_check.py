"""The comparison that decides ``correct``, driven through a whole run at a
tiny size on the CPU (the harness's look for a chip skipped): the program
agrees with the plain float32 reference on one and on four devices, and
``correct`` comes out false for the lower-precision control and for each
fault a training cell can have."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import cell as C, check

SEED = 2 ** 31 + 11
ONE = "qwen1.5b-sft-longalign-1chip"
FOUR = "qwen1.5b-sft-longalign-4chip"


def run(cell, chips):
    return C.run(cell, SEED, 0.2, False, jax.devices()[:chips],
                 time.perf_counter(), log=lambda s: None)


def failed(out):
    return [k for k, c in out["compared"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("workload,chips,tied", [
    (ONE, 1, True), (FOUR, 4, True), ("phi3m-sft-longalign-1chip", 1, False)])
def test_program_agrees_with_reference(tiny_cell, workload, chips, tied):
    out = run(tiny_cell(workload, tie_word_embeddings=tied), chips)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # tiny float32 on the CPU: far inside the chip's limits
    assert all(c["value"] < 1e-4 for c in out["compared"].values())


def test_lower_precision_control_fails(tiny_cell):
    """The reference in bfloat16, put in the program's place."""
    cell = tiny_cell(ONE)
    steps = [s.samples for s in C.traffic.steps(
        cell.mix, 1, SEED, cell.config["run"]["vocab_size"])[:C.FIRST_STEPS]]
    devices = jax.devices()[:1]
    theirs = C.reference(cell, devices).run(SEED, steps)
    control = C.reference(cell, devices, dtype=jnp.bfloat16,
                          precision=None).run(SEED, steps)
    judged = check.judge(check.gaps(control, theirs), cell.limits)
    assert not all(c["ok"] for c in judged.values()), judged


def test_state_left_unchanged_fails(tiny_cell, monkeypatch):
    real = C.Program.run

    def unchanged(self, batch):
        keep = jax.tree.map(jnp.copy, (self.params, self.opt_state))
        loss = real(self, batch)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(C.Program, "run", unchanged)
    out = run(tiny_cell(ONE), 1)
    assert not out["correct"] and "change_gap" in failed(out)


def test_half_batch_left_out_fails(tiny_cell, monkeypatch):
    real = C.build_minibatch

    def half(plan, samples, S):
        plan.assignments = [[[i for i in mb if i % 2 == 0] for mb in dev]
                            for dev in plan.assignments]
        return real(plan, samples, S)

    monkeypatch.setattr(C, "build_minibatch", half)
    out = run(tiny_cell(ONE), 1)
    assert not out["correct"] and failed(out)


def test_exchange_between_chips_left_out_fails(tiny_cell, monkeypatch):
    from repro.core import odc

    def own_part_only(y, axis_name, device_profile=None):
        n = odc.axis_size(axis_name)
        c = y.shape[0] // n
        return jax.lax.dynamic_slice_in_dim(
            y, odc.axis_index(axis_name) * c, c, 0)

    monkeypatch.setattr(odc, "ring_scatter_accumulate", own_part_only)
    out = run(tiny_cell(FOUR), 4)
    assert not out["correct"] and failed(out)


def test_value_altered_where_produced_fails(tiny_cell, monkeypatch):
    """One leaf of the step's output (the embedding) left as it came in."""
    real = C.Program.run

    def drop_one(self, batch):
        keep = jnp.copy(self.params["embed"])
        loss = real(self, batch)
        self.params = dict(self.params, embed=jax.device_put(
            keep, self.params["embed"].sharding))
        return loss

    monkeypatch.setattr(C.Program, "run", drop_one)
    out = run(tiny_cell(ONE), 1)
    assert not out["correct"] and "change_gap" in failed(out)


def test_no_compile_in_the_window_on_four_devices(tiny_cell):
    """On several devices, moving a batch of a new microbatch count onto
    its shardings compiles; set-up warms every count the cycle reaches,
    also one that the first three steps do not."""
    cell = tiny_cell(FOUR, steps_per_cycle=8)
    S = cell.mix["microbatch_tokens"]

    def counts(seed):
        cycle = C.traffic.steps(cell.mix, 4, seed, 256)
        return [C.make_plan(s.lengths, 4, S, strategy="lb_mini")
                .max_microbatches for s in cycle]

    seed = next(s for s in range(1000)
                if set(counts(s)[C.FIRST_STEPS:]) - set(counts(s)[:C.FIRST_STEPS]))
    logs = []
    out = C.run(cell, seed, 1.0, False, jax.devices()[:4],
                time.perf_counter(), log=logs.append)
    assert any("backend compiles in the window: 0" in line for line in logs)
    assert out["correct"], out["compared"]
