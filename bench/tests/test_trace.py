"""The trace reduction on a small trace with known answers."""
import numpy as np
import pytest

from pathlib import Path

from bench.harness import trace

RECORDED = Path(__file__).parent / "data" / "v5e-1chip-qwen.xplane.pb"


def xspace(devices, spans):
    """A text-format XSpace: ``devices`` maps a plane to [(name, start_ns,
    dur_ns)] on its XLA Ops line; ``spans`` are host events."""
    names = sorted({n for ops in devices.values() for n, _, _ in ops}
                   | {n for n, _, _ in spans})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for n, i in ids.items())

    def line(name, evs):
        body = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                       f"duration_ps: {d * 1000} }}\n" for n, s, d in evs)
        return f'lines {{ id: 1 name: "{name}" timestamp_ns: 0\n{body}}}\n'

    planes = "".join(f'planes {{ id: {k + 2} name: "{p}"\n{line("XLA Ops", ops)}{meta}}}\n'
                     for k, (p, ops) in enumerate(devices.items()))
    planes += f'planes {{ id: 1 name: "/host:CPU"\n{line("python", spans)}{meta}}}\n'
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(planes)


SPANS = [("bench.window", 0, 100), ("bench.pack", 0, 10),
         ("bench.wait", 10, 70), ("bench.plan", 90, 10)]


def test_busy_idle_and_exposed_collectives():
    pd = xspace({
        "/device:TPU:0": [("%fusion.1 = f32[4] fusion()", 10, 30),
                          ("%collective-permute-done.2 = f32[4] x()", 30, 20),
                          ("%all-reduce.3 = f32[] all-reduce()", 60, 10),
                          ("%fusion.4 = f32[4] fusion(%all-reduce.3)", 65, 10)],
        # a while spans its body's operations; the gap inside it is idle
        "/device:TPU:1": [("%while.9 = (s32[]) while()", 20, 60),
                          ("%fusion.1 = f32[4] fusion()", 20, 30),
                          ("%fusion.2 = f32[4] fusion()", 55, 25)],
    }, SPANS)
    t = trace.reduce(pd)
    assert t["devices"] == 2
    assert np.isclose(t["window_s"], 100e-9)
    # device 0 busy [10, 50) + [60, 75) = 55 ns; device 1 [20, 50) +
    # [55, 80) = 55 ns
    assert np.isclose(t["busy_s"], 55e-9)
    assert np.isclose(t["idle_share"], 1 - 55 / 100)
    # device 0: collectives [30, 50) and [60, 70); other operations cover
    # [30, 40) and [65, 70) of them: exposed 10 + 5 = 15 ns
    assert np.isclose(t["collective_s"], 30e-9 / 2)
    assert np.isclose(t["collective_exposed_s"], 15e-9 / 2)
    ops = dict(t["device_ops"])
    assert np.isclose(ops["fusion.1"], 30e-9)
    assert np.isclose(ops["while.9"], (60 - 30 - 25) / 2 * 1e-9)  # self time
    gaps = dict(t["idle_gaps"])
    # device 0 idle [0,10) pack, [50,60) and [75,80) wait, [80,90) in no
    # span, [90,100) plan; device 1 [0,10) pack, [10,20) and [50,55)
    # wait, [80,90) none, [90,100) plan
    assert np.isclose(gaps["host: bench.pack"], 10e-9)
    assert np.isclose(gaps["host: bench.wait"], 15e-9)
    assert np.isclose(gaps["host: none"], 10e-9)
    assert np.isclose(gaps["host: bench.plan"], 10e-9)
    assert np.isclose(sum(gaps.values()), 100e-9 - t["busy_s"])


def test_no_device_operations_reads_nothing():
    pd = xspace({}, SPANS)
    assert trace.reduce(pd) is None


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e (one chip, qwen1.5b cell, 6 s
    window, cut to its first 0.3 s): the reduction's invariants."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(RECORDED))
    t = trace.reduce(pd)
    assert t["devices"] == 1
    assert 0 < t["busy_s"] <= t["window_s"]
    gaps = sum(v for _, v in t["idle_gaps"])
    assert np.isclose(gaps, t["window_s"] - t["busy_s"], rtol=1e-6)
    assert t["collective_s"] == 0
    assert all(not n.startswith("%") and " " not in n
               for n, _ in t["device_ops"])


@pytest.mark.parametrize("name,coll", [
    ("collective-permute-start.5", True), ("all-gather-done", True),
    ("reduce-scatter.1", True), ("fusion.12", False), ("copy.3", False)])
def test_collective_names(name, coll):
    assert trace.is_collective(name) is coll
