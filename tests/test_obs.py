"""Observability layer (repro.obs): registry invariants, comm-byte
accounting through the backend seam, sim-vs-real schema identity, and
the divergence report.

Key claims:
  * the metrics registry's instruments hold their contracts — counters
    are monotone, the log2 histogram's buckets cover every message size
    with an explicit overflow, labels round-trip through the JSONL
    snapshot stream;
  * a REAL run (executable ``param_gather`` under shard_map) and a SIM
    run (``simulate_minibatch``'s cost hooks) of the same config emit
    metrics with IDENTICAL counter-name sets — the schema contract the
    divergence tooling aligns on;
  * comm-byte accounting is conservative: flat ODC's logical gather
    bytes equal ``(world - 1) x shard_bytes`` exactly, and pipe-int8's
    inter-tier wire ratio is the measured ``int8_wire_factor``;
  * recording NEVER perturbs simulated arithmetic (makespans equal with
    and without a registry — the BENCH byte-identity guarantee);
  * a seeded sim-vs-sim trace pair diverges by exactly zero (all
    calibration scalars 1.0 where evidence exists).
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.balance import STRATEGIES
from repro.core import backend as B
from repro.data import sample_lengths
from repro.obs import divergence as obs_div
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.sim import CommModel, SimConfig, simulate_minibatch
from repro.sim.trace import chrome_trace

WORLD = 8


# ===========================================================================
# registry invariants
# ===========================================================================
def test_counter_monotone():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("comm.bytes_wire", backend="odc")
    c.inc(5.0)
    c.inc(0.0)
    assert c.value == 5.0
    with pytest.raises(ValueError, match="monotone"):
        c.inc(-1.0)
    with pytest.raises(ValueError, match="monotone"):
        c.inc_per_step(-1.0)


def test_histogram_bucket_cover_and_overflow():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("comm.message_bytes")
    # one observation into every bucket, plus one beyond the last bound
    for ub in obs_metrics.LOG2_BUCKETS:
        h.observe(ub)
    h.observe(2.0 ** 60)
    assert h.count == len(obs_metrics.LOG2_BUCKETS) + 1
    assert sum(h.counts) == h.count  # buckets + overflow partition all
    assert h.counts[-1] == 1  # the 2^60 observation overflowed
    row = h.to_row()
    assert row["buckets"]["overflow"] == 1
    assert row["buckets"]["1"] == 1  # 2^0 landed in the first bucket
    # quantiles are bucket upper bounds, monotone in q
    assert h.quantile(0.5) <= h.quantile(0.95)


def test_labels_round_trip_through_jsonl(tmp_path):
    path = str(tmp_path / "m.jsonl")
    reg = obs_metrics.MetricsRegistry(meta={"driver": "test"})
    reg.attach_jsonl(path)
    reg.counter("comm.messages", backend="odc", op="gather",
                tier="flat").inc(3.0)
    reg.gauge("train.loss").set(1.5)
    reg.histogram("comm.message_bytes", backend="odc", op="gather",
                  tier="flat").observe(1024.0, 3.0)
    reg.step(0)
    reg.close()
    meta, rows = obs_metrics.read_jsonl(path)
    assert meta == {"driver": "test"}
    assert len(rows) == 1
    names = obs_metrics.metric_names(rows)
    assert "comm.messages{backend=odc,op=gather,tier=flat}" in names
    assert "train.loss" in names
    got = {m["name"]: m for m in rows[0]["metrics"]}
    assert got["comm.messages"]["labels"] == {
        "backend": "odc", "op": "gather", "tier": "flat"}
    assert got["comm.message_bytes"]["buckets"] == {"1024": 3.0}


def test_per_step_ledger_and_program_scopes():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("comm.bytes_wire")
    with obs_metrics.recording(reg):
        with reg.program("step"):
            c.inc_per_step(10.0)
    reg.step(0)
    reg.step(1)
    assert c.value == 20.0  # ledger commits on every step
    # a retrace REPLACES the program's group (the old program is dead)
    with reg.program("step"):
        c.inc_per_step(1.0)
    reg.step(2)
    assert c.value == 21.0
    # trace_scale multiplies (scan bodies traced once, run L times)
    with reg.program("step"):
        with obs_metrics.trace_scale(4):
            c.inc_per_step(1.0)
    reg.step(3)
    assert c.value == 25.0


# ===========================================================================
# the comm-byte accounting seam
# ===========================================================================
def _shard_run(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=set(mesh.axis_names))


def _real_counter_rows(backend_name, mesh, axis, spec, x, tmp_path, tag):
    """Run one real fwd+bwd param_gather under a recording registry and
    return the JSONL snapshot rows."""
    path = str(tmp_path / f"real_{tag}.jsonl")
    reg = obs_metrics.MetricsRegistry(meta={"source": "real"})
    reg.attach_jsonl(path)
    with obs_metrics.recording(reg):
        def f(xs):
            g = B.get_backend(backend_name).param_gather(axis)
            return jax.grad(lambda s: (g(s) ** 2).sum() / 2)(xs)
        with reg.program("step"):
            _shard_run(f, mesh, (spec,), spec)(x)
        reg.step(0)
    reg.close()
    return obs_metrics.read_jsonl(path)[1]


def _sim_counter_rows(backend_name, cfg, tmp_path, tag):
    path = str(tmp_path / f"sim_{tag}.jsonl")
    reg = obs_metrics.MetricsRegistry(meta={"source": "sim"})
    reg.attach_jsonl(path)
    lens = sample_lengths("longalign", WORLD * 2, 0).tolist()
    plan = STRATEGIES["lb_mini"](lens, WORLD, 65_536)
    with obs_metrics.recording(reg):
        simulate_minibatch(plan, lens, scheme=backend_name, cfg=cfg)
        reg.step(0)
    reg.close()
    return obs_metrics.read_jsonl(path)[1]


@pytest.mark.parametrize("name", ["odc", "collective", "hier"])
def test_sim_and_real_counter_names_identical(name, tmp_path):
    """The acceptance contract: a sim run and a real run of one config
    emit metrics JSONL with IDENTICAL comm counter-name sets."""
    if name == "hier":
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("node", "device"))
        axis = ("node", "device")
        spec = P(("node", "device"))
        x = jnp.arange(64.0).reshape(32, 2)
        cfg = SimConfig(comm=CommModel(devices_per_node=4))
    else:
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        axis = "data"
        spec = P("data")
        x = jnp.arange(32.0)
        cfg = SimConfig(comm=CommModel(devices_per_node=WORLD))
    real = _real_counter_rows(name, mesh, axis, spec, x, tmp_path, name)
    sim = _sim_counter_rows(name, cfg, tmp_path, name)
    real_names = obs_metrics.metric_names(real, kind="counter",
                                          prefix="comm.")
    sim_names = obs_metrics.metric_names(sim, kind="counter",
                                         prefix="comm.")
    assert real_names == sim_names
    assert real_names  # non-empty: the seam actually recorded
    # histograms carry the same identity too
    assert (obs_metrics.metric_names(real, kind="histogram")
            == obs_metrics.metric_names(sim, kind="histogram"))


def test_flat_odc_bytes_conservation():
    """Logical gather bytes == (world - 1) x shard_bytes, exactly: the
    ring moves every other device's shard to me, once."""
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    x = jnp.arange(64, dtype=jnp.float32)
    shard_bytes = (x.size // WORLD) * x.dtype.itemsize  # 32 bytes/device
    reg = obs_metrics.MetricsRegistry()
    with obs_metrics.recording(reg):
        def f(xs):
            return B.ODC.param_gather("data")(xs)
        with reg.program("step"):
            _shard_run(f, mesh, (P("data"),), P())(x)
        reg.step(0)
    assert reg.total("comm.bytes_logical", op="gather") == \
        (WORLD - 1) * shard_bytes
    assert reg.total("comm.messages", op="gather") == WORLD - 1
    # wire == logical on the uncompressed flat ring
    assert reg.total("comm.bytes_wire", op="gather") == \
        reg.total("comm.bytes_logical", op="gather")


def test_pipe_int8_inter_wire_ratio_is_measured_fact():
    """pipe-int8's 0.254x wire ratio is a fact the counters measure:
    inter-tier wire/logical == int8_wire_factor, intra unchanged."""
    shard = 1024.0 * 1024.0
    vols = {t: (logical, wire) for t, _, logical, wire
            in B.PIPE_INT8.comm_volume("gather", shard, 8, 4)}
    assert vols["inter"][1] / vols["inter"][0] == \
        pytest.approx(B.PIPE_INT8.int8_wire_factor)
    assert B.PIPE_INT8.int8_wire_factor == pytest.approx(0.254, abs=1e-3)
    assert vols["intra"][1] == vols["intra"][0]
    # and hier's two-tier split partitions the flat volume's shard sets
    g, n = 4, 2
    intra_l = vols["intra"][0]
    inter_l = vols["inter"][0]
    assert intra_l == (g - 1) * shard
    assert inter_l == (n - 1) * g * shard


def test_recording_does_not_perturb_sim_arithmetic():
    """The BENCH byte-identity guarantee: a simulated run computes the
    exact same floats with and without a registry recording."""
    lens = sample_lengths("longalign", WORLD * 4, 0).tolist()
    plan = STRATEGIES["lb_mini"](lens, WORLD, 65_536)
    base = {}
    for scheme in ("odc", "collective", "hier", "odc-overlap"):
        base[scheme] = simulate_minibatch(plan, lens, scheme=scheme)
    reg = obs_metrics.MetricsRegistry()
    with obs_metrics.recording(reg):
        for scheme, want in base.items():
            got = simulate_minibatch(plan, lens, scheme=scheme)
            assert got.makespan == want.makespan, scheme
            assert got.device_busy == want.device_busy, scheme
            assert got.bubble_rate == want.bubble_rate, scheme
    assert reg.total("comm.bytes_wire") > 0  # it DID record


# ===========================================================================
# counter tracks in the chrome trace
# ===========================================================================
def test_timeline_counter_track_serializes():
    lens = sample_lengths("longalign", WORLD * 2, 0).tolist()
    plan = STRATEGIES["lb_mini"](lens, WORLD, 65_536)
    r = simulate_minibatch(plan, lens, scheme="odc")
    trace = chrome_trace(r.timeline)
    tracks = [ev for ev in trace["traceEvents"] if ev.get("ph") == "C"]
    assert tracks, "sim timelines carry a cumulative wire-bytes track"
    assert tracks[0]["name"] == "comm wire bytes"
    assert tracks[0]["args"]["value"] > 0


# ===========================================================================
# divergence report
# ===========================================================================
def _seeded_sim_trace(seed):
    lens = sample_lengths("longalign", WORLD * 2, seed).tolist()
    plan = STRATEGIES["lb_mini"](lens, WORLD, 65_536)
    r = simulate_minibatch(plan, lens, scheme="odc",
                           cfg=SimConfig(overlap=0.0))
    return chrome_trace(r.timeline)


def test_divergence_zero_for_identical_seeded_pair():
    a, b = _seeded_sim_trace(0), _seeded_sim_trace(0)
    rep = obs_div.compare_traces(a, b)
    assert rep.makespan_error == 0.0
    assert rep.idle_l1 == 0.0
    assert rep.real_only_lanes == [] and rep.sim_only_lanes == []
    for kind, (r, s, d) in rep.kind_totals.items():
        assert d == 0.0, kind
    for lane, kt in rep.per_lane.items():
        for kind, (r, s, d) in kt.items():
            assert d == 0.0, (lane, kind)
    for hook, scalar in rep.calibration.items():
        assert scalar is None or scalar == 1.0, hook
    # at least ONE hook has evidence (exposed comm at overlap=0.0)
    assert any(s == 1.0 for s in rep.calibration.values())
    text = rep.render()
    assert "makespan error: +0.000%" in text


def test_divergence_sees_a_real_gap():
    a, b = _seeded_sim_trace(0), _seeded_sim_trace(3)
    rep = obs_div.compare_traces(a, b)
    assert rep.real_makespan != rep.sim_makespan
    assert rep.calibration["time_per_cost"] not in (None, 1.0)


# ===========================================================================
# report CLI (sim-vs-sim pair, end to end)
# ===========================================================================
def test_report_cli_simulate_and_render(tmp_path, capsys):
    from repro.launch import report as report_cli
    m1, t1 = str(tmp_path / "a.jsonl"), str(tmp_path / "a.json")
    m2, t2 = str(tmp_path / "b.jsonl"), str(tmp_path / "b.json")
    args = ["--simulate", "--comm", "odc", "--world", "8", "--steps", "2"]
    assert report_cli.main(args + ["--metrics", m1, "--trace", t1]) == 0
    assert report_cli.main(args + ["--metrics", m2, "--trace", t2]) == 0
    out = str(tmp_path / "report.md")
    assert report_cli.main(["--metrics", m1, "--sim-metrics", m2,
                            "--trace", t1, "--sim-trace", t2,
                            "-o", out]) == 0
    capsys.readouterr()
    with open(out) as f:
        text = f.read()
    assert "counter name sets: **IDENTICAL**" in text
    assert "Cost-hook calibration" in text
    assert "| `time_per_cost` | 1.0000 |" in text  # same seeds: zero gap
    assert "Comm bytes by backend / op / tier" in text


# ===========================================================================
# the log helper
# ===========================================================================
def test_runlog_quiet_and_thinning(capsys):
    out = obs_log.RunLog("train")
    out.info("config line")
    out.step(0, "s0")
    out.always("done")
    got = capsys.readouterr().out
    assert got == "[train] config line\n[train] s0\n[train] done\n"

    quiet = obs_log.RunLog("train", quiet=True)
    quiet.info("config line")
    quiet.step(0, "s0")
    quiet.always("done")
    assert capsys.readouterr().out == "[train] done\n"

    thin = obs_log.RunLog("train", every=2)
    for i in range(4):
        thin.step(i, f"s{i}")
    assert capsys.readouterr().out == "[train] s0\n[train] s2\n"


# ===========================================================================
# golden-check helper (benchmarks/common.py)
# ===========================================================================
def test_check_golden_status_transitions(tmp_path):
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), ".."))
    try:
        from benchmarks.common import check_golden
    finally:
        _sys.path.pop(0)
    path = str(tmp_path / "BENCH_x.json")
    rows = [{"a": 1.0}]
    p, status = check_golden(path, "x", {"k": 1}, rows)
    assert (p, status) == (path, "created")
    _, status = check_golden(path, "x", {"k": 1}, rows)
    assert status == "byte-identical"
    _, status = check_golden(path, "x", {"k": 1}, [{"a": 2.0}])
    assert status == "changed"
