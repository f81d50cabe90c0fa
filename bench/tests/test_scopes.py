"""The reduction by scope on a small trace with known answers and on a
trace recorded on the chip, the cut ``bench/run.py --keep`` writes, and
the program's padding counters against the benchmark's
``pad_token_share``."""
import collections
import json
import os
import re
import types
from pathlib import Path

import numpy as np
import pytest

from bench.harness import scopes

DATA = Path(__file__).parent / "data"
# the readers of the scope reduction (``Context.scopes``)
SCOPE_METRICS = ("forward_device_ms", "recompute_device_ms",
                 "backward_device_ms", "optimizer_device_ms",
                 "loss_head_device_ms", "unscoped_device_share",
                 "host_to_device_ms")


FWD, BWD = "jit(step)/jvp()", "jit(step)/transpose(jvp())"
REMAT = BWD + "/while/body/checkpoint/rematted_computation"

# one instruction name, fusion.2, in two programs under different scopes;
# fusion.7's root adds the tied embedding's gradients, its work is the head
HLO = {1: f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8,16], param_1: f32[16,32], param_2: f32[8,32]) -> f32[8,32] {{
  %param_0 = f32[8,16]{{1,0}} parameter(0)
  %param_1 = f32[16,32]{{1,0}} parameter(1)
  %param_2 = f32[8,32]{{1,0}} parameter(2)
  %convolution.1 = f32[8,32]{{1,0}} convolution(%param_0, %param_1), dim_labels=bf_io->bf, metadata={{op_name="{BWD}/lm_head/dot_general"}}
  %dot.3 = f32[8,32]{{1,0}} dot(%param_2, %param_2), lhs_contracting_dims={{0}}, rhs_contracting_dims={{0}}, metadata={{op_name="{BWD}/mlp/dot_general"}}
  %add.1 = f32[8,32]{{1,0}} add(%convolution.1, %dot.3)
  ROOT %add.2 = f32[8,32]{{1,0}} add(%add.1, %param_2), metadata={{op_name="{BWD}/embed/add_any"}}
}}

%fused_computation.2 (param_0.1: f32[8,32]) -> f32[8,32] {{
  %param_0.1 = f32[8,32]{{1,0}} parameter(0)
  ROOT %multiply.1 = f32[8,32]{{1,0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{REMAT}/attention/mul"}}
}}

%fused_computation.3 (param_0.2: f32[8,32]) -> f32[8,32] {{
  %param_0.2 = f32[8,32]{{1,0}} parameter(0)
  ROOT %multiply.2 = f32[8,32]{{1,0}} multiply(%param_0.2, %param_0.2), metadata={{op_name="{FWD}/while/body/attention/mul"}}
}}

%fused_computation.4 (param_0.3: f32[8,32]) -> f32[8,32] {{
  %param_0.3 = f32[8,32]{{1,0}} parameter(0)
  ROOT %negate.1 = f32[8,32]{{1,0}} negate(%param_0.3), metadata={{op_name="jit(step)/shard_map/transpose(jvp(comm.scatter))/neg"}}
}}

%body.1 (p.1: (s32[], f32[8,32])) -> (s32[], f32[8,32]) {{
  %p.1 = (s32[], f32[8,32]{{1,0}}) parameter(0)
  %gte.1 = f32[8,32]{{1,0}} get-tuple-element(%p.1), index=1
  %fusion.12 = f32[8,32]{{1,0}} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{REMAT}/attention/mul"}}
  %gte.2 = s32[] get-tuple-element(%p.1), index=0
  ROOT %tuple.1 = (s32[], f32[8,32]{{1,0}}) tuple(%gte.2, %fusion.12)
}}

ENTRY %main.1 (a: f32[8,16], b: f32[16,32], c: f32[8,32]) -> f32[8,32] {{
  %a = f32[8,16]{{1,0}} parameter(0)
  %b = f32[16,32]{{1,0}} parameter(1)
  %c = f32[8,32]{{1,0}} parameter(2)
  %fusion.7 = f32[8,32]{{1,0}} fusion(%a, %b, %c), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{BWD}/embed/add_any"}}
  %while.4 = (s32[], f32[8,32]{{1,0}}) while(%c), condition=%cond.1, body=%body.1, metadata={{op_name="{FWD}/while"}}
  %while.9 = (s32[], f32[8,32]{{1,0}}) while(%c), condition=%cond.1, body=%body.1, metadata={{op_name="{FWD}/while"}}
  %fusion.2 = f32[8,32]{{1,0}} fusion(%c), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{FWD}/while/body/attention/mul"}}
  %copy.3 = f32[8,32]{{1,0}} copy(%c)
  ROOT %fusion.5 = f32[8,32]{{1,0}} fusion(%c), kind=kLoop, calls=%fused_computation.4
}}
""", 2: f"""HloModule jit_step, is_scheduled=true

ENTRY %main.2 (c: f32[8,32]) -> f32[8,32] {{
  %c = f32[8,32]{{1,0}} parameter(0)
  ROOT %fusion.2 = f32[8,32]{{1,0}} subtract(%c, %c), metadata={{op_name="jit(step)/adamw/sub"}}
}}
"""}


def test_op_names_resolve_fusions():
    one = scopes.op_names(HLO[1])
    assert scopes.module_name(HLO[1]) == "jit_step"
    # the 2x8x32x16 head matmul outweighs the 2x8x32x8 mlp dot and the root
    assert scopes.label(one["fusion.7"]) == "backward/lm_head"
    # a fusion with no matmul takes its root's path; wrapped segments match
    assert scopes.label(one["fusion.12"]) == "recompute/attention"
    assert scopes.label(one["fusion.5"]) == "backward/comm.scatter"
    assert scopes.label(one["fusion.2"]) == "forward/attention"
    assert scopes.label(one.get("copy.3")) == "unscoped/-"
    assert "add.2" not in one  # fused computations run as their fusion
    two = scopes.op_names(HLO[2])
    assert scopes.label(two["fusion.2"]) == "optimizer/adamw"


@pytest.mark.parametrize("path,expect", [
    ("jit(step)/adamw/sub", "optimizer/adamw"),
    (REMAT + "/while/body/closed_call/mlp/dot_general", "recompute/mlp"),
    (BWD + "/checkpoint/lm_head/transpose", "backward/lm_head"),
    (FWD + "/while/body/closed_call/cross_entropy/reduce_max",
     "forward/cross_entropy"),
    ("jit(step)/shard_map/jvp(comm.gather)/while/body/ppermute",
     "forward/comm.gather"),
    (FWD + "/mlp_extra/attention_like/add", "forward/-"),  # whole segments
    ("jit(step)/shard_map/div", "unscoped/-"),
    ("", "unscoped/-"),
])
def test_phase_and_block(path, expect):
    assert scopes.label(path) == expect


def synthetic():
    """Two steps (M = 1, then M = 2) on one device, in ns:

    module 1 [0, 100): fusion.7 [0, 10); while.4 [10, 60) holding
    fusion.12 [10, 30) and the nested while.9 [30, 60), which holds
    fusion.12 [35, 55); fusion.2 [60, 90); copy.3 [90, 100), which
    fusion.5 [95, 105) overlaps.
    between the modules: fusion.2 [110, 120), in no module.
    module 2 [150, 250): fusion.2 [160, 200).
    """
    ops = [(0, 10, "%fusion.7 = f32[8,32] fusion()"),
           (10, 60, "%while.4 = (s32[]) while()"), (10, 30, "fusion.12"),
           (30, 60, "while.9"), (35, 55, "fusion.12"), (60, 90, "fusion.2"),
           (90, 100, "copy.3"), (95, 105, "fusion.5"),
           (110, 120, "fusion.2"), (160, 200, "fusion.2")]
    mods = [(0, 100, "jit_step(11)"), (150, 250, "jit_step(12)")]
    host = [(0, 300, "bench.window"), (100, 104, "data.to_device"),
            (260, 263, "data.to_device"), (305, 310, "data.to_device")]
    text = scopes.xspace_text(
        [("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": mods}),
         ("/host:CPU", {"python": host})], 0)
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def test_reduce_known_answers():
    programs = {m: scopes.op_names(t) for m, t in HLO.items()}
    r = scopes.reduce(synthetic(), programs, [1, 2])
    assert r["steps_matched"] == 2
    got = {k: v * 1e9 for k, v in r["scopes_s"].items()}
    # copy.3 and fusion.5 overlap over [95, 100): 2.5 ns to each
    assert got == pytest.approx({
        "backward/lm_head": 10, "recompute/attention": 20 + 20,
        "forward/attention": 30, "unscoped/-": 7.5 + 10,
        "backward/comm.scatter": 7.5, "optimizer/adamw": 40})
    ph = {k: v * 1e9 for k, v in r["phases_s"].items()}
    assert ph == pytest.approx({"forward": 30, "recompute": 40,
                                "backward": 17.5, "optimizer": 40,
                                "unscoped": 17.5})
    # busy: [0, 30) + [35, 55) + [60, 105) + [110, 120) + [160, 200)
    assert r["busy_s"] * 1e9 == pytest.approx(145)
    assert dict((n, t * 1e9) for n, t in r["unscoped_ops"]) == pytest.approx(
        {"fusion.2": 10, "copy.3": 7.5})
    assert r["to_device_s"] * 1e9 == pytest.approx(4 + 3)


def test_unmatched_steps_count_unscoped():
    programs = {m: scopes.op_names(t) for m, t in HLO.items()}
    r = scopes.reduce(synthetic(), programs, [1, 2, 2])
    assert r["phases_s"]["unscoped"] == pytest.approx(r["busy_s"])


def test_keep_cuts_whole_steps(tmp_path):
    """``scopes.keep`` writes the first steps as a trace and the
    instructions they ran; the cut reduces to those steps alone."""
    from jax.profiler import ProfileData
    programs = {m: scopes.op_names(t) for m, t in HLO.items()}
    scopes.keep(synthetic(), programs, [1, 2], "jit_step", tmp_path, "cell",
                steps=1)
    kept = json.loads((tmp_path / "cell.scopes.json").read_text())
    assert kept["order"] == [1] and kept["module"] == "jit_step"
    assert set(kept["programs"]["1"]) == {"fusion.2", "fusion.7",
                                          "fusion.12", "while.4", "while.9"}
    cut = ProfileData.from_file(str(tmp_path / "cell.xplane.pb"))
    r = scopes.reduce(cut, {int(m): p for m, p in kept["programs"].items()},
                      kept["order"])
    got = {k: v * 1e9 for k, v in r["scopes_s"].items()}
    # fusion.5 ends after the step and is cut; copy.3 runs alone
    assert got == pytest.approx({
        "backward/lm_head": 10, "recompute/attention": 40,
        "forward/attention": 30, "unscoped/-": 10})
    assert r["to_device_s"] == 0


def test_readers_off_a_trace_read_nothing():
    from bench.harness import cell as C
    ctx = types.SimpleNamespace(steps=[object()])
    for name in SCOPE_METRICS:
        assert C.reader(name)(ctx) is None


RECORDED = "qwen1.5b-sft-longalign-1chip"  # bench/run.py --keep, on a v5e


def recorded():
    """The recorded trace of the window's first steps and its instruction
    map: (trace, programs, order, module)."""
    from jax.profiler import ProfileData
    kept = json.loads((DATA / f"{RECORDED}.scopes.json").read_text())
    pd = ProfileData.from_file(str(DATA / f"{RECORDED}.xplane.pb"))
    return (pd, {int(m): p for m, p in kept["programs"].items()},
            kept["order"], kept["module"])


def context(found, steps):
    from bench.harness import cell as C
    from bench.reference import dense_decoder
    return C.Context(run={}, chips=1, peaks=None, setup_s=1.0, window_s=1.0,
                     steps=[object()] * steps, S=1024, hbm_peak_bytes=0,
                     trace=None, step_flops=dense_decoder.step_flops,
                     scopes=found)


def test_scope_split_of_a_recorded_chip_trace():
    """``Context.scopes`` as a traced run fills it, from a trace of the
    qwen cell recorded on a TPU v5e: every step placed in its program,
    the phases adding up to the trace's busy time, every reader a
    number."""
    from bench.harness import cell as C, trace
    pd, programs, order, module = recorded()
    found = C.scope_split(pd, programs, order, module)
    assert found["steps_matched"] == len(order) == 3
    busy = trace.reduce(pd)["busy_s"]
    assert sum(found["phases_s"].values()) == pytest.approx(found["busy_s"])
    assert found["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert all(found["phases_s"][p] > 0 for p in scopes.PHASES)
    assert found["phases_s"]["unscoped"] < 0.5 * busy
    assert {"backward/lm_head", "optimizer/adamw", "forward/attention",
            "recompute/mlp"} <= set(found["scopes_s"])
    assert len(found["device_scopes"]) == 10
    ctx = context(found, len(order))
    got = {name: C.reader(name)(ctx) for name in SCOPE_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    phases = ("forward", "recompute", "backward", "optimizer")
    assert sum(got[f"{p}_device_ms"] for p in phases) == pytest.approx(
        1e3 * (busy - found["phases_s"]["unscoped"]) / len(order))


def test_scope_split_reports_nothing_on_a_step_count_mismatch():
    """A step more in the window than module events in the trace, and a
    trace with no module events at all (recorded before the step program
    named its passes): no split, the counts logged, and the readers read
    nothing."""
    from jax.profiler import ProfileData

    from bench.harness import cell as C
    pd, programs, order, module = recorded()
    logs = []
    assert C.scope_split(pd, programs, order + order[:1], module,
                         logs.append) is None
    assert logs == [f"[bench] scopes: 4 steps in the window, [3] {module} "
                    "module events start in it; 0 steps matched, no split"]
    old = ProfileData.from_file(str(DATA / "v5e-1chip-qwen.xplane.pb"))
    assert C.scope_split(old, programs, [2, 2], module, logs.append) is None
    ctx = context(None, 2)
    assert all(C.reader(name)(ctx) is None for name in SCOPE_METRICS)


def test_padding_counters_match_pad_token_share(tiny_cell):
    """``data.tokens_real`` / ``data.token_slots``, recorded by
    ``build_minibatch`` as it packs, read what ``pad_token_share`` reads
    from the same steps' plans and lengths."""
    from bench.harness import cell as C, traffic
    from repro.balance.strategies import make_plan
    from repro.data.packing import build_minibatch
    from repro.obs import metrics as obs_metrics

    cell = tiny_cell()
    world, S = 4, cell.mix["microbatch_tokens"]
    steps = traffic.steps(cell.mix, world, 7, 256)
    reg = obs_metrics.MetricsRegistry()
    records = []
    with obs_metrics.recording(reg):
        for s in steps:
            plan = make_plan(s.lengths, world, S,
                             strategy=cell.mix["strategy"])
            batch = build_minibatch(plan, s.samples, S)
            records.append(C.StepRecord(batch["tokens"].shape[0], s.lengths,
                                        0.0, 0.0))
    share = C.reader("pad_token_share")(
        types.SimpleNamespace(steps=records, chips=world, S=S))
    real, slots = reg.total("data.tokens_real"), reg.total("data.token_slots")
    assert 0 < real < slots
    assert 100.0 * (1.0 - real / slots) == pytest.approx(share, abs=1e-9)
    assert np.isclose(real, sum(sum(s.lengths) for s in steps))


@pytest.fixture(scope="module")
def v5e():
    """A described TPU v5e (nothing runs); skips where none can be."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def matmul_flops(text):
    """Matmul FLOPs of a compiled module by ``phase/block``, each ``while``
    body counted as often as its condition's bound: (each dot or
    convolution by its own op_name, by the op_name ``op_names`` gives the
    top-level operation it runs in)."""
    comps = scopes.parse(text)
    placed_at = scopes.op_names(text)
    bounds = {}
    for comp in text.split("\n\n"):  # a computation's bound: its constant
        head = re.match(r"(?:ENTRY )?%?([\w.-]+) ", comp)
        bound = re.search(r" s32\[\]\S* constant\((\d+)\)", comp)
        if head and bound:
            bounds[head.group(1)] = int(bound.group(1))
    own, placed = collections.Counter(), collections.Counter()

    def visit(comp, times, top):
        for ins in comps[comp].values():
            if ins.opcode in ("dot", "convolution"):
                flops = times * scopes._cost(ins, comps[comp])
                own[scopes.label(ins.op_name)] += flops
                placed[top or scopes.label(placed_at.get(ins.name))] += flops
            elif ins.opcode == "fusion":
                for c in ins.calls():
                    visit(c, times,
                          top or scopes.label(placed_at.get(ins.name)))
            elif ins.opcode == "while":
                body, cond = (re.search(k + r"=%([\w.-]+)", ins.attrs)
                              .group(1) for k in ("body", "condition"))
                visit(body, times * bounds[cond], top)

    visit(re.search(r"^ENTRY %?([\w.-]+)", text, re.M).group(1), 1, None)
    return own, placed


def test_matmul_flops_keep_the_pass_identities(v5e, tiny_cell):
    """The chip's compiled step (qwen layout at small widths, minibatch
    schedule, M = 2), its matmuls counted by pass: the backward is twice
    the forward in every block (dX and dW), the layer checkpoint is the
    only recompute, attention's batched matmuls (convolutions with a
    padded, dilated window) count what they compute, and the fusions'
    op_names move no matmul to another pass or block."""
    from bench.harness import cell as C

    H, F, V, S, HEADS, KV, HD = 256, 512, 1024, 256, 4, 2, 64
    cell = tiny_cell(hidden_size=H, intermediate_size=F, vocab_size=V,
                     num_attention_heads=HEADS, num_key_value_heads=KV,
                     head_dim=HD)
    cell.mix["microbatch_tokens"] = S
    prog = C.Program(cell, v5e.devices[:1])
    prog.compile(2)
    text = prog.compiled[2].as_text()
    assert re.search(r"convolution\(.*window=\{size=", text)
    own, placed = matmul_flops(text)
    assert placed == own
    fwd = lambda b: own[f"forward/{b}"]
    layers, slots = cell.config["run"]["num_hidden_layers"], 2 * S
    # by hand: the head; three MLP matmuls; q, k, v, o and, in one block
    # of S keys (block_kv = S), Q K^T and P V over every key
    assert fwd("lm_head") == 2 * slots * H * V
    assert fwd("mlp") == layers * 2 * slots * 3 * H * F
    assert fwd("attention") == layers * 2 * slots * (
        2 * H * HEADS * HD + 2 * H * KV * HD + 2 * S * HEADS * HD)
    for b in ("attention", "mlp", "lm_head"):
        assert own[f"backward/{b}"] == 2 * fwd(b)
    # each microbatch's forward runs once; the layer checkpoint redoes
    # attention whole and the MLP less its down projection, whose output
    # no backward reads, and nothing of the head
    assert own["recompute/attention"] == fwd("attention")
    assert own["recompute/mlp"] == pytest.approx(2 / 3 * fwd("mlp"))
    assert own["recompute/lm_head"] == 0
    assert {k.split("/")[0] for k in own} == {"forward", "recompute",
                                             "backward"}
