"""The step program's names on its compiled operations, and the host spans
of its input path.

The device trace of a step reads each operation's ``op_name`` metadata:
``jax.named_scope`` names the blocks (``attention``, ``mlp``, ``lm_head``,
``adamw``, ``comm.gather``, ...), and JAX's name stack the passes
(``jvp(``, ``transpose(``, ``rematted_computation``).  A segment may be
wrapped by a transformation (``transpose(jvp(comm.scatter))``), and
matches by the name inside.  The strict check that every matmul of the
chip's compiled step carries one block is in ``test_chip_compile.py``:
the CPU compiler drops the metadata of the batched dots it rewrites.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.balance.strategies import make_plan
from repro.configs import get_reduced
from repro.core import gspmd
from repro.data.packing import build_minibatch

BLOCKS = {"attention", "mlp", "lm_head"}
_WRAPPED = re.compile(r"[\w.-]+\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(")


def names(path):
    """The segments of an op_name path, each unwrapped."""
    out = []
    for seg in path.split("/"):
        m = _WRAPPED.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAPPED.match(seg)
        out.append(seg)
    return out


def instructions(hlo):
    """(opcode, op_name or None) of every instruction of an HLO text."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), op.group(1) if op else None))
    return out


def compiled_step(world, comm):
    cfg = get_reduced("qwen-1.5b")
    mesh = Mesh(np.asarray(jax.devices()[:world]).reshape(world, 1),
                ("data", "model"))
    gcfg = gspmd.GSPMDConfig(rules=gspmd.ShardingRules(),
                             schedule="minibatch", comm=comm, block_kv=32)
    assert gcfg.remat
    M, S = 2, 64
    shapes = {k: jax.ShapeDtypeStruct((M, world, S), dt) for k, dt in (
        ("tokens", jnp.int32), ("targets", jnp.int32),
        ("positions", jnp.int32), ("segment_ids", jnp.int32),
        ("loss_mask", jnp.float32))}
    jitted, args = gspmd.build_train_artifacts(cfg, mesh, gcfg, shapes)
    return jitted.lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def one_device_step():
    return instructions(compiled_step(1, "odc"))


def test_every_matmul_names_one_block(one_device_step):
    mm = [p for op, p in one_device_step if op in ("dot", "convolution")
          and p is not None]
    assert len(mm) >= 30
    for p in mm:
        assert len(BLOCKS & set(names(p))) == 1, p
    seen = {b for p in mm for b in BLOCKS & set(names(p))}
    assert seen == BLOCKS


def test_the_update_is_adamw(one_device_step):
    sqrt = [p for op, p in one_device_step if op == "sqrt"]
    assert sqrt and all(p is not None and "adamw" in names(p) for p in sqrt)
    assert any(p and names(p)[-2:] == ["adamw", "sub"]
               for _, p in one_device_step)


def test_both_levels_of_remat_are_named(one_device_step):
    """Recomputed operations sit under ``rematted_computation`` below a
    layer's checkpoint alone, in the backward of each microbatch: the one
    level of remat left.  The minibatch loop runs a microbatch's forward
    once, right before its backward, so the head and the loss are never
    recomputed."""
    # the layer loop inside the backward of the microbatch loop, where the
    # folded gradient sum's backward (``transformer._fold_scan``) runs the
    # layer's checkpoint
    layer_remat = ("jit(step)/while/body/closed_call/transpose(jvp())/"
                   "while/body/closed_call/transpose(jvp(jvp()))/"
                   "checkpoint/rematted_computation/")
    blocks = set()
    for _, p in one_device_step:
        # reduction bodies carry paths relative to their reduce
        if p is None or not p.startswith("jit("):
            continue
        segs = names(p)
        if "rematted_computation" in segs:
            assert segs.count("checkpoint") == 1, p
            assert p.startswith(layer_remat), p
            blocks |= BLOCKS & set(segs)
    assert blocks == {"attention", "mlp"}


def test_collective_permutes_name_gather_or_scatter():
    perms = [p for op, p in instructions(compiled_step(4, "odc"))
             if op.startswith("collective-permute")]
    assert perms
    kinds = set()
    for p in perms:
        comm = {"comm.gather", "comm.scatter"} & set(names(p or ""))
        assert len(comm) == 1, p
        kinds |= comm
    assert kinds == {"comm.gather", "comm.scatter"}


def test_input_path_opens_host_spans(tmp_path):
    from jax.profiler import ProfileData
    lengths = [10, 20, 30, 40]
    jax.profiler.start_trace(str(tmp_path))
    try:
        plan = make_plan(lengths, 2, 64)
        build_minibatch(plan, [np.arange(n) for n in lengths], 64)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {e.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events}
    assert {"balance.make_plan", "data.pack", "data.to_device"} <= seen
