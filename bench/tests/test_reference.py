"""The configuration file chooses its reference module: a module the
harness has never seen, in a directory of its own, drives a whole run;
its ``program`` object reaches the compiled step; an unknown reference or
field fails before anything compiles."""
import dataclasses
import json
import time

import jax
import pytest

from bench.harness import cell as C
from bench.reference import dense_decoder

SEED = 2 ** 31 + 23

# a reference module as a later configuration would add it: its own file,
# the dense decoder's mathematics underneath, every call recorded, and a
# FLOP count planted at twice the dense decoder's
STAND_IN = '''
from bench.reference.dense_decoder import *  # noqa: F401,F403
from bench.reference import dense_decoder as dense

FAMILIES = {"dense": "swiglu", "moe": "swiglu"}
CALLS = []


class Shape(dense.Shape):
    @classmethod
    def from_config(cls, run):
        CALLS.append("Shape")
        return super().from_config(run)


def init_params(s, key_data, dtype=dense.jnp.float32):
    CALLS.append("init_params")
    return dense.init_params(s, key_data, dtype)


class Reference(dense.Reference):
    init_params = staticmethod(init_params)

    def __init__(self, *args, **kw):
        CALLS.append("Reference")
        super().__init__(*args, **kw)


def step_flops(run, lengths):
    CALLS.append("step_flops")
    return 2 * dense.step_flops(run, lengths)
'''


@pytest.fixture
def stand_in(tmp_path, monkeypatch):
    """A reference directory of the test's own: the stand-in, and links to
    the real modules."""
    for p in C.REFERENCES.glob("*.py"):
        (tmp_path / p.name).symlink_to(p.resolve())
    (tmp_path / "stand_in.py").write_text(STAND_IN)
    monkeypatch.setattr(C, "REFERENCES", tmp_path)
    monkeypatch.delitem(C.sys.modules, "bench.reference.stand_in",
                        raising=False)
    return lambda: C.reference_module("stand_in")


def test_a_new_reference_module_drives_the_run(tiny_cell, stand_in,
                                               monkeypatch):
    cell = tiny_cell()
    cell.config["reference"] = "stand_in"
    cell.end_to_end = [{"name": "mfu", "unit": "%"}]
    # the CPU has no published peak: give it one, as a chip has
    monkeypatch.setattr(C.peaks, "of", lambda dev: {"bf16_flops": 1e12})
    read, seen = C.reader, []
    monkeypatch.setattr(C, "reader", lambda name: lambda ctx: (
        seen.append(ctx), read(name)(ctx))[1])
    out = C.run(cell, SEED, 0.2, False, jax.devices()[:1],
                time.perf_counter(), log=lambda s: None)
    assert out["correct"], out["compared"]
    assert {"Shape", "init_params", "Reference", "step_flops"} <= set(
        stand_in().CALLS)
    ctx = seen[-1]
    dense = dataclasses.replace(ctx, step_flops=dense_decoder.step_flops)
    assert ctx.model_flops() == 2 * dense.model_flops() > 0
    mfu = out["metrics"]["mfu"]["value"]
    assert mfu == read("mfu")(ctx)
    assert mfu == pytest.approx(2 * read("mfu")(dense), rel=1e-12)


def test_the_reference_declares_the_families_it_implements(stand_in):
    """The registry entry's family and activation are checked against the
    configuration's reference module."""
    conf = json.loads((C.BENCH / "configs" / "qwen-1.5b-d8.json").read_text())
    moe = dict(conf, registry="llama4-maverick-400b-a17b")
    with pytest.raises(ValueError, match="dense_decoder"):
        C.model_config(moe)
    stand_in()
    assert C.model_config(dict(moe, reference="stand_in")).family == "moe"
    gelu = dict(conf, registry="grok-1-314b", reference="stand_in")
    with pytest.raises(ValueError, match="gelu"):
        C.model_config(gelu)


def test_program_object_reaches_the_compiled_step(tiny_cell):
    plain = tiny_cell()
    capped = tiny_cell()
    capped.config["program"] = {"final_logit_softcap": 30.0}
    texts = []
    for cell in (plain, capped):
        prog = C.Program(cell, jax.devices()[:1])
        prog.compile(1)
        texts.append(prog.compiled[1].as_text())
    assert C.model_config(capped.config) == dataclasses.replace(
        C.model_config(plain.config), final_logit_softcap=30.0)
    assert " tanh(" not in texts[0] and " tanh(" in texts[1]


def test_unknown_program_field_fails(tiny_cell):
    cell = tiny_cell()
    cell.config["program"] = {"no_such_field": 1}
    with pytest.raises(ValueError, match="no_such_field"):
        C.model_config(cell.config)
    with pytest.raises(ValueError, match="no_such_field"):
        C.Program(cell, jax.devices()[:1])


@pytest.mark.parametrize("name", ["no_such_module", "../harness/cell", ""])
def test_unknown_reference_fails_in_load_cell(tmp_path, monkeypatch, name):
    spec = json.loads((C.ROOT / "BENCHMARK.json").read_text())
    w = spec["workloads"][0]
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    conf = json.loads((C.ROOT / entry["file"]).read_text())
    conf["reference"] = name
    entry["file"] = str(tmp_path / "c.json")
    (tmp_path / "c.json").write_text(json.dumps(conf))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(C, "ROOT", tmp_path)
    with pytest.raises(ValueError, match="unknown reference"):
        C.load_cell(w["name"])
