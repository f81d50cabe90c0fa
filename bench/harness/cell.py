"""One run of one cell: set-up, the measured window, the check, the result.

Set-up builds one object, :class:`Program` (the program's compiled step
for every microbatch count the cell's traffic reaches, with its training
state), drives it through the run's first three steps, and hands that
same object to the window.  Every step, in set-up and in the window, goes
through :meth:`Program.step`, which does what ``repro.launch.train`` does:
``make_plan``, ``build_minibatch``, ``jax.device_put`` onto the step's
batch shardings, the step, and a wait on its loss.

Configuration, traffic, limits, metric readers and the plain reference
are files found by name (``bench/configs``, ``bench/traffic``,
``bench/limits``, ``bench/metrics``, ``bench/reference``): the
configuration file names its reference module, which owns everything that
depends on the architecture (``bench/reference/__init__.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.balance.strategies import make_plan  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import gspmd  # noqa: E402
from repro.data.packing import build_minibatch  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402

from bench.harness import check, peaks, scopes, trace, traffic  # noqa: E402

FIRST_STEPS = 3  # the steps the reference follows
BATCH_KEYS = {"tokens": jnp.int32, "targets": jnp.int32,
              "positions": jnp.int32, "segment_ids": jnp.int32,
              "loss_mask": jnp.float32}
REFERENCES = BENCH / "reference"
# a configuration's published key -> the program's ModelConfig field
MODEL_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
              "tie_word_embeddings": "tie_embeddings"}


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    workload: str
    config: dict  # bench/configs/<config>.json
    mix: dict  # bench/traffic/<traffic>.json
    chips: int
    limits: Optional[dict]  # bench/limits/<workload>.json
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]

    @property
    def ref(self):
        """The configuration's reference module."""
        return reference_module(self.config["reference"])


def load_cell(workload: str) -> Cell:
    """A cell of BENCHMARK.json, with the files it names."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = next(x for x in spec["workloads"] if x["name"] == workload)
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", [workload] if m["moves"] in names
                                  else [])]
    return cell_from_files(workload, ROOT / conf["file"], w["traffic"],
                           w["chips"], e2e, layer)


def cell_from_files(workload: str, config_file: Path, mix: str, chips: int,
                    end_to_end=(), per_layer=()) -> Cell:
    limits_path = check.DIR / f"{workload}.json"
    cell = Cell(workload, json.loads(Path(config_file).read_text()),
                traffic.load(mix), chips,
                check.load_limits(workload) if limits_path.exists() else None,
                list(end_to_end), list(per_layer))
    cell.ref  # an unknown reference fails here, before anything compiles
    return cell


def reference_module(name: str):
    """``bench/reference/<name>.py``, imported once as
    ``bench.reference.<name>``."""
    path = (REFERENCES / f"{name}.py").resolve()
    if not name.isidentifier() or not path.is_file():
        known = sorted(p.stem for p in REFERENCES.glob("[!_]*.py"))
        raise ValueError(f"unknown reference {name!r}: no {path}; known: "
                         f"{known}")
    qualified = f"bench.reference.{name}"
    mod = sys.modules.get(qualified)
    if mod is None or Path(mod.__file__).resolve() != path:
        spec = importlib.util.spec_from_file_location(qualified, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[qualified] = mod
        spec.loader.exec_module(mod)
    return mod


def model_config(conf: dict):
    """The program's ModelConfig: the registry entry with the sizes the
    configuration file runs at (each key of ``MODEL_KEYS`` that ``run``
    has), then the file's ``program`` object (ModelConfig fields and their
    values).  The registry family and activation have to be ones the
    configuration's reference implements."""
    base = get_config(conf["registry"])
    fields = {f.name for f in dataclasses.fields(base)}
    program = conf.get("program", {})
    unknown = sorted(set(program) - fields)
    if unknown:
        raise ValueError(f"{conf['name']}: 'program' names no ModelConfig "
                         f"field {unknown}")
    sizes = {MODEL_KEYS[k]: v for k, v in conf["run"].items()
             if k in MODEL_KEYS}
    cfg = dataclasses.replace(base, **{**sizes, **program})
    families = reference_module(conf["reference"]).FAMILIES
    if families.get(cfg.family) != cfg.activation:
        raise ValueError(
            f"{conf['name']}: the reference {conf['reference']!r} implements "
            f"{families} (family: activation), not {cfg.family}: "
            f"{cfg.activation}")
    return cfg


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache's key), for every program however
    quick to compile, so that a warm run compiles nothing."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def reader(name: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StepRecord:
    m: int  # microbatches per device (the compiled program's M)
    lengths: List[int]
    prep_s: float  # host: plan + pack + put
    loss: float
    run_s: float = 0.0  # host: dispatch + the wait on the loss


class Program:
    """The program's step, compiled ahead of time for each microbatch count
    of the cell's traffic, and its training state."""

    def __init__(self, cell: Cell, devices):
        self.cell = cell
        self.ref = ref = cell.ref
        self.world = len(devices)
        self.S = cell.mix["microbatch_tokens"]
        self.mesh = Mesh(np.asarray(devices).reshape(self.world, 1),
                         ("data", "model"))
        self.cfg = model_config(cell.config)
        self.gcfg = gspmd.GSPMDConfig(
            rules=gspmd.ShardingRules(), schedule=cell.config["schedule"],
            comm=cell.config["comm"], block_kv=min(512, self.S))
        self.opt = AdamWConfig(**cell.config["optimizer"])
        self.shape = ref.Shape.from_config(cell.config["run"])
        self.jitted = gspmd.jit_train_step(self.cfg, self.mesh, self.gcfg,
                                           self.opt)
        self.p_sh, self.o_sh = gspmd.train_state_shardings(
            self.cfg, self.mesh, self.gcfg)
        self.compiled: Dict[int, object] = {}
        self.wire_bytes: Dict[int, float] = {}  # per device per step
        shape = self.shape
        self._init_state = jax.jit(
            lambda kd: (lambda p: (p, adamw_init(p)))(ref.init_params(shape, kd)),
            out_shardings=(self.p_sh, self.o_sh))
        self._init_params = jax.jit(lambda kd: ref.init_params(shape, kd),
                                    out_shardings=self.p_sh)
        self.params = self.opt_state = None

    def microbatches(self, step: traffic.Step) -> int:
        return max(self.plan(step).max_microbatches, 1)

    def plan(self, step: traffic.Step):
        return make_plan(step.lengths, self.world, self.S,
                         strategy=self.cell.mix["strategy"])

    def compile(self, m: int):
        """AOT-compile the step for M microbatches, recording the comm
        counters the program emits while it traces."""
        stand_in = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                      sharding=sh)
        p_shape = jax.eval_shape(lambda: self.ref.init_params(
            self.shape, np.zeros(2, np.uint32)))
        o_shape = jax.eval_shape(adamw_init, p_shape)
        b_shape = {k: jax.ShapeDtypeStruct((m, self.world, self.S), dt)
                   for k, dt in BATCH_KEYS.items()}
        b_sh = gspmd.train_batch_shardings(b_shape, self.mesh, self.gcfg)
        args = (jax.tree.map(stand_in, p_shape, self.p_sh),
                jax.tree.map(stand_in, o_shape, self.o_sh),
                jax.tree.map(stand_in, b_shape, b_sh))
        reg = obs_metrics.MetricsRegistry()
        with obs_metrics.recording(reg), self.mesh:
            lowered = self.jitted.lower(*args)
        reg.step()
        self.wire_bytes[m] = reg.total("comm.bytes_wire")
        self.compiled[m] = lowered.compile()

    def hbm_peak_bytes(self) -> int:
        """Per-device bytes of the largest compiled step: arguments + temp
        + outputs - aliased (the donated state counted once)."""
        out = 0
        for c in self.compiled.values():
            a = c.memory_analysis()
            out = max(out, a.argument_size_in_bytes + a.temp_size_in_bytes
                      + a.output_size_in_bytes - a.alias_size_in_bytes)
        return out

    def init(self, seed: int):
        self.params, self.opt_state = self._init_state(
            self.ref.seed_key_data(seed))

    def prepare(self, step: traffic.Step):
        with jax.profiler.TraceAnnotation("bench.plan"):
            plan = self.plan(step)
        with jax.profiler.TraceAnnotation("bench.pack"):
            batch = build_minibatch(plan, step.samples, self.S)
        with jax.profiler.TraceAnnotation("bench.put"):
            batch = jax.device_put(batch, gspmd.train_batch_shardings(
                batch, self.mesh, self.gcfg))
        return batch

    def run(self, batch) -> float:
        m = batch["tokens"].shape[0]
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.params, self.opt_state, metrics = self.compiled[m](
                self.params, self.opt_state, batch)
        with jax.profiler.TraceAnnotation("bench.wait"):
            return float(metrics["loss"])

    def step(self, step: traffic.Step) -> StepRecord:
        t0 = time.perf_counter()
        batch = self.prepare(step)
        t1 = time.perf_counter()
        loss = self.run(batch)
        return StepRecord(batch["tokens"].shape[0], step.lengths, t1 - t0,
                          loss, time.perf_counter() - t1)

    # -- what the check reads, before the next step donates the state -------
    def grad_norms(self) -> Dict[str, float]:
        """The first step's clipped gradient per leaf, from AdamW's first
        moment after one step: m = (1 - b1) g."""
        sq = self.ref.leaf_sq_norms(self.opt_state["m"])
        return {k: v / (1.0 - self.opt.b1)
                for k, v in self.ref.sq_to_norms(sq).items()}

    def change_norms(self, seed: int) -> Dict[str, float]:
        p0 = self._init_params(self.ref.seed_key_data(seed))
        sq = self.ref.change_sq_norms(self.params, p0)
        del p0
        return self.ref.sq_to_norms(sq)

    def free(self):
        self.params = self.opt_state = None
        gc.collect()


def first_steps(prog: Program, cycle, seed: int):
    """Train the first steps from the seed; the program's side of the
    check, and the seconds spent reading it (not set-up)."""
    prog.init(seed)
    losses, grad = [], None
    check_s = 0.0
    for i in range(FIRST_STEPS):
        losses.append(prog.step(cycle[i]).loss)
        t = time.perf_counter()
        if i == 0:
            grad = prog.grad_norms()
        if i == FIRST_STEPS - 1:
            change = prog.change_norms(seed)
        check_s += time.perf_counter() - t
    return {"loss": losses, "grad": grad, "change": change}, check_s


def reference(cell: Cell, devices, dtype=jnp.float32, precision="highest"):
    ref, o = cell.ref, cell.config["optimizer"]
    return ref.Reference(ref.Shape.from_config(cell.config["run"]),
                         ref.AdamW(**o), cell.mix["microbatch_tokens"],
                         devices, dtype=dtype, precision=precision)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Context:
    """What the metric readers read."""

    run: dict  # the configuration's sizes
    chips: int
    peaks: Optional[dict]
    setup_s: float
    window_s: float
    steps: List[StepRecord]
    S: int
    hbm_peak_bytes: int
    trace: Optional[dict]
    step_flops: Callable  # the reference module's (run, lengths) -> FLOPs
    scopes: Optional[dict] = None  # scopes.reduce of the traced window

    def model_flops(self) -> float:
        return sum(self.step_flops(self.run, s.lengths) for s in self.steps)


class CompileCounter:
    """Backend compiles, counted by JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == self.EVENT:
            self.n += 1


def scope_split(pd, programs: Dict[int, Dict[str, str]], order: List[int],
                module: str, log=print) -> Optional[dict]:
    """``scopes.reduce`` of the traced window, or None where the window's
    steps and its module events differ in count: then no device op can be
    placed in its program, and the split would put the whole step under
    ``unscoped``."""
    found = scopes.reduce(pd, programs, order, module=module)
    if found is not None and found["steps_matched"] != len(order):
        log(f"[bench] scopes: {len(order)} steps in the window, "
            f"{found['module_events_in_window']} {module} module events "
            f"start in it; {found['steps_matched']} steps matched, no split")
        return None
    return found


def run(cell: Cell, seed: int, seconds: float, traced: bool, devices,
        started: float, log=print, keep: Optional[Path] = None) -> dict:
    """One run; returns the result line's object.  ``started`` is the
    process's start on ``time.perf_counter``'s clock.  ``keep``: a
    directory where a traced run writes its window's first steps
    (``scopes.keep``)."""
    compiles = CompileCounter()
    prog = Program(cell, devices)
    cycle = traffic.steps(cell.mix, prog.world, seed, prog.cfg.vocab_size)
    firsts: Dict[int, traffic.Step] = {}
    for s in cycle:
        firsts.setdefault(prog.microbatches(s), s)
    for m, s in sorted(firsts.items()):
        prog.compile(m)
        # on several chips, moving a batch of a new shape onto its
        # shardings compiles too: once per shape, here and not in the window
        prog.prepare(s)
    mine, check_s = first_steps(prog, cycle, seed)
    # set-up's objects (traced and compiled programs, imports) leave the
    # collector's reach, so that no full collection scans them in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - started - check_s
    log(f"[bench] set-up {setup_s:.3f} s, backend compiles {compiles.n}, "
        f"microbatch counts {sorted(prog.compiled)}")

    trace_dir = CACHE / "trace" / cell.workload
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    before = compiles.n
    full_gcs = gc.get_stats()[2]["collections"]
    records: List[StepRecord] = []
    i = FIRST_STEPS
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            records.append(prog.step(cycle[i % len(cycle)]))
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    in_window = compiles.n - before
    summary = found = None
    if traced:
        jax.profiler.stop_trace()
        pb = sorted(trace_dir.rglob("*.xplane.pb"))
        pd = trace.read(str(pb[-1])) if pb else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = trace.reduce(pd) if pd is not None else None
        if summary is not None:
            hlo = {m: c.as_text() for m, c in prog.compiled.items()}
            programs = {m: scopes.op_names(t) for m, t in hlo.items()}
            module = scopes.module_name(next(iter(hlo.values())))
            order = [r.m for r in records]
            found = scope_split(pd, programs, order, module, log)
            if found is not None:
                log(f"[bench] scopes: {found['steps_matched']} steps matched;"
                    f" busy {found['busy_s']} s (trace {summary['busy_s']}),"
                    f" phases {found['phases_s']}; largest unscoped ops "
                    f"{found['unscoped_ops'][:5]}")
                if keep is not None:
                    scopes.keep(pd, programs, order, module, keep,
                                cell.workload)
        del pd  # the window's whole trace, before the reference runs
    full_gcs = gc.get_stats()[2]["collections"] - full_gcs
    gc.unfreeze()
    log(f"[bench] window {window_s:.3f} s, {len(records)} steps, backend "
        f"compiles in the window: {in_window}")
    slow = sorted(range(len(records)), key=lambda k: -records[k].run_s)[:3]
    log("[bench] slowest dispatch + wait (step in the window, M, s): "
        + ", ".join(f"({k}, {records[k].m}, {records[k].run_s:.4f})"
                    for k in slow)
        + f"; median {statistics.median(r.run_s for r in records):.4f} s; "
        f"full collections in the window: {full_gcs}")

    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"[bench] runtime peak_bytes_in_use per device: "
        f"{[s.get('peak_bytes_in_use') for s in stats]}")
    hbm = prog.hbm_peak_bytes()
    prog.free()
    del prog
    gc.collect()

    theirs = reference(cell, devices).run(
        seed, [s.samples for s in cycle[:FIRST_STEPS]])
    numbers = check.gaps(mine, theirs)
    log(f"[bench] losses program {mine['loss']} reference {theirs['loss']}; "
        f"worst gradient leaf {numbers['grad_leaf']}, worst change leaf "
        f"{numbers['change_leaf']}, still leaves {numbers['still_leaves']}")
    if cell.limits is None:
        raise FileNotFoundError(f"no limits for {cell.workload}")
    compared = check.judge(numbers, cell.limits)

    dev = devices[0]
    ctx = Context(run=cell.config["run"], chips=len(devices),
                  peaks=peaks.of(dev),
                  setup_s=setup_s, window_s=window_s, steps=records,
                  S=cell.mix["microbatch_tokens"],
                  hbm_peak_bytes=hbm, trace=summary,
                  step_flops=cell.ref.step_flops, scopes=found)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = sum(1 for r in records if not math.isfinite(r.loss))
    correct = (in_window == 0 and failed == 0
               and all(c["ok"] for c in compared.values()))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if traced and summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
        if found is not None:
            out["breakdown"]["device_scopes"] = found["device_scopes"]
    out["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                       for k, c in compared.items()}
    return out
