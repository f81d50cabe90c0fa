"""Reduction of a profiler trace by the step program's own names: the
phase and the block of every device operation.

The program names its blocks with ``jax.named_scope`` (``embed``,
``attention``, ``mlp``, ``moe``, ``lm_head``, ``cross_entropy``,
``adamw``, ``comm.gather``, ``comm.scatter``); JAX's own name stack marks
the passes.  Both reach the compiled module as the ``op_name`` metadata of
each HLO instruction, a ``/``-separated path such as
``jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/while/body/closed_call/attention/dot_general``.  A
segment may be wrapped by a transformation, ``jvp(comm.gather)`` or
``transpose(jvp(comm.scatter))``: it matches by the name inside the
wrappers, and whole segments only, never substrings.

- phase, checked in this order: ``adamw`` in the path -> optimizer; a
  ``rematted_computation`` -> recompute (each level of remat adds one);
  a ``transpose(`` wrapper -> backward; a ``jvp(`` wrapper -> forward;
  otherwise, or where no module or instruction matched, unscoped;
- block: the innermost segment that names a block, else ``-``;
- a fusion takes the path of its most expensive fused ``dot`` or
  ``convolution`` (2 x output elements x contracted size) where it has
  one, else of its fused root: the fused computation's instructions carry
  their own ``op_name``, and a fusion whose root adds the tied
  embedding's two gradients does the head's matmul as its work.

Device operations (``XLA Ops`` of each ``/device:TPU:<n>``) are named by
instruction, and one name means different things in different compiled
programs, so each is first placed in the module it ran in: the
``XLA Modules`` event of its device that holds it.  The module events
that start inside the window are matched, in time order, to the programs
the window's steps ran (one per step: the step's microbatch count); where
their counts disagree, no op is placed and all count unscoped
(``bench/harness/cell.py::scope_split`` then reports no split).  Time is that of the
childless operations inside ``bench.window``, as ``trace.reduce`` counts
busy time: where several run at once, the time they overlap is split
evenly between them, so that the parts add up to busy time.  Every number
is averaged over the devices.
"""
from __future__ import annotations

import collections
import json
import math
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench.harness import trace

BLOCKS = ("embed", "attention", "mlp", "moe", "lm_head", "cross_entropy",
          "adamw", "comm.gather", "comm.scatter")
PHASES = ("forward", "recompute", "backward", "optimizer")
UNSCOPED = "unscoped"
NO_BLOCK = "-"
MODULES_LINE = "XLA Modules"
TO_DEVICE_SPAN = "data.to_device"
HOST_SPANS = (trace.SPAN_PREFIX, "data.", "balance.")

_WRAPPED = re.compile(r"([\w.-]+)\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_DIMS = re.compile(r"^[a-z]\w*\[([\d,]*)\]")


# ---------------------------------------------------------------------------
# an op_name path -> phase and block
# ---------------------------------------------------------------------------
def segment(seg: str):
    """``transpose(jvp(comm.scatter))`` -> (("transpose", "jvp"),
    "comm.scatter")."""
    wraps = []
    m = _WRAPPED.match(seg)
    while m:
        wraps.append(m.group(1))
        seg = m.group(2)
        m = _WRAPPED.match(seg)
    return tuple(wraps), seg


def phase(path: str) -> str:
    parts = [segment(s) for s in path.split("/")] if path else []
    names = {n for _, n in parts}
    wraps = {w for ws, _ in parts for w in ws}
    if "adamw" in names:
        return "optimizer"
    if "rematted_computation" in names:
        return "recompute"
    if "transpose" in wraps:
        return "backward"
    if "jvp" in wraps:
        return "forward"
    return UNSCOPED


def block(path: str) -> str:
    for s in reversed(path.split("/")):
        name = segment(s)[1]
        if name in BLOCKS:
            return name
    return NO_BLOCK


def label(path: Optional[str]) -> str:
    """``phase/block`` of an op_name path (None: the op matched nothing)."""
    if path is None:
        return f"{UNSCOPED}/{NO_BLOCK}"
    return f"{phase(path)}/{block(path)}"


# ---------------------------------------------------------------------------
# optimized HLO text -> instruction -> op_name path
# ---------------------------------------------------------------------------
class _Instr:
    __slots__ = ("name", "shape", "opcode", "operands", "attrs", "root")

    def __init__(self, line: str):
        self.root = line.lstrip().startswith("ROOT ")
        lhs, rhs = line.strip().removeprefix("ROOT ").split(" = ", 1)
        self.name = lhs.lstrip("%")
        if rhs.startswith("("):  # a tuple type: up to its closing paren
            depth = 0
            for i, ch in enumerate(rhs):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            self.shape, rest = rhs[:i + 1], rhs[i + 2:]
        else:
            self.shape, _, rest = rhs.partition(" ")
        self.opcode, _, rest = rest.partition("(")
        depth, i = 1, 0
        while i < len(rest) and depth:
            depth += {"(": 1, ")": -1}.get(rest[i], 0)
            i += 1
        self.operands = re.findall(r"%([\w.-]+)", rest[:i - 1])
        self.attrs = rest[i:]

    @property
    def op_name(self) -> Optional[str]:
        m = _OP_NAME.search(self.attrs)
        return m.group(1) if m else None

    def calls(self) -> List[str]:
        m = re.search(r"calls=([^,]*(?:,\s*%[\w.-]+)*)", self.attrs)
        return re.findall(r"%([\w.-]+)", m.group(1)) if m else []


def _dims(shape: str) -> List[int]:
    m = _DIMS.match(shape)
    return [int(d) for d in m.group(1).split(",") if d] if m else []


def _cost(ins: _Instr, table: Dict[str, _Instr]) -> float:
    """Multiply-adds x 2 of a dot or convolution.  A convolution counts the
    window taps that land on an input element: XLA writes a batched
    matmul as a convolution whose padded, dilated window runs over the
    batch dimensions, and the taps on padding or dilation holes do no
    work."""
    out = _dims(ins.shape)
    args = [_dims(table[o].shape) for o in ins.operands[:2] if o in table]
    if len(args) < 2:
        return float(math.prod(out))
    lhs, rhs = args
    if ins.opcode == "dot":
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.attrs)
        dims = m.group(1).split(",") if m else []
        return 2.0 * math.prod(out) * math.prod(lhs[int(d)] for d in dims
                                                 if d)
    m = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", ins.attrs)
    if m is None:
        return float(math.prod(out))
    lab_l, lab_r, lab_o = m.groups()
    w = re.search(r"window=\{([^}]*)\}", ins.attrs)
    win = dict(kv.split("=", 1) for kv in w.group(1).split()) if w else {}

    def field(key, d, default):
        if key not in win:
            return default
        v = win[key].split("x")[d]
        return int(v.split("_")[0]) if key == "pad" else int(v)

    macs = (out[lab_o.index("b")] * out[lab_o.index("f")]
            * rhs[lab_r.index("i")])
    for d in range(sum(c.isdigit() for c in lab_o)):
        n, size = lhs[lab_l.index(str(d))], rhs[lab_r.index(str(d))]
        j = np.arange(out[lab_o.index(str(d))])[:, None]
        t = np.arange(field("size", d, size))[None, :]
        dil = field("lhs_dilate", d, 1)
        p = (j * field("stride", d, 1) + t * field("rhs_dilate", d, 1)
             - field("pad", d, 0))
        macs *= int(((p >= 0) & (p <= (n - 1) * dil) & (p % dil == 0)).sum())
    return 2.0 * macs


def parse(hlo_text: str) -> Dict[str, Dict[str, _Instr]]:
    """Computation name -> instruction name -> instruction."""
    comps: Dict[str, Dict[str, _Instr]] = {}
    cur = None
    for line in hlo_text.splitlines():
        if not line.strip() or line.startswith("HloModule"):
            continue
        if not line[0].isspace():
            if line.rstrip().endswith("{"):
                head = line.removeprefix("ENTRY ").lstrip("%")
                cur = comps.setdefault(head.split(" ", 1)[0], {})
            elif line.startswith("}"):
                cur = None
            continue
        if cur is not None and " = " in line:
            ins = _Instr(line)
            cur[ins.name] = ins
    return comps


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction -> the op_name path its time goes to, for every
    instruction of the module outside fused computations (those run as
    their fusion): a fusion resolved as the module docstring says."""
    comps = parse(hlo_text)
    fused = {c for ins in (i for cs in comps.values() for i in cs.values())
             if ins.opcode == "fusion" for c in ins.calls()}

    def matmuls(comp: str, seen=()):
        for ins in comps.get(comp, {}).values():
            if ins.opcode in ("dot", "convolution") and ins.op_name:
                yield _cost(ins, comps[comp]), ins.op_name
            if ins.opcode == "fusion":
                for c in ins.calls():
                    if c not in seen:
                        yield from matmuls(c, seen + (comp,))

    def resolve(ins: _Instr) -> Optional[str]:
        if ins.opcode != "fusion":
            return ins.op_name
        best = max(((c, n) for comp in ins.calls() for c, n in matmuls(comp)),
                   default=None, key=lambda cn: cn[0])
        if best is not None:
            return best[1]
        for comp in ins.calls():
            root = next((i for i in comps.get(comp, {}).values() if i.root),
                        None)
            if root is not None and root.op_name:
                return root.op_name
        return ins.op_name

    out: Dict[str, str] = {}
    for comp, table in comps.items():
        if comp in fused:
            continue
        for ins in table.values():
            path = resolve(ins)
            if path is not None:
                out[ins.name] = path
    return out


def module_name(hlo_text: str) -> str:
    """``HloModule jit_step, ...`` -> ``jit_step``."""
    first = hlo_text.split("\n", 1)[0]
    return first.removeprefix("HloModule ").split(",", 1)[0].strip()


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------
def events(pd):
    """({device plane: ([op (start, end, name)], [module (start, end,
    name)])}, [host span (start, end, name)]): the benchmark's spans and
    the program's (``data.*``, ``balance.*``)."""
    devices: Dict[str, tuple] = {}
    spans = []
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            ops, mods = devices.setdefault(plane.name, ([], []))
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                trace.op_name(e.name)) for e in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events)
        else:
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(HOST_SPANS))
    return devices, spans


def split_evenly(ops) -> Dict[object, float]:
    """Time of each name of [(start, end, name)]: the union of the
    intervals, each stretch split evenly between the operations running
    in it."""
    points = sorted([(s, 1, i) for i, (s, e, _) in enumerate(ops) if e > s]
                    + [(e, 0, i) for i, (s, e, _) in enumerate(ops) if e > s])
    out: Dict[object, float] = collections.Counter()
    active: Dict[int, None] = {}
    last = None
    for t, start, i in points:
        if active and t > last:
            share = (t - last) / len(active)
            for j in active:
                out[ops[j][2]] += share
        last = t
        if start:
            active[i] = None
        else:
            active.pop(i, None)
    return out


def step_events(mods, lo, hi, module: str, steps: int) -> List[tuple]:
    """A device's module events that start in [lo, hi), one per step of
    the window: those named for the steps' program (``<module>(<id>)``),
    else all of them; [] where neither count is ``steps``."""
    inside = sorted((s, e, name.split("(", 1)[0]) for s, e, name in mods
                    if lo <= s < hi)
    for cand in ([m for m in inside if m[2] == module], inside):
        if len(cand) == steps:
            return [(s, e) for s, e, _ in cand]
    return []


def reduce(pd, programs: Dict[object, Dict[str, str]], order: Sequence,
           module: str = "jit_step", top: int = 10) -> Optional[dict]:
    """The window's device time by phase and by ``phase/block``.

    ``programs``: each compiled program's instruction -> op_name path
    (:func:`op_names`), keyed as ``order`` names them; ``order``: the
    program each step of the window ran, in order; ``module``: the
    programs' HLO module name, which names their ``XLA Modules`` events.
    None where the trace holds no device operation."""
    devices, spans = events(pd)
    if not any(ops for ops, _ in devices.values()):
        return None
    win = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        lo = min(s for ops, _ in devices.values() for s, _, _ in ops)
        hi = max(e for ops, _ in devices.values() for _, e, _ in ops)
    n = len(devices)
    by_label: Dict[str, float] = collections.Counter()
    by_op: Dict[tuple, float] = collections.Counter()
    matched_steps, named = [], []
    for ops, mods in devices.values():
        ops = [(max(s, lo), min(e, hi), name) for s, e, name in ops
               if e > lo and s < hi]
        leaves, _ = trace.nest(ops)
        named.append(sum(1 for s, _, name in mods if lo <= s < hi
                         and name.split("(", 1)[0] == module))
        steps = step_events(mods, lo, hi, module, len(order))
        # where the counts disagree no op can be placed: all count unscoped
        keyed = list(order) if steps else None
        matched_steps.append(len(steps))
        located = []
        j = 0
        for s, e, name in sorted(leaves):
            while j < len(steps) and steps[j][1] <= s:
                j += 1
            key = (keyed[j] if keyed is not None and j < len(steps)
                   and steps[j][0] <= s else None)
            path = programs.get(key, {}).get(name) if key is not None else None
            located.append((s, e, (name, label(path))))
        for (name, lab), t in split_evenly(located).items():
            by_label[lab] += t / n
            by_op[(name, lab)] += t / n
    ns = 1e-9
    phases = {p: 0.0 for p in PHASES + (UNSCOPED,)}
    for lab, t in by_label.items():
        phases[lab.split("/", 1)[0]] += t * ns
    busy = sum(phases.values())
    to_device = sum(min(e, hi) - max(s, lo) for s, e, name in spans
                    if name == TO_DEVICE_SPAN and e > lo and s < hi)
    return {
        "devices": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy,
        "steps_matched": min(matched_steps),
        "module_events_in_window": named,
        "phases_s": phases,
        "scopes_s": {k: v * ns for k, v in by_label.items()},
        "device_scopes": [[k, v * ns] for k, v in
                          collections.Counter(by_label).most_common(top)],
        "unscoped_ops": [[name, v * ns] for (name, lab), v in
                         collections.Counter(by_op).most_common()
                         if lab.startswith(UNSCOPED)][:top],
        "to_device_s": to_device * ns,
    }


# ---------------------------------------------------------------------------
# a small recorded trace, for the tests
# ---------------------------------------------------------------------------
def keep(pd, programs, order, module: str, out: Path, workload: str,
         steps: int = 3):
    """The first ``steps`` steps of the traced window as a small trace,
    ``<out>/<workload>.xplane.pb`` (the ``XLA Ops`` and ``XLA Modules``
    lines and the host spans the reductions read), and beside it
    ``<workload>.scopes.json``: the programs' module name, the programs
    its steps ran, in order, and each program's instruction -> op_name
    path for the instructions in it.  ``bench/tests`` reads such a pair."""
    from jax.profiler import ProfileData

    devices, spans = events(pd)
    win = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    lo, end = win[0]
    planes, used = [], {}
    hi = None
    for plane, (ops, mods) in sorted(devices.items()):
        marks = step_events(mods, lo, end, module, len(order))
        hi = marks[steps - 1][1] if hi is None else hi
        ops = [o for o in ops if lo <= o[0] and o[1] <= hi]
        for m, (s, e) in zip(order, marks[:steps]):
            used.setdefault(m, set()).update(
                n for a, b, n in ops if s <= a and b <= e)
        planes.append((plane, {
            "XLA Ops": ops,
            "XLA Modules": [m for m in mods if lo <= m[0] and m[1] <= hi]}))
    host = [(max(s, lo), min(e, hi), n) for s, e, n in spans
            if s < hi and e > lo]
    planes.append(("/host:CPU", {"python": host}))
    text = xspace_text(planes, lo)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{workload}.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    (out / f"{workload}.scopes.json").write_text(json.dumps({
        "module": module, "order": [int(m) for m in order[:steps]],
        "programs": {str(m): {n: programs[m][n] for n in sorted(names)
                              if n in programs[m]}
                     for m, names in used.items()},
    }, indent=0, sort_keys=True))


def xspace_text(planes, t0: int) -> str:
    """A text-format XSpace of [(plane, {line: [(start_ns, end_ns,
    name)]})], times from ``t0``."""
    t0 = round(t0)
    names = sorted({n for _, lines in planes for evs in lines.values()
                    for _, _, n in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = []
    for k, (plane, lines) in enumerate(planes):
        body = []
        for j, (line, evs) in enumerate(lines.items()):
            ev = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: "
                f"{round((s - t0) * 1000)} duration_ps: "
                f"{round((e - s) * 1000)} }}\n" for s, e, n in evs)
            body.append(f'lines {{ id: {j + 1} name: "{line}" '
                        f"timestamp_ns: {round(t0)}\n{ev}}}\n")
        used = {n for evs in lines.values() for _, _, n in evs}
        meta = "".join(f'event_metadata {{ key: {ids[n]} value {{ id: '
                       f'{ids[n]} name: "{n}" }} }}\n' for n in sorted(used))
        out.append(f'planes {{ id: {k + 1} name: "{plane}"\n'
                   + "".join(body) + meta + "}\n")
    return "".join(out)
