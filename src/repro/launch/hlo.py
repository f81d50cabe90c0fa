"""Loop-aware HLO text analysis for the dry-run roofline.

``compiled.cost_analysis()`` on the CPU backend counts while-loop bodies
ONCE (no trip-count multiplication), which under-counts scanned layer loops
by ~L× and microbatch loops by ~M×.  This module re-derives the roofline
inputs directly from the scheduled HLO text, multiplying nested computation
costs by the loop trip counts XLA records in
``backend_config={"known_trip_count": {"n": ...}}``:

  * flops           — dot ops: 2 · |out| · contracted;  elementwise: |out|
  * bytes           — per-instruction operands+output (fusion boundaries
                      only, mirroring HloCostAnalysis)
  * collective bytes/count by type (all-gather, all-reduce, reduce-scatter,
                      all-to-all, collective-permute)

All numbers are PER DEVICE (the SPMD-partitioned module has per-device
shapes).  Parsing is structural (shapes + operand names); no numerics.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1, "f8e4m3b11fnuz": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "tanh", "exponential", "log", "rsqrt", "sqrt", "negate", "abs", "sign",
    "floor", "ceil", "cosine", "sine", "logistic", "expm1", "log1p",
    "atan2", "remainder", "select", "clamp", "erf", "cbrt", "round-nearest-even",
    "round-nearest-afz",
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_elems(type_str: str) -> int:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instruction:
    name: str
    type_str: str
    op: str
    operands: List[str]
    attrs: str


@dataclasses.dataclass
class Computation:
    name: str
    instructions: List[Instruction]
    shapes: Dict[str, str]  # result name -> type str


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")


def _split_type_rest(rhs: str) -> Tuple[str, str]:
    """rhs starts with a type (scalar/array or tuple); return (type, rest)."""
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rhs[: i + 1], rhs[i + 1:].strip()
    i = rhs.find(" ")
    return rhs[:i], rhs[i + 1:].strip()


def _split_op_operands(rest: str) -> Tuple[str, List[str], str]:
    i = rest.find("(")
    op = rest[:i].strip()
    depth = 0
    j = i
    for j in range(i, len(rest)):
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
            if depth == 0:
                break
    inner = rest[i + 1: j]
    attrs = rest[j + 1:]
    operands = []
    depth = 0
    cur = ""
    for ch in inner:
        if ch == "," and depth == 0:
            operands.append(cur.strip())
            cur = ""
        else:
            if ch in "([{":
                depth += 1
            elif ch in ")]}":
                depth -= 1
            cur += ch
    if cur.strip():
        operands.append(cur.strip())
    names = []
    for o in operands:
        m = re.search(r"%?([\w.\-]+)$", o.strip())
        names.append(m.group(1) if m else o.strip())
    return op, names, attrs


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{", stripped)
            if m and ("->" in stripped):
                name = m.group(1)
                cur = Computation(name, [], {})
                if stripped.startswith("ENTRY"):
                    comps["__entry__"] = cur
                comps[name] = cur
            continue
        if stripped == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        try:
            type_str, rest = _split_type_rest(rhs)
            if "(" not in rest:
                continue
            op, operands, attrs = _split_op_operands(rest)
        except Exception:
            continue
        cur.shapes[name] = type_str
        cur.instructions.append(Instruction(name, type_str, op, operands, attrs))
    return comps


_TRIP_RE = re.compile(r'known_trip_count.*?"n"\s*:\s*"?(\d+)')


def _trip_count(attrs: str, comps, cond_name: Optional[str]) -> int:
    m = _TRIP_RE.search(attrs)
    if m:
        return int(m.group(1))
    # fallback: largest integer constant in the condition computation
    if cond_name and cond_name in comps:
        best = 1
        for ins in comps[cond_name].instructions:
            if ins.op == "constant":
                mm = re.search(r"constant\((\d+)\)", ins.attrs or "")
            else:
                mm = None
            if mm:
                best = max(best, int(mm.group(1)))
        return best
    return 1


_CALLS_RE = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    transcendentals: float = 0.0
    coll_bytes: Optional[Dict[str, float]] = None
    coll_count: Optional[Dict[str, float]] = None
    inter_pod_bytes: float = 0.0  # collective bytes crossing the pod (DCN)

    def __post_init__(self):
        if self.coll_bytes is None:
            self.coll_bytes = {k: 0.0 for k in COLLECTIVE_OPS}
        if self.coll_count is None:
            self.coll_count = {k: 0.0 for k in COLLECTIVE_OPS}

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += mult * other.flops
        self.bytes += mult * other.bytes
        self.transcendentals += mult * other.transcendentals
        self.inter_pod_bytes += mult * other.inter_pod_bytes
        for k in COLLECTIVE_OPS:
            self.coll_bytes[k] += mult * other.coll_bytes[k]
            self.coll_count[k] += mult * other.coll_count[k]

    @property
    def total_coll_bytes(self) -> float:
        return sum(self.coll_bytes.values())

    def as_dict(self):
        return {
            "flops": self.flops,
            "bytes": self.bytes,
            "transcendentals": self.transcendentals,
            "collective_bytes": dict(self.coll_bytes),
            "collective_count": dict(self.coll_count),
            "collective_bytes_total": self.total_coll_bytes,
            "inter_pod_bytes": self.inter_pod_bytes,
        }


# ---------------------------------------------------------------------------
# replica-group parsing: which devices does a collective span?
# ---------------------------------------------------------------------------
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_RG_LIST_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_STP_RE = re.compile(r"source_target_pairs=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")


def _parse_groups(attrs: str):
    """Returns a list of device-id groups, or None."""
    m = _RG_IOTA_RE.search(attrs)
    if m:
        import numpy as np
        ng, gs = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",") if d]
        n = 1
        for d in dims:
            n *= d
        arr = np.arange(n).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",") if p]
            arr = arr.transpose(perm)
        return arr.reshape(ng, gs).tolist()
    m = _RG_LIST_RE.search(attrs)
    if m:
        groups = []
        for g in re.findall(r"\{([0-9, ]*)\}", m.group(1)):
            ids = [int(x) for x in g.replace(" ", "").split(",") if x]
            if ids:
                groups.append(ids)
        return groups or None
    return None


def _parse_pairs(attrs: str):
    m = _STP_RE.search(attrs)
    if not m:
        return None
    pairs = []
    for g in re.findall(r"\{([0-9, ]*)\}", m.group(1)):
        ids = [int(x) for x in g.replace(" ", "").split(",") if x]
        if len(ids) == 2:
            pairs.append((ids[0], ids[1]))
    return pairs or None


def _inter_pod_fraction(ins: Instruction, base_op: str,
                        pod_of) -> float:
    """Per-device fraction of this collective's traffic that must cross the
    pod boundary (minimal-volume model: a reduction/gather over p pods
    moves at least (p-1)/p of its payload across; a permute pair crosses or
    it does not)."""
    if base_op == "collective-permute":
        pairs = _parse_pairs(ins.attrs)
        if not pairs:
            return 0.0
        crossing = sum(1 for a, b in pairs if pod_of(a) != pod_of(b))
        return crossing / len(pairs)
    groups = _parse_groups(ins.attrs)
    if not groups:
        return 0.0
    fr = []
    for g in groups:
        pods = {pod_of(d) for d in g}
        fr.append((len(pods) - 1) / max(len(pods), 1))
    return sum(fr) / len(fr)


def _dot_flops(ins: Instruction, comp: Computation) -> float:
    out = _shape_elems(ins.type_str)
    contracted = 1
    m = _CDIMS_RE.search(ins.attrs)
    if m and ins.operands:
        lhs_type = comp.shapes.get(ins.operands[0], "")
        dims = _shape_dims(lhs_type)
        for d in m.group(1).split(","):
            if d and int(d) < len(dims):
                contracted *= dims[int(d)]
    return 2.0 * out * contracted


def _comp_cost(comp_name: str, comps, memo, *, inside_fusion=False,
               pod_of=None) -> Cost:
    if comp_name in memo:
        return memo[comp_name]
    memo[comp_name] = Cost()  # break recursion defensively
    comp = comps.get(comp_name)
    if comp is None:
        return memo[comp_name]
    cost = Cost()
    for ins in comp.instructions:
        op = ins.op
        out_bytes = _shape_bytes(ins.type_str)
        in_bytes = sum(_shape_bytes(comp.shapes.get(o, "")) for o in ins.operands)
        if op == "while":
            cond = _COND_RE.search(ins.attrs)
            body = _CALLS_RE.search(ins.attrs)
            trip = _trip_count(ins.attrs, comps, cond.group(1) if cond else None)
            if body:
                cost.add(_comp_cost(body.group(1), comps, memo, pod_of=pod_of), trip)
            continue
        if op == "conditional":
            m = _BRANCH_RE.search(ins.attrs)
            if m:
                names = [re.sub(r"^%", "", s.strip()) for s in m.group(1).split(",")]
                sub = [_comp_cost(n, comps, memo, pod_of=pod_of) for n in names if n]
                if sub:
                    # charge the most expensive branch
                    best = max(sub, key=lambda c: c.flops + c.bytes)
                    cost.add(best)
            cost.bytes += out_bytes + in_bytes
            continue
        if op in ("fusion", "call", "custom-call", "map", "reduce", "sort",
                  "reduce-window", "scatter", "select-and-scatter",
                  "async-start", "async-update", "async-done"):
            m = _CALLS_RE.search(ins.attrs)
            if m:
                inner = _comp_cost(m.group(1), comps, memo, inside_fusion=True, pod_of=pod_of)
                cost.flops += inner.flops
                cost.transcendentals += inner.transcendentals
                for k in COLLECTIVE_OPS:
                    cost.coll_bytes[k] += inner.coll_bytes[k]
                    cost.coll_count[k] += inner.coll_count[k]
            if op == "reduce":
                cost.flops += _shape_elems(comp.shapes.get(ins.operands[0], "")) if ins.operands else 0
            cost.bytes += out_bytes + in_bytes
            continue
        base = op.split(".")[0]
        if base in COLLECTIVE_OPS:
            cost.coll_bytes[base] += in_bytes
            cost.coll_count[base] += 1
            cost.bytes += out_bytes + in_bytes
            if pod_of is not None:
                cost.inter_pod_bytes += in_bytes * _inter_pod_fraction(
                    ins, base, pod_of)
            continue
        if base == "dot":
            cost.flops += _dot_flops(ins, comp)
            cost.bytes += out_bytes + in_bytes
            continue
        if base == "convolution":
            # rare here; approximate as dot on output
            cost.flops += 2.0 * _shape_elems(ins.type_str)
            cost.bytes += out_bytes + in_bytes
            continue
        if base in _ELEMENTWISE:
            cost.flops += _shape_elems(ins.type_str)
            if base in ("tanh", "exponential", "log", "rsqrt", "sqrt",
                        "logistic", "expm1", "log1p", "erf", "cosine", "sine"):
                cost.transcendentals += _shape_elems(ins.type_str)
            if not inside_fusion:
                cost.bytes += out_bytes + in_bytes
            continue
        if base in ("parameter", "constant", "get-tuple-element", "tuple",
                    "bitcast", "after-all", "partition-id", "replica-id"):
            continue
        # data movement (copy, broadcast, slice, dus, transpose, reshape...)
        if not inside_fusion:
            cost.bytes += out_bytes + in_bytes
    memo[comp_name] = cost
    return cost


def analyze_hlo_text(text: str, *, devices_per_pod: int = 0) -> Cost:
    """devices_per_pod > 0 additionally attributes collective traffic that
    crosses the pod boundary (device ids are row-major over the mesh, so
    pod(id) = id // devices_per_pod)."""
    comps = parse_hlo(text)
    if "__entry__" not in comps:
        raise ValueError("no ENTRY computation found in HLO text")
    memo: Dict[str, Cost] = {}
    pod_of = (lambda d: d // devices_per_pod) if devices_per_pod else None
    return _comp_cost(comps["__entry__"].name, comps, memo, pod_of=pod_of)


# ===========================================================================
# roofline terms: per-chip peaks keyed by jax ``device_kind``
# ===========================================================================
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect over 4 links = 50 GB/s per link and direction.  The DCN
# figure is this repo's modeling assumption (~50 Gbit/s effective per chip
# across pods), not a published peak.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
                    "dcn_bw": 6.25e9},
}
# the chip the dry-run's production meshes model (repro.launch.mesh)
TARGET_DEVICE_KIND = "TPU v5 lite"


def peak_rates(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind without a
    row in ``PEAKS`` is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peak rates for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_terms(cost: Cost, *, chips: int, device_kind: str,
                   model_flops: float = 0.0):
    """cost is PER DEVICE; returns the three roofline terms in seconds plus
    bookkeeping.  model_flops is the global 6·N·D estimate."""
    peak = peak_rates(device_kind)
    compute_t = cost.flops / peak["flops"]
    memory_t = cost.bytes / peak["hbm_bw"]
    coll_t = cost.total_coll_bytes / peak["ici_bw"]
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": coll_t}
    dom = max(terms, key=terms.get)
    out = {
        **terms,
        "dominant": dom.replace("_s", ""),
        "hlo_flops_per_device": cost.flops,
        "hlo_bytes_per_device": cost.bytes,
        "collective_bytes_per_device": cost.total_coll_bytes,
        "collective_bytes_by_type": dict(cost.coll_bytes),
        "collective_count_by_type": dict(cost.coll_count),
        "chips": chips,
        "device_kind": device_kind,
    }
    if model_flops:
        hlo_global = cost.flops * chips
        out["model_flops"] = model_flops
        out["useful_flop_ratio"] = model_flops / max(hlo_global, 1.0)
    return out
