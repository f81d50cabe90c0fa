"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, idle
share, exposed collective time and a breakdown.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO instruction (the text before
`` = ``).  They nest: a ``while`` spans the operations of its body, so the
time an operation runs is its self time (its span less its children's),
and only operations with no children count as running.  Host spans are
the benchmark's own ``jax.profiler.TraceAnnotation`` events, named
``bench.<what>``, on any host line; the device planes share the host's
clock in the trace.

- busy: the union of a device's childless operations inside the window;
- idle share: 1 - busy / window;
- exposed collective time: the part of the union of a device's collective
  operations (all-gather, all-reduce, reduce-scatter, collective-permute,
  all-to-all, with their -start/-done halves) during which no other
  operation of that device runs;
- idle gaps: the time between busy intervals, charged to the innermost
  host span open over it (``host: none`` where none is).

Every per-device number is averaged over the devices.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


def merge(iv: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(iv: Sequence[Interval]) -> float:
    return sum(e - s for s, e in iv)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def read(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def nest(ops):
    """(childless operations, self time by name) of one device's nested
    operations [(start, end, name)]."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    self_time = [e - s for s, e, _ in ops]
    leaf = [True] * len(ops)
    stack: List[int] = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            leaf[stack[-1]] = False
            self_time[stack[-1]] -= e - s
        stack.append(i)
    by_name: Dict[str, float] = collections.Counter()
    for (_, _, name), t in zip(ops, self_time):
        by_name[name] += t
    return [o for o, k in zip(ops, leaf) if k], by_name


def events(pd):
    """({device plane: [(start, end, name)]}, [host span (start, end, name)])."""
    devices: Dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                devices.setdefault(plane.name, []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                    for e in line.events)
            elif not device:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def segments(spans) -> List[Tuple[float, float, str]]:
    """The host's timeline cut where any span starts or ends, each piece
    labelled with the innermost span open over it."""
    spans = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid, best = (a + b) / 2, None
        for s, e, name in spans:
            if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        if best:
            out.append((a, b, f"host: {best[2]}"))
    return out


def charge(gaps: Sequence[Interval], segs) -> Dict[str, float]:
    """Gap time by the label of the host segment it falls in."""
    out: Dict[str, float] = collections.Counter()
    total = measure(gaps)
    i = j = 0
    while i < len(gaps) and j < len(segs):
        s, e = max(gaps[i][0], segs[j][0]), min(gaps[i][1], segs[j][1])
        if s < e:
            out[segs[j][2]] += e - s
        if gaps[i][1] < segs[j][1]:
            i += 1
        else:
            j += 1
    rest = total - sum(out.values())
    if rest > 0:
        out["host: none"] += rest
    return out


def reduce(pd, top: int = 10) -> Optional[dict]:
    """The trace's numbers, or None where it holds no device operation."""
    devices, spans = events(pd)
    if not any(devices.values()):
        return None
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        lo = min(s for ops in devices.values() for s, _, _ in ops)
        hi = max(e for ops in devices.values() for _, e, _ in ops)
    window = hi - lo
    n = len(devices)
    segs = segments(spans)
    busy = exposed = coll_total = 0.0
    op_time: Dict[str, float] = collections.Counter()
    gap_time: Dict[str, float] = collections.Counter()
    for ops in devices.values():
        ops = [(max(s, lo), min(e, hi), name) for s, e, name in ops
               if e > lo and s < hi]
        ops, self_time = nest(ops)
        union = merge([(s, e) for s, e, _ in ops])
        coll = merge([(s, e) for s, e, name in ops if is_collective(name)])
        other = merge([(s, e) for s, e, name in ops if not is_collective(name)])
        busy += measure(union)
        coll_total += measure(coll)
        exposed += measure(coll) - measure(intersect(coll, other))
        for name, t in self_time.items():
            op_time[name] += t / n
        edges = [lo] + [t for iv in union for t in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for k, v in charge(gaps, segs).items():
            gap_time[k] += v / n
    ns = 1e-9
    return {
        "devices": n,
        "window_s": window * ns,
        "busy_s": busy / n * ns,
        "idle_share": 1.0 - busy / n / window,
        "collective_s": coll_total / n * ns,
        "collective_exposed_s": exposed / n * ns,
        "device_ops": [[k, v * ns] for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v * ns] for k, v in gap_time.most_common(top)],
    }
