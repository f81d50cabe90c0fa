#!/usr/bin/env python3
"""One traced run of a cell, as ``bench/run.py --trace 1`` makes it, with
the window's device time split by phase and block
(``bench/harness/scopes.py``).

    python3 bench/trace_scopes.py --workload <name> --seed <n> --seconds <s> \
        [--keep <dir>]

The run is ``bench.harness.cell.run`` itself: set-up, the traced window,
the check.  This script keeps what that run drops once it has read it:
the window's profiler trace and the compiled programs' optimized HLO, and
reduces the trace by scope.  The last line of stdout is the run's result
object with, added: ``scope_metrics``, the readers in ``bench/metrics``
that ``BENCHMARK.json`` does not list yet; ``tokens_per_s`` of the traced
window (the traced run's cost is this against ``bench/run.py --trace 0``
at the same seed); ``steps_matched``; ``busy_s`` of ``trace.py`` and of
the reduction; ``phases_s``; and the breakdowns ``device_scopes`` (the top
``phase/block`` pairs), ``scoped_ops`` (the top operations, each with its
pair) and ``unscoped_ops``.

``--keep`` writes the window's first three steps as a small trace
(``<workload>.xplane.pb``: the ``XLA Ops`` and ``XLA Modules`` lines and
the host spans the reductions read) and, beside it,
``<workload>.scopes.json``: the programs' module name, the programs its
steps ran, in order, and each program's instruction -> op_name path for
the instructions in it.
``bench/tests/test_scopes.py`` reads such a pair.

It stays until ``cell.run`` reduces the trace by scope itself (PERF.md,
Open question 10).  Exits 1 without the cell's TPU chips.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

KEEP_STEPS = 3
# the readers of bench/metrics that BENCHMARK.json does not list yet
SCOPE_METRICS = ("forward_device_ms", "recompute_device_ms",
                 "backward_device_ms", "optimizer_device_ms",
                 "loss_head_device_ms", "unscoped_device_share",
                 "host_to_device_ms")


def traced_run(spec, seed: int, seconds: float, devices, log):
    """``cell.run`` with ``traced``; (its result, the window's trace, each
    program's optimized HLO text by microbatch count, the window's step
    records)."""
    from bench.harness import cell as C, trace
    kept = {}

    class Keeping(C.Program):
        def __init__(self, *args):
            super().__init__(*args)
            self.records = []

        def step(self, step):
            self.records.append(super().step(step))
            return self.records[-1]

        def free(self):
            kept["hlo"] = {m: c.as_text() for m, c in self.compiled.items()}
            kept["records"] = self.records
            super().free()

    def read(path):
        kept["trace"] = trace.read(path)
        return kept["trace"]

    program, view = C.Program, C.trace
    C.Program = Keeping
    C.trace = types.SimpleNamespace(read=read, reduce=trace.reduce)
    try:
        out = C.run(spec, seed, seconds, True, devices, STARTED, log=log)
    finally:
        C.Program, C.trace = program, view
    steps = kept["records"][len(kept["records"]) - out["attempted"]:]
    return out, kept.get("trace"), kept["hlo"], steps


def keep(pd, programs, order, module: str, out: Path, workload: str,
         steps: int = KEEP_STEPS):
    """The first ``steps`` steps of the traced window, as a small trace and
    its instruction map (see the module's docstring)."""
    from jax.profiler import ProfileData

    from bench.harness import scopes, trace
    devices, spans = scopes.events(pd)
    win = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    lo, end = win[0]
    planes, used = [], {}
    hi = None
    for plane, (ops, mods) in sorted(devices.items()):
        marks = scopes.step_events(mods, lo, end, module, len(order))
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    import jax

    from bench.harness import cell as C, scopes

    C.use_compile_cache()
    spec = C.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print("trace_scopes: needs the cell's TPU chips", file=sys.stderr)
        return 1
    log = lambda s: print(s, file=sys.stderr, flush=True)
    out, pd, hlo, steps = traced_run(spec, args.seed, args.seconds,
                                     devices[:spec.chips], log)
    programs = {m: scopes.op_names(t) for m, t in hlo.items()}
    module = scopes.module_name(next(iter(hlo.values())))
    order = [r.m for r in steps]
    found = (scopes.reduce(pd, programs, order, module=module)
             if pd is not None else None)
    ctx = types.SimpleNamespace(steps=steps, scopes=found,
                                window_s=out["device"].get("window_s"))
    out["scope_metrics"] = {m: C.reader(m)(ctx) for m in SCOPE_METRICS}
    if ctx.window_s:
        out["tokens_per_s"] = C.reader("tokens_per_s")(ctx)
    if found is not None:
        out["steps_matched"] = found["steps_matched"]
        out["busy_s"] = {"trace": out["device"].get("busy_s"),
                         "scopes": found["busy_s"]}
        out["module_events"] = found["module_events"]
        out["phases_s"] = found["phases_s"]
        out.setdefault("breakdown", {}).update(
            device_scopes=found["device_scopes"],
            scoped_ops=found["device_ops"], unscoped_ops=found["unscoped_ops"])
        log(f"[scopes] {len(steps)} steps, {found['steps_matched']} "
            f"matched; device_scopes {found['device_scopes']}")
        if args.keep and found["steps_matched"]:
            keep(pd, programs, order, module, Path(args.keep), spec.workload)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
