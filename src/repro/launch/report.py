"""Telemetry report CLI: render metrics JSONL + trace pairs, or simulate.

Two modes under ``python -m repro.launch.report``:

**Render** (default) — turn the telemetry artifacts a run wrote into one
text/markdown utilization report:

  python -m repro.launch.report --metrics real.jsonl --trace real.json \
      --sim-metrics sim.jsonl --sim-trace sim.json -o report.md

Sections (each appears when its inputs are given): run metadata, comm
bytes by backend/op/tier with the wire/logical compression ratio,
message-size percentiles off the log2 histograms, per-step time
percentiles and final gauges, counter-name schema comparison between the
real and sim metrics files, per-lane busy fractions + straggler ranking
off the traces, and the sim-vs-real divergence report
(``repro.obs.divergence``) with one calibration scalar per simulator
cost hook.

**Simulate** (``--simulate``) — produce the SIM side of a pair: balance
the same synthetic length stream the real driver trains on, run
``repro.sim.simulate_training`` under a recording registry, and write
metrics JSONL + a Chrome trace whose counter names match what a real
``launch.train`` run of the same config emits:

  python -m repro.launch.report --simulate --comm odc --world 8 \
      --steps 2 --metrics sim.jsonl --trace sim.json
"""
from __future__ import annotations

import argparse
from typing import Dict, List, Optional, Tuple

from repro.obs import divergence as obs_div
from repro.obs import metrics as obs_metrics

_BYTE_NAMES = ("comm.messages", "comm.bytes_logical", "comm.bytes_wire")


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.2f} {unit}" if unit != "B" else f"{n:.0f} B"
        n /= 1024.0
    return f"{n:.2f} TiB"


def _pct(series: List[float], q: float) -> float:
    if not series:
        return 0.0
    s = sorted(series)
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[i]


def _final(rows: List[dict]) -> List[dict]:
    return rows[-1]["metrics"] if rows else []


def _gauge_series(rows: List[dict], name: str) -> List[float]:
    out = []
    for row in rows:
        for m in row.get("metrics", ()):
            if m.get("kind") == "gauge" and m.get("name") == name:
                out.append(m["value"])
    return out


def _hist_quantile(buckets: Dict[str, float], q: float) -> float:
    """Bucket-upper-bound quantile off a serialized histogram row."""
    items = sorted(((float("inf") if k == "overflow" else float(k)), c)
                   for k, c in buckets.items())
    total = sum(c for _, c in items)
    if total <= 0:
        return 0.0
    target = q * total
    seen = 0.0
    for ub, c in items:
        seen += c
        if seen >= target and c > 0:
            return ub
    return items[-1][0]


def _section_meta(title: str, meta: dict) -> List[str]:
    lines = [f"## {title}", ""]
    for k in sorted(meta):
        lines.append(f"- {k}: {meta[k]}")
    return lines + [""]


def _section_bytes(metrics: List[dict]) -> List[str]:
    by: Dict[Tuple[str, str, str], Dict[str, float]] = {}
    hists: Dict[Tuple[str, str, str], dict] = {}
    for m in metrics:
        lab = m.get("labels", {})
        key = (lab.get("backend", "?"), lab.get("op", "?"),
               lab.get("tier", "?"))
        if m["kind"] == "counter" and m["name"] in _BYTE_NAMES:
            by.setdefault(key, {})[m["name"]] = m["value"]
        elif m["kind"] == "histogram" and m["name"] == "comm.message_bytes":
            hists[key] = m
    if not by:
        return []
    lines = ["## Comm bytes by backend / op / tier", "",
             "| backend | op | tier | messages | logical | wire "
             "| wire/logical | msg p50 | msg p95 |",
             "|---|---|---|---|---|---|---|---|---|"]
    for key in sorted(by):
        v = by[key]
        logical = v.get("comm.bytes_logical", 0.0)
        wire = v.get("comm.bytes_wire", 0.0)
        ratio = wire / logical if logical > 0 else 0.0
        h = hists.get(key, {})
        p50 = _hist_quantile(h.get("buckets", {}), 0.50) if h else 0.0
        p95 = _hist_quantile(h.get("buckets", {}), 0.95) if h else 0.0
        lines.append(
            f"| {key[0]} | {key[1]} | {key[2]} "
            f"| {v.get('comm.messages', 0.0):.0f} "
            f"| {_fmt_bytes(logical)} | {_fmt_bytes(wire)} "
            f"| {ratio:.4f} | {_fmt_bytes(p50)} | {_fmt_bytes(p95)} |")
    return lines + [""]


def _section_steps(rows: List[dict]) -> List[str]:
    lines = []
    for name in ("train.step_s", "posttrain.step_s", "sim.step_makespan_s"):
        series = _gauge_series(rows, name)
        if series:
            lines.append(f"- `{name}`: n={len(series)} "
                         f"p50={_pct(series, 0.50):.4g}s "
                         f"p95={_pct(series, 0.95):.4g}s")
    if not lines:
        return []
    return ["## Step times", ""] + lines + [""]


def _section_gauges(metrics: List[dict]) -> List[str]:
    rows = [m for m in metrics if m["kind"] == "gauge"]
    if not rows:
        return []
    lines = ["## Final gauges", "", "| gauge | value |", "|---|---|"]
    for m in rows:
        mid = obs_metrics.metric_id(m["name"], m.get("labels", {}))
        lines.append(f"| `{mid}` | {m['value']:.6g} |")
    return lines + [""]


def _section_schema(real_rows: List[dict],
                    sim_rows: List[dict]) -> List[str]:
    real = obs_metrics.metric_names(real_rows, kind="counter")
    sim = obs_metrics.metric_names(sim_rows, kind="counter")
    lines = ["## Counter-name schema (real vs sim)", "",
             f"- shared: {len(real & sim)}",
             f"- real-only: {len(real - sim)}",
             f"- sim-only: {len(sim - real)}"]
    for name in sorted(real - sim):
        lines.append(f"  - real-only: `{name}`")
    for name in sorted(sim - real):
        lines.append(f"  - sim-only: `{name}`")
    status = "IDENTICAL" if real == sim else "DIVERGENT"
    lines.append(f"- counter name sets: **{status}**")
    return lines + [""]


def _section_trace(title: str, trace: dict) -> List[str]:
    totals = obs_div.lane_kind_totals(trace)
    if not totals:
        return []
    makespan = trace.get("otherData", {}).get("makespan_s", 0.0)
    lines = [f"## Utilization: {title}", "",
             f"- makespan: {makespan:.6g} s", "",
             "| lane | busy s | busy frac | comm s | barrier s | push s |",
             "|---|---|---|---|---|---|"]
    busy_by_lane = {}
    for lane in sorted(totals):
        kt = totals[lane]
        busy = sum(kt.get(k, 0.0) for k in obs_div.BUSY_KINDS)
        busy_by_lane[lane] = busy
        frac = busy / makespan if makespan > 0 else 0.0
        lines.append(f"| {lane} | {busy:.6g} | {frac:.1%} "
                     f"| {kt.get('comm', 0.0):.6g} "
                     f"| {kt.get('barrier', 0.0):.6g} "
                     f"| {kt.get('push', 0.0):.6g} |")
    durs = [ev.get("dur", 0.0) / 1e6
            for ev in trace.get("traceEvents", ())
            if ev.get("ph") == "X"
            and ev.get("cat") in obs_div.BUSY_KINDS]
    if durs:
        lines += ["", f"- busy-event durations: n={len(durs)} "
                      f"p50={_pct(durs, 0.50):.4g}s "
                      f"p95={_pct(durs, 0.95):.4g}s"]
    if busy_by_lane:
        ranked = sorted(busy_by_lane.items(), key=lambda kv: -kv[1])
        lines += ["- straggler ranking (busiest first): "
                  + ", ".join(f"{ln} ({b:.4g}s)" for ln, b in ranked[:8])]
    return lines + [""]


def _render(args) -> int:
    sections: List[str] = ["# Telemetry report", ""]
    real_rows = sim_rows = None
    if args.metrics:
        meta, real_rows = obs_metrics.read_jsonl(args.metrics)
        sections += _section_meta(f"Run: {args.metrics}", meta)
        sections += _section_bytes(_final(real_rows))
        sections += _section_steps(real_rows)
        sections += _section_gauges(_final(real_rows))
    if args.sim_metrics:
        meta, sim_rows = obs_metrics.read_jsonl(args.sim_metrics)
        sections += _section_meta(f"Sim run: {args.sim_metrics}", meta)
        sections += _section_bytes(_final(sim_rows))
        sections += _section_steps(sim_rows)
    if real_rows is not None and sim_rows is not None:
        sections += _section_schema(real_rows, sim_rows)
    real_trace = sim_trace = None
    if args.trace:
        from repro.sim.trace import read_trace
        real_trace = read_trace(args.trace)
        sections += _section_trace(args.trace, real_trace)
    if args.sim_trace:
        from repro.sim.trace import read_trace
        sim_trace = read_trace(args.sim_trace)
        sections += _section_trace(args.sim_trace, sim_trace)
    if real_trace is not None and sim_trace is not None:
        report = obs_div.compare_traces(real_trace, sim_trace)
        sections += [report.render()]
    text = "\n".join(sections)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
        print(f"[report] wrote {args.output}")
    else:
        print(text)
    return 0


def _simulate(args) -> int:
    """Write the sim side of a sim-vs-real pair: same dataset stream,
    same balancing entry point, the simulator's cost hooks recording the
    same counter names the executable backends record."""
    from repro.balance import make_plan
    from repro.core import backend as backends
    from repro.data import sample_lengths
    from repro.sim import CommModel, SimConfig, Timeline, simulate_minibatch
    from repro.sim.trace import write_trace

    backend = backends.get_backend(args.comm)
    cfg = SimConfig(comm=CommModel(devices_per_node=args.devices_per_node))
    reg = obs_metrics.MetricsRegistry(meta={
        "driver": "launch.report", "comm": backend.name,
        "world": args.world, "strategy": args.strategy,
        "dataset": args.dataset, "source": "sim"})
    if args.metrics:
        reg.attach_jsonl(args.metrics)
    tl = Timeline(source="sim", meta={
        "model": "training", "scheme": backend.name, "driver":
        "launch.report", "world": args.world})
    offset = 0.0
    with obs_metrics.recording(reg):
        for t in range(args.steps):
            lens = sample_lengths(
                args.dataset, args.world * args.minibatch_per_device,
                args.seed + t).tolist()
            lens = [min(int(l), args.max_tokens) for l in lens]
            plan = make_plan(lens, args.world, args.max_tokens,
                             strategy=args.strategy, cp=args.cp)
            r = simulate_minibatch(plan, lens, scheme=backend.name,
                                   cfg=cfg, step=t)
            # per-step counter recording happened inside the cost hooks;
            # mirror launch.train's per-step driver metrics so the two
            # files' counter-name sets are IDENTICAL, then snapshot
            reg.gauge("train.loss").set(0.0)  # the sim has no loss
            reg.gauge("train.step_s").set(r.makespan)
            reg.gauge("sim.step_makespan_s").set(r.makespan)
            reg.counter("train.tokens").inc(float(sum(lens)))
            reg.counter("train.samples").inc(float(len(lens)))
            # the padding counters build_minibatch records as it packs
            reg.counter("data.tokens_real").inc(float(sum(lens)))
            reg.counter("data.token_slots").inc(float(
                max(plan.max_microbatches, 1) * plan.world_size
                * args.max_tokens * getattr(plan, "cp", 1)))
            reg.step(t)
            # splice this step's lane events into the run timeline at the
            # current offset, so the trace covers the whole run
            for lane in r.timeline.lanes:
                dst = tl.lane(lane.name)
                for ev in lane.events:
                    dst.place(offset + ev.start, ev.duration, ev.kind,
                              ev.name)
            for track, samples in r.timeline.counters.items():
                for ts, v in samples:
                    tl.count(track, offset + ts, v)
            offset += r.makespan
    if args.metrics:
        reg.close()
        print(f"[report] wrote sim metrics {args.metrics}")
    if args.trace:
        write_trace(args.trace, tl)
        print(f"[report] wrote sim trace {args.trace}")
    if not args.metrics and not args.trace:
        print("[report] --simulate: nothing to write "
              "(pass --metrics and/or --trace)")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="render telemetry artifacts, or simulate a run's "
                    "telemetry (--simulate)")
    ap.add_argument("--metrics", default="",
                    help="real run's metrics JSONL (in --simulate mode: "
                         "the sim metrics OUTPUT path)")
    ap.add_argument("--sim-metrics", default="",
                    help="sim run's metrics JSONL to compare schemas with")
    ap.add_argument("--trace", default="",
                    help="real run's Chrome trace (in --simulate mode: "
                         "the sim trace OUTPUT path)")
    ap.add_argument("--sim-trace", default="",
                    help="sim run's Chrome trace; with --trace, the "
                         "divergence report is appended")
    ap.add_argument("-o", "--output", default="",
                    help="write the report here (default: stdout)")
    ap.add_argument("--simulate", action="store_true",
                    help="run the simulator under a recording registry "
                         "and write schema-identical telemetry instead "
                         "of rendering")
    # --simulate knobs (mirroring launch.train's planning inputs)
    ap.add_argument("--comm", default="odc")
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--strategy", default="lb_mini")
    ap.add_argument("--dataset", default="longalign")
    ap.add_argument("--minibatch-per-device", type=int, default=4)
    ap.add_argument("--max-tokens", type=int, default=512)
    ap.add_argument("--devices-per-node", type=int, default=8)
    ap.add_argument("--cp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.simulate:
        return _simulate(args)
    if not (args.metrics or args.sim_metrics or args.trace
            or args.sim_trace):
        ap.error("nothing to render: pass --metrics/--trace "
                 "(or --simulate)")
    return _render(args)


if __name__ == "__main__":
    raise SystemExit(main())
