#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<workload>.json`` are set
from, on the chip at the cell's own size, in one process:

- the program: its first three steps through the timed path, against the
  reference, on every seed given (the lower readings);
- the control: the reference computed in bfloat16 at the default matmul
  precision, put in the program's place, on the first ``--control``
  seeds (upper readings);
- faults, planted in the reference put in the program's place, on the
  same seeds: half of each step's samples left out, the mean over the
  rest; and, on several chips, the exchange between chips left out (each
  step's gradient from the first device's samples alone, divided by the
  whole step's tokens).  A step that returns its state unchanged reads 1
  on ``change_gap`` by definition and needs no run.

    python3 bench/calibrate.py --workload <name> --seeds 1-12 --control 3 \
        [--out readings.json]

Prints one JSON line per reading and writes them all to ``--out``
(default ``calibrate_<workload>.json``).  No window is measured.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seed_list(text: str):
    a, _, b = text.partition("-")
    return list(range(int(a), int(b) + 1)) if b else [int(x) for x in
                                                      text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp

    from bench.harness import cell as C, check, traffic

    C.use_compile_cache()
    cell = C.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    prog = C.Program(cell, devices)
    seeds = seed_list(args.seeds)
    cycles = {s: traffic.steps(cell.mix, prog.world, s, prog.cfg.vocab_size)
              for s in seeds}
    for m in sorted({prog.microbatches(st) for s in seeds
                     for st in cycles[s][:C.FIRST_STEPS]}):
        prog.compile(m)
    reference = C.reference(cell, devices)
    control = C.reference(cell, devices, dtype=jnp.bfloat16, precision=None)
    out = []

    def record(kind, seed, run, theirs, t0):
        numbers = check.gaps(run, theirs)
        row = {"kind": kind, "seed": seed,
               "seconds": time.perf_counter() - t0,
               **{k: numbers[k] for k in check.NUMBERS},
               "grad_leaf": numbers["grad_leaf"],
               "change_leaf": numbers["change_leaf"],
               "loss": run["loss"], "reference_loss": theirs["loss"]}
        out.append(row)
        print(json.dumps(row), flush=True)

    for i, seed in enumerate(seeds):
        steps = [st.samples for st in cycles[seed][:C.FIRST_STEPS]]
        t0 = time.perf_counter()
        mine, _ = C.first_steps(prog, cycles[seed], seed)
        prog.free()
        theirs = reference.run(seed, steps)
        record("program", seed, mine, theirs, t0)
        if i >= args.control:
            continue
        t0 = time.perf_counter()
        record("control_bf16", seed, control.run(seed, steps), theirs, t0)
        t0 = time.perf_counter()
        half = [s[::2] for s in steps]
        record("fault_half_batch", seed, reference.run(seed, half), theirs,
               t0)
        if prog.world > 1:
            t0 = time.perf_counter()
            own, toks = [], []
            for st in cycles[seed][:C.FIRST_STEPS]:
                plan = prog.plan(st)
                own.append([st.samples[j] for mb in plan.assignments[0]
                            for j in mb])
                toks.append(sum(len(t) - 1 for t in st.samples))
            record("fault_no_exchange", seed,
                   reference.run(seed, own, tokens=toks), theirs, t0)
    Path(args.out or f"calibrate_{args.workload}.json").write_text(
        json.dumps(out, indent=1))
    for kind in sorted({r["kind"] for r in out}):
        rows = [r for r in out if r["kind"] == kind]
        print(kind, {k: (min(r[k] for r in rows), max(r[k] for r in rows))
                     for k in check.NUMBERS}, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
