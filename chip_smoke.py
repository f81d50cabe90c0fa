#!/usr/bin/env python3
"""On-chip smoke test of the SFT training path, at the published widths of
``qwen-1.5b`` with random weights made from a seed.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the sharded path on four chips

Everything runs in this one process, through the user entry point
``repro.launch.train.main``.

One chip: 8 of the 28 layers, FSDP with the ODC p2p ring and the
``minibatch`` schedule, LB-Mini balancing on LongAlign-shaped data,
3 steps.  Every loss must be finite and the step-0 loss within 0.5 of
ln(vocab) = 11.93, the cross-entropy of a near-uniform prediction.

Four chips: 12 layers (at 16 the ``minibatch`` schedule leaves 0.5 GB of
HBM to spare and at 28 it does not fit), run three times on the same seed
and data: ``collective``/``layer`` (the FSDP baseline), ``odc``/``minibatch``
and ``odc-overlap``.  The three runs must agree at every step within
``STEP0_TOL`` and ``LATER_TOL``.

Prints the losses, compile time, step times (not a benchmark) and every
device's ``peak_bytes_in_use``; the last line of stdout is one JSON object
``{"ok": true, "device": {...}}``.  Exits non-zero, with no such line,
where JAX finds no TPU or where the repo's ``src/`` is not beside this
file.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out"

ARCH = ["--arch", "qwen-1.5b"]
COMMON = ["--strategy", "lb_mini", "--dataset", "longalign", "--steps", "3",
          "--seed", "0"]
ONE_CHIP = ["--layers", "8", "--comm", "odc", "--schedule", "minibatch"]
FOUR_CHIP_LAYERS = ["--layers", "12"]
FOUR_CHIP_RUNS = (("collective", "layer"), ("odc", "minibatch"),
                  ("odc-overlap", "minibatch"))

# Losses of the three four-chip runs must agree within these (absolute, on
# a token-mean loss near 12).  Step 0 is a forward pass over bit-identical
# gathered weights (gathers only copy), so it is the tightest; what can
# differ is the program: f32 matmuls run as bf16 passes at the TPU's
# default precision, and how XLA fuses each schedule decides which f32
# intermediates are rounded to bf16 — about 1e-5 of the loss (1.7e-4
# between `minibatch` and `layer` on a v5e).  Later steps follow gradient
# reduce-scatters that sum the devices' contributions in different
# orders; AdamW's first steps move each weight by about lr * sign(g), so a
# gradient entry near zero whose sign flips moves its weight by 2 * lr.
STEP0_TOL = 1e-3
LATER_TOL = 2e-2
# ln(151936): the loss of a uniform prediction over qwen-1.5b's vocabulary
UNIFORM_LOSS = math.log(151_936)
UNIFORM_TOL = 0.5


def fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def train_run(tag: str, argv) -> dict:
    """One ``launch.train`` run; its per-step losses and step times come
    back through the run's own ``--metrics`` JSONL."""
    from repro.launch import train

    OUT_DIR.mkdir(exist_ok=True)
    path = OUT_DIR / f"chip_smoke_{tag}.jsonl"
    rc = train.main(list(argv) + ["--metrics", str(path)])
    if rc != 0:
        raise RuntimeError(f"{tag}: launch.train exited {rc}")
    losses, step_s = [], []
    for line in path.read_text().splitlines():
        rec = json.loads(line)
        gauges = {m["name"]: m["value"] for m in rec.get("metrics", ())
                  if m["kind"] == "gauge"}
        if "train.loss" in gauges:
            losses.append(gauges["train.loss"])
            step_s.append(gauges["train.step_s"])
    return {"losses": losses, "step_s": step_s}


def check_losses(tag: str, losses, steps: int):
    if len(losses) != steps:
        raise RuntimeError(f"{tag}: {len(losses)} losses, expected {steps}")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{tag}: non-finite loss in {losses}")
    if abs(losses[0] - UNIFORM_LOSS) > UNIFORM_TOL:
        raise RuntimeError(
            f"{tag}: step-0 loss {losses[0]} is not within {UNIFORM_TOL} "
            f"of ln(151936) = {UNIFORM_LOSS:.4f}")


def report(tag: str, run: dict):
    print(f"[chip_smoke] {tag} losses: "
          f"{', '.join(repr(x) for x in run['losses'])}")
    print(f"[chip_smoke] {tag} step seconds (step 0 includes compiling; "
          f"not a benchmark): "
          f"{', '.join(f'{x:.3f}' for x in run['step_s'])}")


def smoke(chips: int):
    """Run the phase for ``chips``; raises on any failure."""
    if chips == 1:
        run = train_run("1chip", ARCH + ONE_CHIP + COMMON)
        report("odc/minibatch", run)
        check_losses("odc/minibatch", run["losses"], 3)
        return
    runs = {}
    for comm, schedule in FOUR_CHIP_RUNS:
        tag = f"{comm}/{schedule}"
        runs[tag] = run = train_run(
            f"4chip_{comm}", ARCH + FOUR_CHIP_LAYERS + COMMON
            + ["--comm", comm, "--schedule", schedule])
        report(tag, run)
        check_losses(tag, run["losses"], 3)
    (base_tag, base), *others = runs.items()
    for tag, run in others:
        for step, (a, b) in enumerate(zip(base["losses"], run["losses"])):
            tol = STEP0_TOL if step == 0 else LATER_TOL
            print(f"[chip_smoke] step {step}: |{tag} - {base_tag}| = "
                  f"{abs(a - b)!r} (tolerance {tol})")
            if abs(a - b) > tol:
                raise RuntimeError(
                    f"step {step}: {tag} loss {b!r} differs from "
                    f"{base_tag} loss {a!r} by more than {tol}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip training smoke; 4: the three "
                         "sharded comm paths compared on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU found: JAX reports platform "
                    f"{devices[0].platform!r}")
    if len(devices) != args.chips:
        return fail(f"--chips {args.chips} but JAX sees {len(devices)} "
                    "devices")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the repo's src/repro is not beside {Path(__file__).name}")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.launch.cache import enable_compile_cache

    print(f"[chip_smoke] compile cache: {enable_compile_cache()}")
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    try:
        smoke(args.chips)
    except Exception as e:  # any failed phase fails the smoke
        return fail(f"{type(e).__name__}: {e}")

    print(f"[chip_smoke] backend compile: {sum(compile_s):.1f} s over "
          f"{len(compile_s)} programs")
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"[chip_smoke] device {d.id} peak_bytes_in_use: "
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
