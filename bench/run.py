#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1> \
        [--keep <dir>]

Set-up (weights from the seed, the program's step compiled for every
microbatch count the cell's traffic reaches, the first three steps) is
timed as ``setup_s``; then the window trains for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and reduced by the step program's own names
(``bench/harness/scopes.py``); ``--keep`` then also writes the window's
first three steps as a small trace for ``bench/tests``.  After the window the plain reference retrains the first three
steps and ``correct`` says whether the program agreed with it within the
cell's limits (``bench/harness/check.py``).

The last line of stdout is the result as one JSON object; the numbers
compared, each beside its limit, are also the last lines of stderr.
Exits 1, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for, or where the program's ``src/`` is not in the checkout.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default="")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program (src/repro) is not in {ROOT}")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    import jax

    from bench.harness import cell as C

    C.use_compile_cache()

    spec = C.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU found: JAX reports {devices[0].platform!r}")
    if len(devices) < spec.chips:
        return fail(f"{args.workload} needs {spec.chips} chips; JAX sees "
                    f"{len(devices)}")
    if spec.limits is None:
        return fail(f"no limits file for {args.workload}")
    out = C.run(spec, args.seed, args.seconds, bool(args.trace),
                devices[:spec.chips], STARTED,
                log=lambda s: print(s, file=sys.stderr, flush=True),
                keep=Path(args.keep) if args.keep else None)
    for k, c in out["compared"].items():
        print(f"{k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
