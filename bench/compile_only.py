#!/usr/bin/env python3
"""Compile each cell's step, and its reference, for a described TPU v5e
topology at the cell's real sizes, and print ``memory_analysis()`` per
device.  Nothing runs and no chip is needed: this rehearses whether a
cell fits before a chip run, and what ``hbm_peak_gb`` will read.

    JAX_PLATFORMS=cpu python3 bench/compile_only.py [--microbatch-tokens N] [workload ...]

Without workloads, every cell of BENCHMARK.json.  ``--microbatch-tokens``
compiles at another microbatch budget than the traffic states.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def gb(x):
    return f"{x / 1e9:.3f}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--microbatch-tokens", type=int, default=0)
    ap.add_argument("--skip-reference", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    import jax
    from jax.experimental import topologies

    from bench.harness import cell as C, traffic

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workloads or [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for name in names:
        cell = C.load_cell(name)
        if args.microbatch_tokens:
            cell.mix["microbatch_tokens"] = args.microbatch_tokens
        devices = topo.devices[:cell.chips]
        prog = C.Program(cell, devices)
        cycle = traffic.steps(cell.mix, prog.world, 0, prog.cfg.vocab_size)
        for m in sorted({prog.microbatches(s) for s in cycle}):
            prog.compile(m)
            a = prog.compiled[m].memory_analysis()
            total = (a.argument_size_in_bytes + a.temp_size_in_bytes
                     + a.output_size_in_bytes - a.alias_size_in_bytes)
            print(f"{name} S={prog.S} M={m}: args {gb(a.argument_size_in_bytes)}"
                  f" temp {gb(a.temp_size_in_bytes)} out "
                  f"{gb(a.output_size_in_bytes)} alias "
                  f"{gb(a.alias_size_in_bytes)} total {gb(total)} GB; "
                  f"wire {gb(prog.wire_bytes[m])} GB/step", flush=True)
        if args.skip_reference:
            continue
        r = C.reference(cell, devices)
        p = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), jax.eval_shape(
            lambda: r.init(0)), r.p_sh)
        row = jax.ShapeDtypeStruct((r.n, prog.S), jax.numpy.int32)
        mask = jax.ShapeDtypeStruct((r.n, prog.S), jax.numpy.float32)
        with jax.default_matmul_precision("highest"):
            a = r._grad.lower(p, p, row, row, mask).compile().memory_analysis()
        print(f"{name} reference gradient block: args "
              f"{gb(a.argument_size_in_bytes)} temp {gb(a.temp_size_in_bytes)}"
              f" GB", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
