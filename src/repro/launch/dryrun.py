import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# NOTE: the two lines above MUST run before any jax import (jax locks the
# device count on first init).  Do not move them.

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes, and emit the roofline inputs.

For each combo this produces a JSON record with:
  * memory_analysis   — per-device argument/output/temp bytes (fits check)
  * cost_analysis     — XLA's own counters (loop bodies counted once)
  * hlo               — loop-aware per-device flops / bytes / collective
                        bytes by type (repro.launch.hlo)
  * roofline          — the three terms in seconds + dominant + MODEL_FLOPS

Usage:
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all --out-dir results/dryrun
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.core.gspmd import (
    GSPMDConfig, ShardingRules, build_serve_artifacts, build_train_artifacts,
)
from repro.launch import hlo as hlo_mod
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, shape_applicable, train_batch_shapes


def model_flops_estimate(cfg, shape) -> float:
    """6·N_active·D for train (fwd+bwd), 2·N_active·D for inference."""
    n = cfg.num_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per request
    return 2.0 * n * shape.global_batch


def run_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
              schedule: str = "layer", comm: str = "collective",
              hybrid_pod: bool = False, moe_ep: str = "none",
              num_microbatches: int = 0, block_kv: int = 0,
              remat: bool = True, param_dtype: str = "float32",
              save_hlo: str = ""):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "no sub-quadratic story (see DESIGN.md)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    if multi_pod and not hybrid_pod:
        # paper-faithful flat FSDP: parameters sharded across all 512 chips
        rules = ShardingRules(data=("pod", "data"), model="model", pod=None)
    elif multi_pod:
        # ZeRO++-style hybrid (paper §6.1): gather/scatter stays intra-pod
        rules = ShardingRules(data="data", model="model", pod="pod")
    else:
        rules = ShardingRules(data="data", model="model", pod=None)
    gcfg = GSPMDConfig(
        rules=rules, schedule=schedule, comm=comm, hybrid_pod=hybrid_pod,
        moe_ep=moe_ep, remat=remat,
        # train default 2048 per the §Perf hillclimb (scan-carry traffic);
        # serve default 4096 (decode reads the whole cache)
        block_kv=block_kv or (2048 if shape.kind == "train" else 4096),
        param_dtype=jnp.dtype(param_dtype),
    )
    chips = mesh.size

    t0 = time.time()
    if shape.kind == "train":
        dp = 1
        for a in rules.dp_axes:
            dp *= mesh.shape[a]
        batch_shapes = train_batch_shapes(
            cfg, shape, num_microbatches=num_microbatches, dp_size=dp)
        jitted, args = build_train_artifacts(cfg, mesh, gcfg, batch_shapes)
        lowered = jitted.lower(*args)
    else:
        jitted, args = build_serve_artifacts(
            cfg, mesh, gcfg, kind=shape.kind, batch=shape.global_batch,
            seq_len=shape.seq_len)
        lowered = jitted.lower(*args)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_rec = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "total_bytes": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
    }
    try:
        ca = dict(compiled.cost_analysis())
        ca = {k: float(v) for k, v in ca.items()
              if isinstance(v, (int, float))}
    except Exception:  # pragma: no cover
        ca = {}

    text = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(text)
    devices_per_pod = (chips // mesh.shape["pod"]) if multi_pod else 0
    cost = hlo_mod.analyze_hlo_text(text, devices_per_pod=devices_per_pod)
    # the host devices stand in for the production chips: price them as such
    kind = hlo_mod.TARGET_DEVICE_KIND
    roof = hlo_mod.roofline_terms(
        cost, chips=chips, device_kind=kind,
        model_flops=model_flops_estimate(cfg, shape))
    if multi_pod:
        # DCN term: cross-pod bytes at data-center-network bandwidth
        roof["inter_pod_bytes_per_device"] = cost.inter_pod_bytes
        roof["dcn_s"] = (cost.inter_pod_bytes
                         / hlo_mod.peak_rates(kind)["dcn_bw"])

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": dict(mesh.shape),
        "schedule": schedule,
        "comm": comm,
        "hybrid_pod": hybrid_pod,
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_rec,
        "xla_cost_analysis": {k: ca[k] for k in ("flops", "bytes accessed")
                              if k in ca},
        "hlo": cost.as_dict(),
        "roofline": roof,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="", choices=[""] + list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--schedule", default="layer",
                    choices=("layer", "minibatch"))
    ap.add_argument("--comm", default="collective",
                    choices=("collective", "odc"),
                    help="comm-backend registry name (how gathers/scatters "
                         "move bytes); the production dry-run meshes are "
                         "single-tier, so 'hier' is not offered here")
    ap.add_argument("--moe-ep", default="none", choices=("none", "data"))
    ap.add_argument("--hybrid-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--block-kv", type=int, default=0)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) for this mesh")
    ap.add_argument("--out", default="", help="JSON output path")
    ap.add_argument("--save-hlo", default="", help="dump scheduled HLO here")
    args = ap.parse_args(argv)

    combos = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                combos.append((arch, shape))
    else:
        combos = [(args.arch, args.shape)]

    records = []
    for arch, shape in combos:
        try:
            rec = run_combo(
                arch, shape, multi_pod=args.multi_pod,
                schedule=args.schedule, comm=args.comm,
                hybrid_pod=args.hybrid_pod, moe_ep=args.moe_ep,
                num_microbatches=args.microbatches, block_kv=args.block_kv,
                remat=not args.no_remat, param_dtype=args.param_dtype,
                save_hlo=args.save_hlo)
        except Exception as e:  # noqa: BLE001
            rec = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        records.append(rec)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" dom={r['dominant']} compute={r['compute_s']:.4f}s"
                     f" mem={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s"
                     f" compile={rec['compile_s']}s")
        elif status == "error":
            extra = " " + rec["error"][:200]
        print(f"[dryrun] {arch} x {shape}: {status}{extra}", flush=True)

    out = records[0] if len(records) == 1 else records
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    else:
        json.dump(out, sys.stdout, indent=2)
        print()
    bad = [r for r in records if r["status"] == "error"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
