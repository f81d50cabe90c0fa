"""End-to-end SFT training driver (runs on whatever devices exist).

Wires together the whole stack: synthetic length-realistic data →
load-balancing strategy (LocalSort / LB-Micro / LB-Mini) → sequence
packing → the FSDP±ODC GSPMD engine → sharded AdamW → checkpointing.

LB-Mini produces *different microbatch counts per device*; the SPMD
program pads every device to the max count with empty (fully-masked)
microbatches — under the ODC schedule the loop body has no collectives,
so on real hardware the pad cost collapses to the minibatch barrier
(paper Fig. 2); the timing consequences are modeled in ``repro.sim``.

The parameters and AdamW state are created inside jit straight into their
FSDP shardings and the step donates them, so the state is never held
twice and never lands unsharded on one device.

Example (CPU, reduced config):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  PYTHONPATH=src python -m repro.launch.train --arch qwen-1.5b --reduced \
      --steps 20 --strategy lb_mini --schedule minibatch --comm odc

Example (one TPU chip, published widths, depth cut to 8 layers):
  PYTHONPATH=src python -m repro.launch.train --arch qwen-1.5b --layers 8 \
      --steps 3
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.balance.cost import CostModel
from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import get_config, get_reduced
from repro.core import backend as backends
from repro.core.gspmd import (
    GSPMDConfig,
    ShardingRules,
    init_train_state,
    jit_train_step,
    train_batch_shardings,
)
from repro.data.loader import SyntheticSFTLoader
from repro.data.packing import build_minibatch  # noqa: F401 (re-export:
#   the plan->batch assembly now lives in repro.data.packing, shared with
#   the posttrain pipeline and the GRPO example)
from repro.launch.mesh import (
    make_cp_mesh,
    make_hier_mesh,
    make_host_mesh,
    make_pipe_mesh,
)
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.optim import AdamWConfig
from repro.sim.trace import TraceRecorder, maybe_span


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen-1.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the same family")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the config's depth to N layers; every width "
                         "stays as published (0 = the config's own depth)")
    ap.add_argument("--dataset", default="longalign",
                    choices=("longalign", "swesmith", "aime"))
    ap.add_argument("--strategy", default="lb_mini",
                    choices=("local_sort", "lb_micro", "lb_mini",
                             "lb_mini_het", "lb_token"))
    ap.add_argument("--schedule", default="minibatch",
                    choices=backends.SCHEDULES,
                    help="where gathers/scatters are PLACED: 'layer' (per "
                         "layer per microbatch, FSDP baseline), 'minibatch' "
                         "(once per minibatch, ODC), 'overlap' (ODC with "
                         "double-buffered parameter prefetch: gather layer "
                         "l+1 under layer l's compute; scatter l under "
                         "l-1's backward)")
    ap.add_argument("--comm", default="odc",
                    choices=backends.backend_names(include_aliases=True),
                    help="how each gather/scatter MOVES bytes — a comm-"
                         "backend registry name: 'collective' (fused "
                         "AG/RS), 'odc' (p2p ring), 'odc-overlap' (odc + "
                         "implied overlap schedule), 'hier' (intra-node "
                         "collective + inter-node ring over a node×device "
                         "mesh, see --nodes); 'pipe'/'pipe-int8' (1F1B "
                         "stage pipeline over a pipe×data mesh, see "
                         "--pipe-stages; -int8 compresses stage-boundary "
                         "traffic to chunked int8); 'cp'/'cp-ring' (ring "
                         "attention over a data×cp mesh, see --cp); legacy "
                         "aliases (e.g. the sim's 'overlap') resolve to the "
                         "same backends")
    ap.add_argument("--nodes", type=int, default=2,
                    help="with --comm hier: node count of the (node, "
                         "device, model) mesh (devices per node = "
                         "device_count / nodes / model)")
    ap.add_argument("--pipe-stages", type=int, default=2,
                    help="with --comm pipe/pipe-int8: stage count of the "
                         "(pipe, data, model) mesh (devices per stage = "
                         "device_count / stages / model)")
    ap.add_argument("--pipe-interleave", action="store_true",
                    help="with --comm pipe/pipe-int8: interleaved 1F1B "
                         "(halved warmup depth)")
    ap.add_argument("--cp", type=int, default=2,
                    help="with --comm cp/cp-ring: context-parallel degree "
                         "of the (data, cp, model) mesh — each ring group "
                         "of cp adjacent devices sequence-shards its "
                         "microbatches (ring attention); pair with "
                         "--strategy lb_token so over-long sequences are "
                         "token-split across the ring")
    ap.add_argument("--device-profile", default="none",
                    choices=("none", "homogeneous", "one_slow", "bimodal",
                             "uniform"),
                    help="simulated heterogeneity: balances plans for the "
                         "profile (strategy lb_mini_het) and routes the ODC "
                         "p2p ring through the profile's device order")
    ap.add_argument("--slow-factor", type=float, default=2.0,
                    help="straggler severity: affected devices run at "
                         "1/slow-factor nominal speed")
    ap.add_argument("--profile-jitter", type=float, default=0.0,
                    help="sigma of the per-step lognormal slowdown noise")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--minibatch-per-device", type=int, default=4)
    ap.add_argument("--max-tokens", type=int, default=512,
                    help="microbatch token budget (memory model)")
    ap.add_argument("--max-len", type=int, default=384,
                    help="rescale the length distribution to this max")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup-steps", type=int, default=0)
    ap.add_argument("--cosine", action="store_true",
                    help="cosine decay to 10%% over --steps (with warmup)")
    ap.add_argument("--data-axis", type=int, default=0,
                    help="0 = all devices on data axis")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-every", "--ckpt-every", type=int, default=0,
                    dest="save_every",
                    help="checkpoint (params + optimizer) to --ckpt-dir "
                         "every N steps (legacy alias: --ckpt-every)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(bit-identical to an uninterrupted run: the "
                         "loader replays the skipped steps' data stream)")
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace JSON of the run's wall-clock "
                         "step timing (same schema as the simulator's "
                         "timeline traces — open in chrome://tracing or "
                         "ui.perfetto.dev, or render next to a simulated "
                         "run of the same config)")
    ap.add_argument("--metrics", default="",
                    help="write per-step metrics snapshots (counters, "
                         "gauges, message-size histograms) as JSONL — the "
                         "same counter names a simulated run of this "
                         "config emits; render with "
                         "`python -m repro.launch.report`")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--config", default="",
                    help="tune_result.json from `python -m "
                         "repro.launch.tune`: launches the tuner's winning "
                         "config (comm/strategy/mesh/minibatch knobs); "
                         "explicit CLI flags still override the file")
    obs_log.add_log_args(ap)
    from repro.tune.config import apply_config_arg
    tuned = apply_config_arg(ap, argv, mode="train")
    args = ap.parse_args(argv)
    out = obs_log.from_args("train", args)
    if tuned is not None:
        out.info(f"--config {args.config}: launching tuned winner "
                 f"{tuned['winner']} (CLI flags override)")

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.layers:
        full_depth = cfg.num_layers
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
        out.info(f"depth cut: {args.layers} of {full_depth} layers, widths "
                 "unchanged")
    comm = backends.get_backend(args.comm)  # resolve aliases up front
    if comm.name == "hier":
        # two-tier FSDP: params sharded node-major over (node, device)
        mesh = make_hier_mesh(nodes=args.nodes, model=args.model_axis)
        rules = ShardingRules(data=("node", "device"))
        world = mesh.shape["node"] * mesh.shape["device"]
    elif comm.name.startswith("pipe"):
        # 1F1B stage pipeline: params sharded stage-major over (pipe, data)
        mesh = make_pipe_mesh(stages=args.pipe_stages, model=args.model_axis)
        rules = ShardingRules(data=("pipe", "data"))
        world = mesh.shape["pipe"] * mesh.shape["data"]
    elif comm.name == "cp":
        # context-parallel ring groups: params stay ZeRO-sharded over the
        # flat (data, cp) axes (byte-identical to flat ODC); the batch
        # sequence dim is sharded over cp (ring attention inside groups)
        mesh = make_cp_mesh(cp=args.cp, model=args.model_axis)
        rules = ShardingRules(data=("data", "cp"))
        world = mesh.shape["data"] * mesh.shape["cp"]
    else:
        mesh = make_host_mesh(data=args.data_axis, model=args.model_axis)
        rules = ShardingRules()
        world = mesh.shape["data"]
    dev = jax.devices()[0]
    out.info(f"{cfg.name} ({cfg.family}) on {dev.platform} "
             f"{dev.device_kind} x{jax.device_count()}, mesh "
             f"{dict(mesh.shape)} strategy={args.strategy} "
             f"schedule={args.schedule} comm={comm.name}")

    profile = None
    if args.device_profile != "none":
        from repro.balance import make_straggler_profile
        profile = make_straggler_profile(
            args.device_profile, world, slow_factor=args.slow_factor,
            seed=args.seed, jitter=args.profile_jitter)
        out.info(f"device profile {args.device_profile}: speeds="
                 f"{[round(s, 3) for s in profile.speeds]}")

    gcfg = GSPMDConfig(
        rules=rules, schedule=args.schedule, comm=comm.name,
        block_kv=min(512, args.max_tokens), device_profile=profile,
        pipe_stages=(args.pipe_stages
                     if comm.name.startswith("pipe") else 0),
        pipe_interleave=args.pipe_interleave,
    )
    lr_schedule = None
    if args.cosine or args.warmup_steps:
        from repro.optim import cosine_schedule
        lr_schedule = (lambda s: cosine_schedule(
            s, args.steps, args.warmup_steps)) if args.cosine else \
            (lambda s: jnp.minimum(1.0, (s + 1) / max(1, args.warmup_steps)))
    step_fn = jit_train_step(cfg, mesh, gcfg, AdamWConfig(lr=args.lr),
                             lr_schedule=lr_schedule)
    params, opt_state = init_train_state(cfg, mesh, gcfg,
                                         jax.random.PRNGKey(args.seed))

    start_step = 0
    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume needs --ckpt-dir")
        last = latest_step(args.ckpt_dir)
        if last is not None:
            fresh = {"params": params, "opt": opt_state}
            state = load_checkpoint(
                args.ckpt_dir, last, fresh,
                shardings=jax.tree.map(lambda x: x.sharding, fresh))
            params, opt_state = state["params"], state["opt"]
            start_step = last
            out.info(f"resumed from {args.ckpt_dir} at step {last}")
        else:
            out.info(f"--resume: no checkpoint in {args.ckpt_dir!r}, "
                     "starting fresh")

    cm = CostModel(attention_free=cfg.is_attention_free,
                   window=cfg.sliding_window)
    loader = SyntheticSFTLoader(
        args.dataset, vocab_size=cfg.vocab_size, world_size=world,
        minibatch_per_device=args.minibatch_per_device,
        max_tokens=args.max_tokens, strategy=args.strategy,
        max_len=args.max_len, cost_model=cm, seed=args.seed,
        device_profile=profile,
        cp=args.cp if comm.name == "cp" else 1)

    def extras_for(step):
        """Per-step-seeded modality stubs: a resumed run regenerates the
        exact embeddings an uninterrupted run would have drawn."""
        if cfg.family == "audio":
            rng = np.random.RandomState(step)
            return {"encoder_embeds": lambda M, W: rng.randn(
                M, W, 16, cfg.d_model).astype(np.float32)}
        if cfg.frontend == "vision" and cfg.frontend_tokens:
            rng = np.random.RandomState(step)
            return {"vision_embeds": lambda M, W: rng.randn(
                M, W, cfg.frontend_tokens, cfg.d_model).astype(np.float32)}
        return None

    rec = None
    if args.trace:
        rec = TraceRecorder(meta={
            "driver": "launch.train", "arch": cfg.name,
            "strategy": args.strategy, "schedule": args.schedule,
            "comm": comm.name, "world": world})

    reg = None
    if args.metrics:
        reg = obs_metrics.MetricsRegistry(meta={
            "driver": "launch.train", "arch": cfg.name,
            "strategy": args.strategy, "schedule": args.schedule,
            "comm": comm.name, "world": world, "source": "real"})
        reg.attach_jsonl(args.metrics)
        obs_metrics.set_active(reg)

    t_start = time.time()
    samples_done = 0
    loss = None  # no steps run yet (--steps 0 exits with a clean summary)
    try:
        for i, step_data in enumerate(
                loader.steps(args.steps, skip=start_step),
                start=start_step):
            # a jax.profiler trace of the run carries each step's id
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                with maybe_span(rec, "host", "compute",
                                f"build minibatch {i}"):
                    batch = build_minibatch(step_data["plan"],
                                            step_data["sample_tokens"],
                                            args.max_tokens,
                                            extras=extras_for(i))
                    batch = jax.device_put(
                        batch, train_batch_shardings(batch, mesh, gcfg))
                t0 = time.time()
                with maybe_span(rec, "trainer", "compute",
                                f"train step {i}"):
                    # program scope: a retrace (new batch shapes) REPLACES
                    # the step program's per-step comm ledger instead of
                    # stacking on the stale one
                    with obs_metrics.program("train_step"):
                        with mesh:
                            params, opt_state, metrics = step_fn(
                                params, opt_state, batch)
                    # blocks on the device result
                    loss = float(metrics["loss"])
            dt_step = time.time() - t0
            samples_done += len(step_data["lengths"])
            if reg is not None:
                reg.gauge("train.loss").set(loss)
                reg.gauge("train.step_s").set(dt_step)
                reg.counter("train.tokens").inc(float(metrics["tokens"]))
                reg.counter("train.samples").inc(
                    float(len(step_data["lengths"])))
                if "moe_pairs_held" in metrics:
                    # (token, expert) pairs the held experts computed,
                    # padding slots included, beside data.token_slots
                    reg.counter("moe.pairs_held").inc(
                        float(metrics["moe_pairs_held"]))
                reg.step(i)
                if rec is not None:
                    rec.count("comm wire bytes",
                              reg.total("comm.bytes_wire"))
            out.step(i, f"step {i:4d} loss={loss:.4f} "
                        f"tokens={float(metrics['tokens']):.0f} "
                        f"M={step_data['plan'].max_microbatches} "
                        f"dt={dt_step:.2f}s")
            if args.ckpt_dir and args.save_every \
                    and (i + 1) % args.save_every == 0:
                with maybe_span(rec, "host", "push",
                                f"checkpoint step {i + 1}"):
                    save_checkpoint(args.ckpt_dir, i + 1,
                                    {"params": params, "opt": opt_state})
    finally:
        if reg is not None:
            obs_metrics.set_active(None)
            reg.close()
    dt = time.time() - t_start
    if rec is not None:
        out.always(f"wrote trace {rec.write(args.trace)}")
    if reg is not None:
        out.always(f"wrote metrics {args.metrics}")
    if loss is None:
        out.always("done: no training steps run (--steps "
                   f"{args.steps}); setup OK")
        return 0
    out.always(f"done: {samples_done} samples in {dt:.1f}s "
               f"({samples_done / dt:.2f} samples/s) final loss={loss:.4f}")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
