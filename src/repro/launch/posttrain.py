"""Asynchronous post-training driver: rollout→train with ODC weight push.

Routes both post-training workloads (GRPO RL and SFT) through the
``repro.posttrain`` subsystem: generator → RolloutBuffer (bounded
staleness) → LB-Mini balancer → FSDP±ODC trainer → p2p weight push.

``--staleness 0`` replays the synchronous alternating loop bit for bit
(golden-tested); ``--staleness K`` lets the generator run K waves ahead
on last-pushed weights.  ``--rollout engine`` generates rollouts with a
real prefill/decode ``GenerationEngine`` under the pushed weights
(``synthetic`` uses the paper's seeded sampler and skips generation
cost, matching its measurement convention); ``--rollout continuous``
streams the same rollouts through the in-flight batching engine with
live versioned weight pushes landing between decode steps.

Examples (CPU, reduced config):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.posttrain --task grpo --reduced \
      --iters 4 --staleness 1 --comm odc
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.posttrain --task sft --reduced \
      --iters 4 --dataset longalign --staleness 0
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.balance.cost import CostModel
from repro.configs import get_config, get_reduced
from repro.core import backend as backends
from repro.core.gspmd import GSPMDConfig, ShardingRules, make_train_step
from repro.launch.mesh import (make_hier_mesh, make_host_mesh,
                               make_pipe_mesh)
from repro.models import transformer as T
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.optim import AdamWConfig, adamw_init
from repro.posttrain import (
    ContinuousGenerationEngine, GenerationEngine, GRPOTask,
    PostTrainPipeline, SFTTask, WeightPusher,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="grpo", choices=("grpo", "sft"))
    ap.add_argument("--arch", default="qwen-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--staleness", type=int, default=0,
                    help="SSP bound K: the generator may run K waves ahead "
                         "of the trainer on last-pushed weights (0 = the "
                         "synchronous alternating loop, bit-identical)")
    ap.add_argument("--strategy", default="lb_mini",
                    choices=("local_sort", "lb_micro", "lb_mini",
                             "lb_mini_het"))
    ap.add_argument("--schedule", default="minibatch",
                    choices=backends.SCHEDULES)
    ap.add_argument("--comm", default="odc",
                    choices=backends.backend_names(include_aliases=True),
                    help="comm backend for BOTH the train step and the "
                         "trainer->generator weight push (p2p backends "
                         "push without a trainer-side barrier); 'hier' "
                         "builds a (node, device, model) mesh — see "
                         "--nodes")
    ap.add_argument("--nodes", type=int, default=2,
                    help="with --comm hier: node count of the two-tier "
                         "FSDP mesh")
    ap.add_argument("--pipe-stages", type=int, default=2,
                    help="with --comm pipe/pipe-int8: stage count of the "
                         "(pipe, data, model) mesh")
    ap.add_argument("--rollout", default="synthetic",
                    choices=("synthetic", "engine", "continuous"),
                    help="grpo only: 'engine' decodes real rollouts with "
                         "a GenerationEngine under the pushed weights; "
                         "'continuous' streams them through a "
                         "ContinuousGenerationEngine with live versioned "
                         "weight pushes between decode steps")
    ap.add_argument("--slots", type=int, default=4,
                    help="--rollout continuous: decode lanes of the "
                         "in-flight batching engine")
    ap.add_argument("--no-push", action="store_true",
                    help="skip the weight push (synthetic rollouts never "
                         "read generator params)")
    # grpo knobs
    ap.add_argument("--prompts", type=int, default=8)
    ap.add_argument("--group", type=int, default=4)
    ap.add_argument("--rollout-max-len", type=int, default=192)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--length-variance", type=float, default=1.0)
    # sft knobs
    ap.add_argument("--dataset", default="longalign",
                    choices=("longalign", "swesmith", "aime"))
    ap.add_argument("--minibatch-per-device", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=384)
    # shared
    ap.add_argument("--max-tokens", type=int, default=256,
                    help="microbatch token budget")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace JSON of the pipeline's "
                         "wall-clock events (wave generation, weight "
                         "pushes, train steps) in the simulator's timeline "
                         "schema — open in chrome://tracing / "
                         "ui.perfetto.dev next to a simulate_posttrain "
                         "trace of the same config")
    ap.add_argument("--metrics", default="",
                    help="write per-step metrics snapshots (comm counters, "
                         "staleness/buffer gauges) as JSONL; render with "
                         "`python -m repro.launch.report`")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--config", default="",
                    help="tune_result.json from `python -m "
                         "repro.launch.tune --mode posttrain`: launches "
                         "the tuner's winning config; explicit CLI flags "
                         "still override the file")
    obs_log.add_log_args(ap)
    from repro.tune.config import apply_config_arg
    tuned = apply_config_arg(ap, argv, mode="posttrain")
    args = ap.parse_args(argv)
    out = obs_log.from_args("posttrain", args)
    if tuned is not None:
        out.info(f"--config {args.config}: launching tuned winner "
                 f"{tuned['winner']} (CLI flags override)")

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    comm = backends.get_backend(args.comm)
    if comm.name == "hier":
        # two-tier FSDP, as in launch.train: params node-major over
        # (node, device) so the backend's two-stage gather applies
        mesh = make_hier_mesh(nodes=args.nodes, model=args.model_axis)
        rules = ShardingRules(data=("node", "device"))
        world = mesh.shape["node"] * mesh.shape["device"]
    elif comm.name.startswith("pipe"):
        # 1F1B stage pipeline, as in launch.train; the weight push rides
        # the same two-tier wire (int8-compressed for pipe-int8)
        mesh = make_pipe_mesh(stages=args.pipe_stages,
                              model=args.model_axis)
        rules = ShardingRules(data=("pipe", "data"))
        world = mesh.shape["pipe"] * mesh.shape["data"]
    else:
        mesh = make_host_mesh(model=args.model_axis)
        rules = ShardingRules()
        world = mesh.shape["data"]
    gcfg = GSPMDConfig(rules=rules, schedule=args.schedule,
                       comm=comm.name, block_kv=min(128, args.max_tokens),
                       pipe_stages=(args.pipe_stages
                                    if comm.name.startswith("pipe")
                                    else 0))
    out.info(f"{cfg.name} task={args.task} mesh={dict(mesh.shape)} "
             f"staleness={args.staleness} comm={comm.name} "
             f"strategy={args.strategy} rollout="
             f"{args.rollout if args.task == 'grpo' else 'loader'}")

    step = jax.jit(make_train_step(cfg, mesh, gcfg, AdamWConfig(lr=args.lr)))
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = adamw_init(params)

    # same arch-aware cost model as launch.train, so the balancer plans
    # match the synchronous driver on attention-free / windowed archs
    cm = CostModel(attention_free=cfg.is_attention_free,
                   window=cfg.sliding_window)
    rec = None
    if args.trace:
        from repro.sim.trace import TraceRecorder
        rec = TraceRecorder(meta={
            "driver": "launch.posttrain", "arch": cfg.name,
            "task": args.task, "comm": comm.name,
            "staleness": args.staleness, "world": world})

    if args.task == "grpo":
        engine = None
        if args.rollout == "engine":
            engine = GenerationEngine(cfg, mesh, gcfg)
        elif args.rollout == "continuous":
            engine = ContinuousGenerationEngine(
                cfg, mesh, gcfg, slots=args.slots,
                max_len=args.rollout_max_len, trace=rec)
        task = GRPOTask(
            vocab_size=cfg.vocab_size, prompts=args.prompts,
            group=args.group, max_len=args.rollout_max_len,
            max_tokens=args.max_tokens, strategy=args.strategy,
            seed=args.seed, length_variance=args.length_variance,
            rollout_source=args.rollout, engine=engine,
            prompt_len=args.prompt_len, cost_model=cm)
    else:
        task = SFTTask(
            vocab_size=cfg.vocab_size, world=world, dataset=args.dataset,
            minibatch_per_device=args.minibatch_per_device,
            max_tokens=args.max_tokens, max_len=args.max_len,
            strategy=args.strategy, seed=args.seed, cost_model=cm)

    # only engine-backed rollouts read the generator params; synthetic
    # GRPO and the SFT loader are version-independent, so a push every
    # step would be pure wasted gather traffic
    pusher = None
    if (not args.no_push and args.task == "grpo"
            and args.rollout in ("engine", "continuous")):
        pusher = WeightPusher(cfg, mesh, gcfg)
    live = (engine if args.task == "grpo" and args.rollout == "continuous"
            and pusher is not None else None)
    pipe = PostTrainPipeline(task=task, step_fn=step, mesh=mesh, world=world,
                             staleness=args.staleness, pusher=pusher,
                             trace=rec, live_engine=live, log=out)

    reg = None
    if args.metrics:
        reg = obs_metrics.MetricsRegistry(meta={
            "driver": "launch.posttrain", "arch": cfg.name,
            "task": args.task, "comm": comm.name,
            "staleness": args.staleness, "world": world, "source": "real"})
        reg.attach_jsonl(args.metrics)
        obs_metrics.set_active(reg)

    t0 = time.time()
    try:
        params, opt, metrics = pipe.run(args.iters, params, opt)
    finally:
        if reg is not None:
            obs_metrics.set_active(None)
            reg.close()
    dt = time.time() - t0
    if rec is not None:
        out.always(f"wrote trace {rec.write(args.trace)}")
    if reg is not None:
        out.always(f"wrote metrics {args.metrics}")
    if not metrics:
        out.always(f"done: no steps run (--iters {args.iters}); "
                   "setup OK")
        return 0
    n = sum(m["rollouts"] for m in metrics)
    out.always(f"done: {n} rollouts / {len(metrics)} steps in "
               f"{dt:.1f}s  final loss={metrics[-1]['loss']:+.5f}  "
               f"max staleness seen={pipe.buffer.max_staleness_seen}  "
               f"pushes={pusher.pushes if pusher else 0}")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
