"""Serving driver: wave-at-a-time or continuous (in-flight) batching.

A thin CLI over ``repro.posttrain``'s engines — the same prefill/decode
path (GSPMD sharding rules shared with training, KV cache over
batch/model) that the asynchronous post-training pipeline's rollout
workers use.

Default mode prefills one fixed request batch and decodes it in lockstep
(``GenerationEngine``).  ``--continuous`` routes the same requests
through the ``ContinuousGenerationEngine`` instead: a request queue
feeds ``--slots`` decode lanes through the block allocator, short
requests retire early (``--length-spread`` carves per-request lengths),
and freed slots admit queued requests mid-decode.  ``--trace`` writes
the engine's per-slot scheduled timeline (decode events per slot, push
lane) as a Chrome trace — the artifact the CI serve job uploads.

Examples (CPU, reduced config):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --reduced \
      --batch 8 --prompt-len 64 --gen 32
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen-1.5b --reduced \
      --continuous --slots 4 --requests 12 --length-spread 4 \
      --trace serve_trace.json
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.core.gspmd import GSPMDConfig, ShardingRules
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.posttrain.engine import ContinuousGenerationEngine, GenerationEngine


def _request_lengths(n: int, gen: int, spread: float, seed: int):
    """Per-request generated-token counts in [gen/spread, gen], seeded —
    the mixed-length stream continuous batching exists for."""
    rng = np.random.RandomState(seed)
    lo = max(1, int(round(gen / max(spread, 1.0))))
    return rng.randint(lo, gen + 1, size=n)


def _serve_continuous(cfg, mesh, gcfg, params, args, key, out, reg):
    S, G = args.prompt_len, args.gen
    rec = None
    if args.trace:
        from repro.sim.trace import TraceRecorder
        rec = TraceRecorder(meta={"driver": "launch.serve", "arch": cfg.name,
                                  "mode": "continuous", "slots": args.slots,
                                  "clock": "scheduled"})
    engine = ContinuousGenerationEngine(
        cfg, mesh, gcfg, slots=args.slots, max_len=S + G,
        block_size=args.block_size, trace=rec)
    engine.publish(params, 0)
    lens = _request_lengths(args.requests, G, args.length_spread, args.seed)
    tokens = jax.random.randint(key, (args.requests, S), 1, cfg.vocab_size)
    for b in range(args.requests):
        engine.submit(np.asarray(tokens[b]), int(lens[b]))
    done = engine.run()
    total = int(sum(len(c.generated) for c in done))
    out.info(f"continuous: {len(done)} requests "
             f"({total} generated tokens) over {args.slots} slots in "
             f"{engine.steps} decode steps")
    out.info(f"kv blocks: {engine.allocator.num_blocks} x "
             f"{engine.allocator.block_size} positions, all freed: "
             f"{engine.allocator.free_blocks == engine.allocator.num_blocks}")
    by_rid = {c.rid: c for c in done}
    first = by_rid.get(0)
    if first is not None:  # --requests 0: nothing was admitted or decoded
        out.info(f"req 0: {len(first.generated)} tokens "
                 f"(weights v{first.weight_version}, {first.finish_reason}) "
                 f"ids: {first.generated[:16].tolist()}")
    if reg is not None:
        reg.gauge("serve.requests_done").set(float(len(done)))
        reg.gauge("serve.generated_tokens").set(float(total))
        reg.gauge("serve.decode_steps").set(float(engine.steps))
        reg.step(0)
    if rec is not None:
        out.always(f"wrote per-slot trace {rec.write(args.trace)}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--data-axis", type=int, default=0)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="in-flight batching: a request queue over --slots "
                         "decode lanes with block-allocated KV; short "
                         "requests retire early and queued ones join "
                         "mid-decode")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous: decode lanes (the decode batch width)")
    ap.add_argument("--requests", type=int, default=12,
                    help="continuous: queued request count")
    ap.add_argument("--length-spread", type=float, default=4.0,
                    help="continuous: max/min generated-length ratio of the "
                         "request stream")
    ap.add_argument("--block-size", type=int, default=16,
                    help="continuous: KV-block granularity (positions)")
    ap.add_argument("--trace", default="",
                    help="continuous: write the per-slot scheduled timeline "
                         "as a Chrome trace JSON")
    ap.add_argument("--metrics", default="",
                    help="write a metrics snapshot (engine counters, "
                         "throughput gauges) as JSONL; render with "
                         "`python -m repro.launch.report`")
    obs_log.add_log_args(ap)
    args = ap.parse_args(argv)
    out = obs_log.from_args("serve", args)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_host_mesh(data=args.data_axis, model=args.model_axis)
    gcfg = GSPMDConfig(rules=ShardingRules(), block_kv=256)
    mode = "continuous" if args.continuous else "wave"
    out.info(f"{cfg.name} mesh={dict(mesh.shape)} mode={mode} "
             f"prompt={args.prompt_len} gen={args.gen}")

    reg = None
    if args.metrics:
        reg = obs_metrics.MetricsRegistry(meta={
            "driver": "launch.serve", "arch": cfg.name, "mode": mode,
            "slots": args.slots, "source": "real"})
        reg.attach_jsonl(args.metrics)
        obs_metrics.set_active(reg)

    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    try:
        if args.continuous:
            return _serve_continuous(cfg, mesh, gcfg, params, args, key,
                                     out, reg)

        B, S = args.batch, args.prompt_len
        tokens = jax.random.randint(key, (B, S), 1, cfg.vocab_size)
        extras = {}
        if cfg.family == "audio":
            extras["encoder_embeds"] = jax.random.normal(
                key, (B, S, cfg.d_model))
        if cfg.frontend == "vision" and cfg.frontend_tokens:
            n = min(cfg.frontend_tokens, S)
            extras["vision_embeds"] = jax.random.normal(
                key, (B, n, cfg.d_model))

        engine = GenerationEngine(cfg, mesh, gcfg)
        res = engine.generate(params, tokens, args.gen,
                              batch_extras=extras or None)
        out.info(f"prefill {B}x{S} in {res.prefill_s:.2f}s "
                 f"({B * S / max(res.prefill_s, 1e-9):.0f} tok/s)")
        out.info(f"decoded {args.gen - 1} steps x {B} requests in "
                 f"{res.decode_s:.2f}s "
                 f"({B * (args.gen - 1) / max(res.decode_s, 1e-9):.1f} "
                 "tok/s)")
        ids = jnp.asarray(res.generated)
        out.info(f"sample output ids: {ids[0, :16].tolist()}")
        if reg is not None:
            reg.gauge("serve.prefill_s").set(res.prefill_s)
            reg.gauge("serve.decode_s").set(res.decode_s)
            reg.gauge("serve.generated_tokens").set(
                float(B * (args.gen - 1)))
            reg.step(0)
        return 0
    finally:
        if reg is not None:
            obs_metrics.set_active(None)
            reg.close()
            out.always(f"wrote metrics {args.metrics}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
