"""The routed expert path's roofline time over its device time, in %.

Roofline time: max(FLOPs / bf16 peak, bytes / HBM bandwidth) of the held
experts' matmuls (``work``), for the window's steps.  Device time: every
pass under the ``moe`` scope (``bench/harness/scopes.py``): the router,
the dispatch, the grouped-matmul kernels (``moe/experts``) and the
combine, the layer checkpoint's recompute included.  None off a trace or
off a chip with a published peak."""


def pairs_per_slot(run) -> float:
    """(token, expert) pairs a token slot routes to the held experts, on
    average: k * held / E (0.75 for 6 of 64 with 8 held)."""
    return (run["num_experts_per_tok"] * run["n_routed_experts"]
            / run["router_outputs"])


def work(run, S: int, m: int):
    """(FLOPs, bytes) of one step of m microbatches of S token slots, padding
    included, per chip.  Per expert layer and microbatch, P = S * pairs a
    slot: FLOPs 3 passes (forward; backward: input and weight gradients)
    x 3 matmuls (gate, up, down) x 2 P d f; bytes: the held experts'
    float32 weights read once in the forward and once in the backward,
    their gradient written once, and the pairs' d-wide float32 rows: in and
    out in the forward, the output's gradient and the input in and the
    input's gradient out in the backward.  The recompute counts for
    nothing."""
    d, f = run["hidden_size"], run["moe_intermediate_size"]
    layers = run["num_hidden_layers"] - run["first_k_dense_replace"]
    P = S * pairs_per_slot(run)
    weights = run["n_routed_experts"] * 3 * d * f * 4
    flops = 3 * 3 * 2 * P * d * f
    nbytes = 3 * weights + 5 * P * d * 4
    return layers * m * flops, layers * m * nbytes


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None or ctx.peaks is None:
        return None
    t = sum(v for k, v in scopes["scopes_s"].items()
            if k.split("/", 1)[1] == "moe")
    if t <= 0:
        return None
    roof = 0.0
    for s in ctx.steps:
        flops, nbytes = work(ctx.run, ctx.S, s.m)
        roof += max(flops / ctx.peaks["bf16_flops"],
                    nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * roof / t
