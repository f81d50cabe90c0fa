"""Device milliseconds a step under the ``moe`` scope (the expert layer's
norm, router, dispatch, held experts and combine; the shared experts are
under ``mlp``), in every pass: the self time of those operations in the
traced window (``bench/harness/scopes.py``), over the steps the window
completed.  None off a trace."""


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None:
        return None
    t = sum(v for k, v in scopes["scopes_s"].items()
            if k.split("/", 1)[1] == "moe")
    return 1e3 * t / len(ctx.steps)
