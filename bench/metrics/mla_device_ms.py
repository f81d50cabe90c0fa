"""Device milliseconds a step under the ``attention`` scope (each layer's
norm, query, latent compression and expansion, attention and output
projection; in this cell the layers' latent attention), in every pass:
the self time of those operations in the traced window
(``bench/harness/scopes.py``), over the steps the window completed.
None off a trace."""


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None:
        return None
    t = sum(v for k, v in scopes["scopes_s"].items()
            if k.split("/", 1)[1] == "attention")
    return 1e3 * t / len(ctx.steps)
