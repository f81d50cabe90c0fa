"""Device milliseconds a step in the step program's recompute phase, every
level of remat (the minibatch schedule has one, the layer checkpoint):
the self time of its operations in the traced window
(``bench/harness/scopes.py``), over the steps the window completed.  None
off a trace."""


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None:
        return None
    return 1e3 * scopes["phases_s"]["recompute"] / len(ctx.steps)
