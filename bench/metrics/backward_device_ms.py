"""Device milliseconds a step in the step program's backward phase: the
self time of its operations in the traced window (``bench/harness/
scopes.py``), over the steps the window completed.  None off a trace."""


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None:
        return None
    return 1e3 * scopes["phases_s"]["backward"] / len(ctx.steps)
