"""Device milliseconds a step under the ``adamw`` scope (the update and
its global-norm clip): the self time of those operations in the traced
window (``bench/harness/scopes.py``), over the steps the window
completed.  None off a trace."""


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None:
        return None
    return 1e3 * scopes["phases_s"]["optimizer"] / len(ctx.steps)
