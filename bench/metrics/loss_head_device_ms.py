"""Device milliseconds a step under the ``lm_head`` and ``cross_entropy``
scopes (final norm, head matmul, float32 log-softmax and target pick), in
every pass: the self time of those operations in the traced window
(``bench/harness/scopes.py``), over the steps the window completed.
None off a trace."""


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None:
        return None
    blocks = ("lm_head", "cross_entropy")
    t = sum(v for k, v in scopes["scopes_s"].items()
            if k.split("/", 1)[1] in blocks)
    return 1e3 * t / len(ctx.steps)
