"""Host input per step: planning (``make_plan``), packing
(``build_minibatch``) and ``device_put``, mean milliseconds over the
window's steps (host clock)."""


def read(ctx):
    return 1e3 * sum(s.prep_s for s in ctx.steps) / len(ctx.steps)
