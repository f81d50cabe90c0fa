"""Share of the token slots the step computes that hold no sample token:
1 - real tokens / (M x devices x microbatch tokens), over the window.
Under SPMD every device runs the largest M, so the empty microbatches of
lighter devices count as padding too."""


def read(ctx):
    real = sum(sum(s.lengths) for s in ctx.steps)
    slots = sum(s.m * ctx.chips * ctx.S for s in ctx.steps)
    return 100.0 * (1.0 - real / slots)
