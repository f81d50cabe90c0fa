"""Seconds from the process's start to the first timed step: imports,
weights, compiling (or loading from the compile cache) every microbatch
count, and the first three steps.  The seconds spent reading the check's
norms from the state are left out."""


def read(ctx):
    return ctx.setup_s
