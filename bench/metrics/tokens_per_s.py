"""Training throughput: real (non-pad) sample tokens of every step the
window completed, over the window's seconds (host clock, from the window's
start to the end of its last step, which waits on the step's loss).
Counted over the whole global batch, so summed over the cell's chips."""


def read(ctx):
    return sum(sum(s.lengths) for s in ctx.steps) / ctx.window_s
