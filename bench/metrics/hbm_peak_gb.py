"""Device memory one chip needs for the step: the compiled program's
arguments + temp + outputs - aliased bytes (``memory_analysis()``), for the
largest microbatch count the cell's traffic reaches, in GB (1e9 bytes).
The runtime's ``peak_bytes_in_use`` on a TPU v5e leaves out the program's
temp space, so it is printed on an earlier line only."""


def read(ctx):
    return ctx.hbm_peak_bytes / 1e9
