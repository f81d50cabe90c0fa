"""Share of the traced window in which no operation runs on a device,
averaged over the devices (profiler trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
