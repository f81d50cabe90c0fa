"""Host milliseconds a step in the program's ``data.to_device`` spans
(``build_minibatch``'s copy of the packed batch to the default device,
inside ``bench.pack``) within the traced window, over the steps the window
completed (host clock, read from the trace).  None off a trace."""


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None:
        return None
    return 1e3 * scopes["to_device_s"] / len(ctx.steps)
