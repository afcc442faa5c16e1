"""Device milliseconds per batched step: the union of the device's
operations in the profiled window, averaged over the chips, divided by the
steps that ran in the window."""


def read(ctx):
    if ctx.trace is None or not len(ctx.step_hops):
        return None
    return 1e3 * ctx.trace["busy_s"] / len(ctx.step_hops)
