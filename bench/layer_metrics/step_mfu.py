"""The whole step's share of the chips' int8 peak, %: the operations of
every stream-hop emitted in the profiled window (the configuration's work
count, ``ctx.work.ops_per_hop``: 8,147,328 at the paper's width) over the
window's length, the chips and the peak."""


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    ops = ctx.work.ops_per_hop(ctx.geometry) * int(ctx.step_hops.sum())
    return 100.0 * ops / (ctx.trace["window_s"] * ctx.chips
                          * ctx.peak["int8_ops_per_s"])
