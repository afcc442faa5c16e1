"""Roofline share of the hop step, %: the least time the chips could take
for the steps of the profiled window over the device time they took.

Each step's least time is the larger of its operations over the int8
peak and its bytes over HBM bandwidth, per chip, for the stream-hops that
chip emitted; operations and bytes are the configuration's work count's
(``ctx.work``), at the algorithm's own widths, so the number reads the
same work whatever backend runs it."""


def read(ctx):
    if ctx.trace is None or ctx.peak is None or not len(ctx.step_hops):
        return None
    least = sum(ctx.work.least_step_s(ctx.geometry, h / ctx.chips,
                                      ctx.peak)[0]
                for h in ctx.step_hops.tolist())
    return 100.0 * least / ctx.trace["busy_s"]
