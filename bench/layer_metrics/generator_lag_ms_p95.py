"""95th percentile of the load generator's lag, ms: the time each chunk
was pushed minus the time it was due, over every chunk of the window.
Read from the benchmark's own clock; a late generator shows here before
it shows as a fast system."""
import numpy as np


def read(ctx):
    if ctx.lag_s.size == 0:
        return None
    return 1e3 * float(np.percentile(ctx.lag_s, 95))
