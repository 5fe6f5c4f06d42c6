"""90th percentile (nearest rank) of how long a request waited in the
admission queue, from its arrival to the take of its round (the program's
``serve.queue_wait`` spans), in milliseconds."""

import numpy as np

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "serve.queue_wait")
    return float(np.quantile(d, 0.90, method="inverted_cdf")) * 1e3 if d else None
