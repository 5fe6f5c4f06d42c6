"""Share of its roofline that the batch executable (``BatchedModel``'s
``whole_batch``) reaches: the least time of one batch (``work.least_time_s``:
2 x MACs at the int8 peak, or the least bytes at HBM bandwidth, whichever
is longer) over the mean device time of one run of the executable."""

import numpy as np

from benchmarks.chip.work import least_time_s


def read(ctx):
    t = ctx["trace"]
    runs = [] if t is None else [d for name, ds in t["modules"].items() if "whole_batch" in name for d in ds]
    if not runs:
        return None
    return 100.0 * least_time_s(ctx["work"], ctx["peak"], ctx["traffic"]["batch"]) / float(np.mean(runs))
