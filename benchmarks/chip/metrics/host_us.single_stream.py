"""Mean microseconds of a query in which the device ran nothing: the query
span's length less the device busy time inside it, from the trace
(``AotModel.run``'s coercion, lookup and dispatch, and the output fetch)."""

import numpy as np

from benchmarks.chip.reduce import overlap


def read(ctx):
    t = ctx["trace"]
    q = None if t is None else t["spans"].get("query")
    if q is None or len(q) == 0:
        return None
    return 1e6 * float(np.mean([(e - s) - overlap(t["busy"], s, e) for s, e in q]))
