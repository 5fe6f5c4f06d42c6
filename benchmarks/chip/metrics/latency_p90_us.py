"""90th percentile (nearest rank) of every query's time from numpy input to
numpy output, over all queries of the window (MLPerf SingleStream)."""

import numpy as np


def read(ctx):
    lat = ctx["run"].get("latencies_s")
    return None if lat is None or len(lat) == 0 else float(np.quantile(lat, 0.90, method="inverted_cdf")) * 1e6
