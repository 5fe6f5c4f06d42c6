"""90th percentile (nearest rank) of every request's time from its due time
to its outputs on the host, over all requests of the window (the latency
of MLPerf Server, at the 90th percentile); a failed request counts with the
time it was waited for."""

import numpy as np


def read(ctx):
    lat = ctx["run"].get("latencies_s")
    return None if lat is None or len(lat) == 0 else float(np.quantile(lat, 0.90, method="inverted_cdf")) * 1e3
