"""99th percentile (nearest rank) of every request's time from its due time
to its outputs on the host, over all requests of the window.  Host stalls
of about 0.1 s, a few in a minute, set it, so it swings from run to run and
stands here beside ``server_p90_ms``."""

import numpy as np


def read(ctx):
    lat = ctx["run"].get("latencies_s")
    return None if lat is None or len(lat) == 0 else float(np.quantile(lat, 0.99, method="inverted_cdf")) * 1e3
