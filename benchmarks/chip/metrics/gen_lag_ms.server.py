"""99th percentile (nearest rank) of how late the load generator submitted
a request after its due time, in milliseconds: a starved generator shows
here, not as a fast server."""

import numpy as np


def read(ctx):
    lag = ctx["run"].get("lags_s")
    return None if lag is None or len(lag) == 0 else float(np.quantile(lag, 0.99, method="inverted_cdf")) * 1e3
