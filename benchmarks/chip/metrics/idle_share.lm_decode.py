"""Share of the traced window in which no XLA op ran on the device."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None or t["window_s"] <= 0 else 100.0 * (1.0 - t["busy_s"] / t["window_s"])
