"""The whole step's share of the chip's peak: inferences per second x
2 x MACs per inference, over the int8 peak (the chip's highest)."""


def read(ctx):
    t0, t1 = ctx["run"]["window"]
    ips = ctx["run"]["completed"] / (t1 - t0)
    return 100.0 * ips * 2 * ctx["work"]["macs"] / ctx["peak"]["int8_ops_per_s"]
