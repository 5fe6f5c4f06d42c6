"""The whole step's share of the chip's peak: inferences per second (as
``throughput_ips`` counts them) x 2 x MACs per inference, over the int8 peak
(the chip's highest)."""


def read(ctx):
    ips = ctx["run"]["completed"] / (ctx["run"]["last_done"] - ctx["run"]["window"][0])
    return 100.0 * ips * 2 * ctx["work"]["macs"] / ctx["peak"]["int8_ops_per_s"]
