"""Host seconds of ``repro.core.dispatch`` (pattern matching, LOMA DSE,
DP partitioning) for the cell's network."""


def read(ctx):
    return ctx["setup"]["dispatch_s"]
