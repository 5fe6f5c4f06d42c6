"""Mean host milliseconds per batch of ``BatchedModel.unstack`` after the
batch's executable returned: the device-to-host copy and the split into
per-request rows."""


def read(ctx):
    d = ctx["spans"].durations("unstack", *ctx["run"]["window"])
    return 1e3 * sum(d) / len(d) if d else None
