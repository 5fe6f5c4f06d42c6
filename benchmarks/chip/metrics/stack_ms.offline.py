"""Mean host milliseconds per batch of ``BatchedModel.run_batch_async``:
stacking the batch's inputs onto the device and enqueueing the executable."""


def read(ctx):
    d = ctx["spans"].durations("stack", *ctx["run"]["window"])
    return 1e3 * sum(d) / len(d) if d else None
