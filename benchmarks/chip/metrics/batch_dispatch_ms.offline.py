"""Mean host milliseconds of the program's ``batch.dispatch`` span a batch:
the lookup of the batch's executable and the call that enqueues it."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "batch.dispatch")
    return 1e3 * sum(d) / len(d) if d else None
