"""Mean host milliseconds of the program's ``batch.stack`` span a batch:
``BatchedModel.stack``, the stack of the batch's host rows on the host and
its copy to the device, one per input."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "batch.stack")
    return 1e3 * sum(d) / len(d) if d else None
