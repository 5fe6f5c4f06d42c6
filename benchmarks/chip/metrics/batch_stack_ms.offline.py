"""Mean host milliseconds of the program's ``batch.stack`` span a batch:
``BatchedModel.stack``, each row's input coercion and the stack onto the
device."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "batch.stack")
    return 1e3 * sum(d) / len(d) if d else None
