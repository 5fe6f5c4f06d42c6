"""Mean host microseconds of the program's ``lm.decode`` span: the
dispatch of one decode step (``ServeEngine.steps``)."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "lm.decode")
    return 1e6 * sum(d) / len(d) if d else None
