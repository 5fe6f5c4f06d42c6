"""Mean host microseconds of ``AotModel.run``'s entry lookup and
executable call a query (the program's ``aot.dispatch`` span)."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "aot.dispatch")
    return 1e6 * sum(d) / len(d) if d else None
