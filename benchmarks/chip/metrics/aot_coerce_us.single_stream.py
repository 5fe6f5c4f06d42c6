"""Mean host microseconds of ``AotModel.run``'s input coercion a query
(the program's ``aot.coerce`` span)."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "aot.coerce")
    return 1e6 * sum(d) / len(d) if d else None
