"""Mean host milliseconds a round of ``ModelServer`` spends building and
validating its stream schedule (the program's ``serve.schedule`` span)."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "serve.schedule")
    return 1e3 * sum(d) / len(d) if d else None
