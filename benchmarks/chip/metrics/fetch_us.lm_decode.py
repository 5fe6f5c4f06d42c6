"""Mean host microseconds of the program's ``lm.fetch`` span: the host's
wait for one step's tokens (with the step dispatched ahead, the device
time of a step less its dispatch)."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    d = program.durations(ctx, "lm.fetch")
    return 1e6 * sum(d) / len(d) if d else None
