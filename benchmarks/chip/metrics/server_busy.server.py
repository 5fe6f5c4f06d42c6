"""Share of the window in which the serving thread was in a round (the
program's ``serve.round`` spans, cut to the window), not waiting on its
queue."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    t0, t1 = ctx["run"]["window"]
    inside = sum(max(0.0, min(e, t1) - max(s, t0)) for s, e, _ in program.spans("serve.round"))
    return 100.0 * inside / (t1 - t0) if inside else None
