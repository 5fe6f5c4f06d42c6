"""Share of the device's idle time in the traced window that fell inside
the serving thread's ``serve.schedule`` spans: the idle seconds the
per-round schedule holds the device to, over all idle seconds.  The spans
are mapped onto the profiler's clock through the ``window`` span and cut
to it."""

from benchmarks.chip import program
from benchmarks.chip.reduce import overlap

program.enable()


def read(ctx):
    t = ctx["trace"]
    if t is None or t["window_s"] <= t["busy_s"]:
        return None
    p0, p1 = t["spans"]["window"][0]
    inside = 0.0
    for s, e, _ in program.spans("serve.schedule"):
        s, e = max(program.to_profiler(ctx, s), p0), min(program.to_profiler(ctx, e), p1)
        if e > s:
            inside += (e - s) - overlap(t["busy"], s, e)
    return 100.0 * inside / (t["window_s"] - t["busy_s"]) if inside else None
