"""Host-to-device transfers per inference: the ``h2d`` counts of the
window's ``batch.stack`` spans (input tensors that came as host arrays)
over their ``rows``."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    spans = program.rows(ctx, "batch.stack")
    rows = sum(a["rows"] for _, _, a in spans)
    return sum(a["h2d"] for _, _, a in spans) / rows if rows else None
