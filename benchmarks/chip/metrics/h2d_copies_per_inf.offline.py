"""Host-to-device copies per inference: the ``copies`` counts of the
window's ``batch.stack`` spans (the transfers the stack made) over their
``rows``.  Nothing where a span lacks the count, as on a program that does
not record it."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    spans = program.rows(ctx, "batch.stack")
    rows = sum(a["rows"] for _, _, a in spans)
    if not rows or any("copies" not in a for _, _, a in spans):
        return None
    return sum(a["copies"] for _, _, a in spans) / rows
