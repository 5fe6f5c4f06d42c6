"""Mean routed rows per held expert per step: the ``expert_rows`` counts
of the window's ``lm.fetch`` spans (routed (token, expert) pairs that the
held experts computed, summed over the layers) over layers x experts
held.  Even routing reads batch x top_k / experts routed (64 x 10 / 72 =
8.9)."""

from benchmarks.chip import program

program.enable()


def read(ctx):
    rows = [a["expert_rows"] for _, _, a in program.rows(ctx, "lm.fetch") if "expert_rows" in a]
    w = ctx["work"]
    return sum(rows) / (len(rows) * w["layers"] * w["experts_held"]) if rows else None
