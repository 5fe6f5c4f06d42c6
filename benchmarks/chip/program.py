"""The program's own spans and counters, for the per-layer metrics that read
them.

With ``repro.obs.enable_tracing(profiler=True)`` the program records its
spans in memory (``repro.obs.get_tracer()``, on ``time.perf_counter`` from
the tracer's ``epoch``) and also writes them into the profiler's trace as
``match.<name>``.  ``run.py`` imports a metric's reader only when it
prints that metric, so only a ``--trace 1`` run imports the readers that
read spans; each calls :func:`enable` as it is imported, and a
``--trace 0`` run never imports this module, so the end-to-end runs
execute the program with its tracer off.  On a program whose
``enable_tracing`` takes no ``profiler`` argument, :func:`enable` turns
nothing on and every reader returns nothing.

Enabling from a reader's import is a stand-in: a later benchmark change
should move it into ``run.py``, before set-up, and let ``reduce.load`` keep
the ``match.*`` events too, so that idle gaps are put down to them.
"""

from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[2] / "src"


def enable() -> bool:
    """Turn on the program's tracer with its spans in the profiler's trace;
    False where the program cannot write them there."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro import obs

    try:
        obs.enable_tracing(profiler=True)
    except TypeError:  # a program without profiler spans
        return False
    return True


def spans(name: str) -> list[tuple[float, float, dict]]:
    """``(start, end, attrs)`` of every ``name`` span the program recorded,
    in ``time.perf_counter`` seconds; none where :func:`enable` could not
    turn the spans on."""
    from repro import obs

    tr = obs.get_tracer()
    if getattr(tr, "annotate", None) is None:
        return []
    out = []
    for ev in tr.chrome_trace()["traceEvents"]:
        if ev["name"] == name and ev["ph"] == "X":
            start = tr.epoch + ev["ts"] * 1e-6
            out.append((start, start + ev["dur"] * 1e-6, ev.get("args", {})))
    return out


def rows(ctx: dict, name: str) -> list[tuple[float, float, dict]]:
    """The ``name`` spans that began inside the run's window."""
    lo, hi = ctx["run"]["window"]
    return [r for r in spans(name) if lo <= r[0] < hi]


def durations(ctx: dict, name: str) -> list[float]:
    """Seconds of each ``name`` span that began inside the window."""
    return [e - s for s, e, _ in rows(ctx, name)]


def to_profiler(ctx: dict, t: float) -> float:
    """A ``time.perf_counter`` time on the profiler's clock.  The
    benchmark's ``window`` span is recorded on both clocks (``ctx["spans"]``
    and the trace); the map is the line through its two ends."""
    (h0, h1), (p0, p1) = ctx["spans"].rows["window"][0], ctx["trace"]["spans"]["window"][0]
    return p0 + (t - h0) * (p1 - p0) / (h1 - h0)
