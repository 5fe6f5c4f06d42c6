"""Host spans around the benchmark's own calls into the program.

Each span is kept in memory on the host clock (``time.perf_counter``) and,
in a traced run, also written into the profiler's trace as a
``TraceAnnotation`` named ``bench.<name>``, so that the trace reduction can
say what the host was doing while the device sat idle.
"""

from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

PREFIX = "bench."


class Spans:
    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.rows: dict[str, list[tuple[float, float]]] = defaultdict(list)

    @contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            from jax.profiler import TraceAnnotation

            with TraceAnnotation(PREFIX + name):
                yield
        else:
            yield
        self.rows[name].append((t0, time.perf_counter()))

    def durations(self, name: str, start: float, end: float) -> list[float]:
        """Seconds of each ``name`` span that began inside ``[start, end)``."""
        return [b - a for a, b in self.rows.get(name, ()) if start <= a < end]
