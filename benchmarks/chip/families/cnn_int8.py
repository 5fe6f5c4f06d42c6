"""The int8 CNN family: MLPerf Tiny networks from ``repro.cnn.nets``.

The four parts a family gives the harness, bound to the benchmark's CNN
modules: ``model.build`` (the README flow, checked against the
configuration's layers), ``model.int8_pool`` (seeded int8 inputs),
the exact comparison with ``reference.forward`` and ``work.work``.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmarks.chip import model, reference
from benchmarks.chip.work import work  # noqa: F401  (the family's work part)


def build(config: dict):
    """The program, and the set-up parts timed while building it."""
    m = model.build(config)
    return m, {"dispatch_s": m.dispatch_s}


def inputs(config: dict, traffic: dict, rng: np.random.Generator) -> tuple[dict, list[dict]]:
    """The seeded pool, and one request per row of it."""
    pool = model.int8_pool(config, traffic["pool"], rng)
    return pool, [{k: v[i] for k, v in pool.items()} for i in range(traffic["pool"])]


def check(config: dict, pool: dict, answers: list, unanswered: int, rng, rows: int) -> tuple[bool, dict]:
    """Compare a seeded sample of the delivered answers with the reference,
    exactly; whether any answer was compared, and the numbers beside their
    limits (the harness holds each number to its limit)."""
    idx = np.concatenate([i for i, _ in answers]) if answers else np.zeros(0, int)
    got = [r for _, rs in answers for r in rs]
    pick = np.sort(rng.choice(len(idx), size=min(rows, len(idx)), replace=False))
    uniq, inv = np.unique(idx[pick], return_inverse=True)
    want = reference.forward(config, reference.make_weights(config), {k: v[uniq] for k, v in pool.items()})[inv]
    out = [np.asarray(next(iter(got[p].values())), np.float64) for p in pick]
    c = reference.compare(np.stack(out) if out else np.zeros((0,) + want.shape[1:]), want)
    checks = {
        "max_abs_err": {"value": c["max_abs_err"], "limit": 0.0},
        "wrong_rows": {"value": c["wrong_rows"], "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
    }
    print(f"benchmark: compared {len(pick)} of {len(idx)} answers with the reference", file=sys.stderr)
    return len(pick) > 0, checks
