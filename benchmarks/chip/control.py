"""Readings of the lower-precision controls, which ``correct`` must reject.

    python3 -m benchmarks.chip.control --workload <cell> --seeds 1,2,3

The control is the plain reference put in the program's place and computed
in a lower precision (``reference.PRECISIONS``: ``int4`` operands, the
nearest precision below the configuration's int8, and ``bf16_acc``
accumulators).  For each seed it draws the cell's input pool as a run of
that seed does, samples as many answers as a run compares
(``traffic["check_rows"]``, drawn from the pool like a window's requests),
and prints the numbers a run compares, with the program's place taken by
each control.  Runs on the host; the program is not involved.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import reference, run
from .model import int8_pool


def readings(config: dict, traffic: dict, seed: int) -> dict:
    pool_rng, order_rng, _ = (np.random.default_rng(s) for s in np.random.SeedSequence(seed).spawn(3))
    pool = int8_pool(config, traffic["pool"], pool_rng)
    pick = order_rng.integers(traffic["pool"], size=traffic["check_rows"])
    x = {k: v[pick] for k, v in pool.items()}
    weights = reference.make_weights(config)
    want = reference.forward(config, weights, x)
    return {p: reference.compare(reference.forward(config, weights, x, p), want) for p in reference.PRECISIONS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmarks.chip.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    spec = json.loads(run.SPEC.read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    config = run.load_named(run.HERE, "configs", cell["config"])
    traffic = run.load_named(run.HERE, "traffic", cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell["name"], "seed": seed, "rows": traffic["check_rows"]} | readings(config, traffic, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
