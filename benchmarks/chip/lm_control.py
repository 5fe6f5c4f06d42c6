"""Readings of the LM cells' controls, which ``correct`` must reject.

    python3 -m benchmarks.chip.lm_control --workload granite_4_0_h_small.decode_b64 \\
        --seeds 1,2 --seconds 3 [--controls none,float8,no_shared]

Each control is the program run with one change that the check must see;
the reference keeps the stated weights.  ``float8`` rounds every matrix of
the weights the engine holds to ``float8_e4m3fn`` (with a per-tensor
scale), a lower precision than the configuration's bf16; ``no_shared``
leaves out the shared expert; ``none`` changes nothing.  For each seed and
control it builds the engine, prefills and decodes for ``--seconds`` as a
run of the cell does, and prints the numbers a run compares.  TPU only,
one process.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np

from . import run
from .spans import Spans


def float8(params):
    """Every bf16 matrix rounded to 4 exponent and 3 mantissa bits (the
    grid of ``float8_e4m3fn``) after a per-tensor scale.  An explicit
    ``reduce_precision``: XLA on the TPU drops a convert to float8 and back
    as excess precision, which would leave the weights as they were."""
    import jax
    import jax.numpy as jnp

    def rnd(a):
        if a.ndim < 2 or a.dtype != jnp.bfloat16:
            return a
        s = jnp.max(jnp.abs(a.astype(jnp.float32))) / 240.0  # the largest normal of e4m3 with infinities
        r = jax.lax.reduce_precision(a.astype(jnp.float32) / s, exponent_bits=4, mantissa_bits=3)
        return (r * s).astype(a.dtype)

    return jax.tree.map(rnd, params)


def no_shared(params):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if "'shared'" in jax.tree_util.keystr(path) else a, params
    )


CONTROLS = {"none": None, "float8": float8, "no_shared": no_shared}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmarks.chip.lm_control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--controls", default="float8,no_shared")
    args = ap.parse_args(argv)
    spec = json.loads(run.SPEC.read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    config = run.load_named(run.HERE, "configs", cell["config"])
    traffic = run.load_named(run.HERE, "traffic", cell["traffic"])
    family = run.load_family(run.HERE, config)
    driver = run.load_module(run.HERE / "drivers" / f"{traffic['driver']}.py")
    sys.path.insert(0, str(run.CHECKOUT / "src"))
    run.device_gate(cell["chips"])
    run.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in args.controls.split(","):
            pool_rng, order_rng, check_rng = (np.random.default_rng(s) for s in np.random.SeedSequence(seed).spawn(3))
            model, _ = family.build(config, transform=CONTROLS[name])
            pool, requests = family.inputs(config, traffic, pool_rng)
            state = driver.setup(model, traffic, requests, Spans(annotate=False))
            res = driver.window(state, args.seconds, order_rng)
            driver.close(state)
            del model, state
            gc.collect()
            compared, checks = family.check(config, pool, res["answers"], res["unanswered"], check_rng, traffic["check_rows"])
            # run.main's decision, in its own words
            correct = bool(compared) and bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())
            rows = np.round(family.row_errors(config, res["answers"]), 5).tolist()  # (rows, positions)
            line = {"workload": cell["name"], "seed": seed, "control": name, "correct": correct, "checks": checks}
            print(json.dumps(line | {"positions": res["answers"]["positions"][0].tolist(), "rel_l2": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
