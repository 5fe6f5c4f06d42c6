"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 -m benchmarks.chip --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are looked up
by name: the cell in ``BENCHMARK.json``, then ``configs/<config>.json``
(which names a model family, ``families/<family>.py``),
``traffic/<traffic>.json`` (which names a driver, ``drivers/<driver>.py``)
and one reader ``metrics/<metric>.py`` per metric.  A family gives four
functions: ``build(config)`` returns the model the drivers take and a dict
of named set-up parts; ``inputs(config, traffic, rng)`` the pool that the
check reads and the requests that the driver sends; ``check(config, pool,
answers, unanswered, rng, rows)`` returns ``(compared, checks)``: whether
it found answers to compare, and each compared number as a ``{"value",
"limit"}``; and ``work(config)`` the work of one inference,
which the readers take as ``ctx["work"]``.  A run:

1. refuses to go on without a TPU and as many chips as the cell asks for;
2. builds the program from the configuration (the family's ``build``),
   makes the cell's seeded inputs (its ``inputs``), and lets the driver
   warm up the cell's own shapes; all of that, from process start, is
   ``setup_s``;
3. drives the window for ``--seconds`` (with ``--trace 1`` under the
   profiler), reads the device's peak memory and frees the program;
4. compares a seeded sample of the answers the window delivered to the
   host with the family's plain reference (its ``check``); the run is
   ``correct`` only where the family compared something and every
   compared number is within its limit;
5. prints the metrics (the cell's end-to-end metrics, or with ``--trace 1``
   its per-layer metrics), then the compared numbers beside their limits,
   on standard error and as the last key of the result line.

It exits nonzero, with no result line, where anything is missing.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
SPEC = CHECKOUT / "BENCHMARK.json"
FAMILY_PARTS = ("build", "inputs", "check", "work")


def load_named(root: Path, kind: str, name: str) -> dict:
    """A configuration or traffic mix by name: ``<root>/<kind>/<name>.json``."""
    return json.loads((root / kind / f"{name}.json").read_text())


def load_module(path: Path):
    """A driver, family or metric reader from its file (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_chip_{path.parent.name}_{path.stem}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(root: Path, config: dict):
    """The configuration's model family, ``<root>/families/<family>.py``;
    raises where the configuration names none or the module lacks a part."""
    if "family" not in config:
        raise ValueError(f"configuration {config.get('name')!r} names no family")
    family = load_module(root / "families" / f"{config['family']}.py")
    missing = [f for f in FAMILY_PARTS if not callable(getattr(family, f, None))]
    if missing:
        raise ValueError(f"family {config['family']!r} lacks {', '.join(missing)}")
    return family


def cell_metrics(spec: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics that ``cell`` reports."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if cell in m["workloads"] or ("workloads" not in m and m["moves"] in names)]
    return e2e, per_layer


def device_gate(chips: int) -> dict:
    """The devices this run measures; exits nonzero without a TPU or with
    fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"benchmark: needs {chips} TPU chip(s), found {len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else the fixed directory ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak_bytes(chips: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def main(argv=None, *, root: Path = HERE, spec_path: Path = SPEC, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="python3 -m benchmarks.chip")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    spec = json.loads(Path(spec_path).read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        ap.error(f"no workload {args.workload!r} in {spec_path}")
    config = load_named(root, "configs", cell["config"])
    traffic = load_named(root, "traffic", cell["traffic"])
    e2e, per_layer = cell_metrics(spec, cell["name"])
    wanted = per_layer if args.trace else e2e
    readers = {m["name"]: load_module(root / "metrics" / f"{m['name']}.py") for m in wanted}
    driver = load_module(root / "drivers" / f"{traffic['driver']}.py")
    family = load_family(root, config)

    sys.path.insert(0, str(CHECKOUT / "src"))
    device = device_gate(cell["chips"])
    t_device = time.perf_counter()
    from . import reduce, work
    from .spans import Spans

    peak = work.peak_for(device["kind"])
    print(f"benchmark: {cell['name']} on {device['kind']} x{device['count']}, compile cache {use_compile_cache()}", file=sys.stderr)
    pool_rng, order_rng, check_rng = (np.random.default_rng(s) for s in np.random.SeedSequence(args.seed).spawn(3))
    model, parts = family.build(config)
    t_build = time.perf_counter()
    pool, requests = family.inputs(config, traffic, pool_rng)
    spans = Spans(annotate=bool(args.trace))
    state = driver.setup(model, traffic, requests, spans)
    # what set-up built lives to the end: keep the collector from rescanning
    # it in the window, where a full collection stalls the host for ~50 ms
    gc.collect()
    gc.freeze()
    setup = {"setup_s": time.perf_counter() - t_start, "compile_s": state["compile_s"], **parts}
    print(
        f"benchmark: set-up {setup['setup_s']:.2f} s: to the device {t_device - t_start:.2f}, "
        f"build {t_build - t_device:.2f} ({', '.join(f'{k} {v:.2f}' for k, v in parts.items())}), "
        f"pool and warm-up {t_start + setup['setup_s'] - t_build:.2f} (compile {state['compile_s']:.2f})",
        file=sys.stderr,
    )

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        if trace_dir:
            import jax

            # no Python function tracing: it would slow the host path it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level, opts.host_tracer_level = 0, 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        res = driver.window(state, args.seconds, order_rng)
        if trace_dir:
            jax.profiler.stop_trace()
        driver.close(state)
        device["memory_peak_bytes"] = memory_peak_bytes(cell["chips"])
        trace = None
        if trace_dir:
            t0 = time.perf_counter()
            trace = reduce.reduce_dir(trace_dir, cell["chips"])
            if trace is None:
                raise RuntimeError("the trace holds no window span or no device ops")
            size = sum(f.stat().st_size for f in Path(trace_dir).rglob("*") if f.is_file())
            print(f"benchmark: trace of {size / 2**20:.1f} MiB reduced in {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del model, state
    gc.collect()

    compared, checks = family.check(config, pool, res["answers"], res["unanswered"], check_rng, traffic["check_rows"])
    # the family gives the numbers and their limits; the harness holds each to its limit
    correct = bool(compared) and bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())
    ctx = {
        "cell": cell, "config": config, "traffic": traffic, "setup": setup, "run": res, "spans": spans,
        "trace": trace, "work": family.work(config), "peak": peak,
    }
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(ctx)
        if value is None and m in e2e:
            raise RuntimeError(f"end-to-end metric {m['name']} has nothing to read in {cell['name']}")
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["top_ops"], "idle_gaps": trace["idle_by_span"]}
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(line))
    return 0
