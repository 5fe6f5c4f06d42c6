"""Chip smoke: the MATCH compile -> AOT -> serve path on one TPU.

    python chip_smoke.py

One process, no children.  It refuses to run without a TPU (there is no
CPU fallback), then for the four MLPerf-Tiny nets at their published
sizes, batch 1, on the ``gap9`` and ``tpu_v5e`` targets:

1. compiles the README flow (transforms -> dispatch -> lower -> AOT),
2. runs the whole-graph executable on seeded int8-valued inputs and
   requires every output to equal ``repro.cnn.execute_graph`` run on the
   host CPU backend of the same process, bit for bit,
3. on gap9, requires a compiled Pallas kernel (``tpu_custom_call``) in
   the executable for every ``pallas_gemm`` segment,

then serves 32 requests through a ``ModelServer`` over DAE x gap9 (the
vmapped Mosaic GEMM) and checks each answer the same way.  The run times
it prints are smoke figures, not benchmark measurements.  The last line
of its output is ``{"ok": true, "device": {...}}``; any failure raises
and exits nonzero before that line.

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<checkout>/.jax_cache``, so a second run compiles less.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
from pathlib import Path

# the reference runs on the host CPU backend: keep it loadable when the
# platform list is pinned to the accelerator
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
TARGETS = ("gap9", "tpu_v5e")
CHECK_INPUTS = 3  # seeded inputs compared against the reference per pair
TIMED_RUNS = 50
SERVED_REQUESTS = 32


def device_gate() -> dict:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {dev.platform!r}")
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(f"device: kind={info['kind']} count={info['count']}", flush=True)
    return info


def int8_inputs(graph, rng) -> dict:
    return {
        k: rng.integers(-128, 128, size=s).astype(np.float32)
        for k, s in graph.inputs.items()
    }


def reference(graph, params, inputs) -> dict:
    from repro.cnn import execute_graph

    with jax.default_device(jax.devices("cpu")[0]):
        return {k: np.asarray(v) for k, v in execute_graph(graph, params, inputs).items()}


def max_abs_err(got: dict, want: dict) -> float:
    """Max |got - want| over all outputs; raises unless bit-exact."""
    err = 0.0
    for k, w in want.items():
        g = np.asarray(got[k])
        err = max(err, float(np.max(np.abs(g.astype(np.float64) - w))))
        if g.shape != w.shape or not np.array_equal(g, w):
            raise AssertionError(f"output {k} differs from the CPU reference (max |err| {err})")
    return err


def check_pair(net: str, graph, target: str, rng):
    from repro.backend import lower
    from repro.cnn import init_graph_params
    from repro.core import apply_transforms, dispatch
    from repro.core.graph import dead_node_elimination, integerize, layout_to

    g = apply_transforms(graph, [dead_node_elimination, integerize(1), layout_to("NHWC")])
    compiled = lower(dispatch(g, target))
    aot = compiled.to_aot()
    params = init_graph_params(g, SEED)
    checks = [int8_inputs(g, rng) for _ in range(CHECK_INPUTS)]
    entry = aot.warmup(params, checks[0])

    routes: dict[str, int] = {}
    for ls in compiled.segments:
        routes[ls.route] = routes.get(ls.route, 0) + 1
    if target == "gap9":
        kernels = entry.executable.as_text().count("tpu_custom_call")
        gemms = routes.get("pallas_gemm", 0)
        if gemms == 0 or kernels < gemms:
            raise AssertionError(
                f"{net} x {target}: {kernels} tpu_custom_call for {gemms} pallas_gemm segments"
            )

    err = max(max_abs_err(aot.run(params, x), reference(g, params, x)) for x in checks)
    times = []
    for _ in range(TIMED_RUNS):
        t0 = time.perf_counter()
        jax.block_until_ready(aot.run(params, checks[0]))
        times.append(time.perf_counter() - t0)
    print(
        f"pair {net} x {target}: routes={routes} trace_s={entry.trace_us * 1e-6:.3f} "
        f"compile_s={entry.compile_us * 1e-6:.3f} max_abs_err={err} "
        f"smoke_median_run_us={statistics.median(times) * 1e6:.1f} (smoke figure, not a benchmark)",
        flush=True,
    )
    return g, compiled, params


def serve(g, compiled, params, rng) -> None:
    from repro.serve import ModelServer

    requests = [int8_inputs(g, rng) for _ in range(SERVED_REQUESTS)]
    with ModelServer(compiled, params, batch_slots=8, stream_depth=2) as server:
        server.warmup(requests[0])
        handles = [server.submit(x) for x in requests]
        for h, x in zip(handles, requests):
            max_abs_err(h.result(), reference(g, params, x))
        stats = server.stats()
    if stats["completed"] != SERVED_REQUESTS:
        raise AssertionError(f"served {stats['completed']} of {SERVED_REQUESTS} requests")
    print(f"serve DAE x gap9: {SERVED_REQUESTS} requests bit-exact", flush=True)
    print(f"serve stats: {json.dumps(stats, sort_keys=True)}", flush=True)


def main() -> None:
    device = device_gate()
    from repro.backend.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    from repro.cnn import mlperf_tiny_networks

    rng = np.random.default_rng(SEED)
    built = {}
    for target in TARGETS:
        for net, graph in mlperf_tiny_networks().items():
            built[net, target] = check_pair(net, graph, target, rng)
    serve(*built["DAE", "gap9"], rng)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
