"""Paper-faithful compilation example: the DIANA/GAP9 MATCH flow with
transformations, dispatch, backend lowering + static memory planning,
bit-exact execution and per-module breakdown — plus the Fig. 9-style L1
ablation on one network.

  PYTHONPATH=src python examples/compile_cnn_match.py [--json] [--pipeline]
                                                      [--aot] [--trace]
                                                      [--serve] [--slo]

``--json`` additionally prints the machine-readable deployment report
(``CompiledModel.report_dict()``) — the same payload CI and the
calibration fitter consume.  ``--pipeline`` re-dispatches under the
makespan objective and prints the concurrent schedule's Gantt timeline
and per-module occupancy (``repro.pipeline``) next to the sequential
report, then proves the pipelined runtime bit-exact.  ``--aot`` fuses
the whole graph into ONE jitted executable (``repro.backend.aot``),
proves it bit-exact against the per-segment path, and prints the
per-segment vs AOT latency with the measured dispatch overhead.
``--trace`` records the whole MobileNet x gap9 flow — compile-phase
spans, measured per-module runtime lanes, pipelined worker lanes and the
predicted Gantt side-by-side — into one Chrome-trace JSON
(``match_trace.json``, loadable in ui.perfetto.dev) and prints the
predicted-vs-measured drift summary (``repro.obs``).  ``--serve`` fronts
the compiled model with a ``repro.serve.ModelServer`` replica — bounded
admission queue, vmap batch packing, priority-aware rounds — submits a
mixed-priority burst, proves every served output bit-exact with
sequential ``run``, and prints the replica stats that land in
``report_dict()["serve"]``.  ``--slo`` declares burn-rate service-level
objectives on a replica (``repro.obs.SloSpec``), arms the incident
flight recorder, induces an overload (tiny reject-policy queue under a
burst) so the latency/rejection objectives breach, and shows the
resulting Perfetto-loadable incident dump (``match_incident.json``) —
then points at the offline views: ``python -m repro.obs slo`` /
``python -m repro.obs flight``.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.backend import lower
from repro.cnn import dscnn_graph, init_graph_params
from repro.core import apply_transforms, dispatch
from repro.core.graph import dead_node_elimination, integerize, layout_to
from repro.targets import get_target

# 1. network transformations (paper Table II pipeline)
g = dscnn_graph()
g = apply_transforms(g, [dead_node_elimination, integerize(1), layout_to("NHWC")])

# 2. heterogeneous dispatch on both targets, resolved by registry name
for tgt in (get_target("gap9"), get_target("diana")):
    mapped = dispatch(g, tgt)
    mods = {k: f"{v:.0f}cyc" for k, v in mapped.cycles_by_module().items()}
    print(f"{tgt.name:6s}: {mapped.latency_s()*1e3:7.3f} ms  {mods}")
    first = mapped.module_of("conv_4x10")
    print(f"        4x10-filter first layer -> {first} (paper: not NE16-able)")

# 3. lower the *mapped* graph: fused, memory-planned segment executors,
#    golden-checked bit-exact against the interpreter
params = init_graph_params(g)
x = {k: np.random.default_rng(0).integers(-128, 128, s).astype("float32") for k, s in g.inputs.items()}
mapped = dispatch(g, "gap9")
compiled = lower(mapped)

max_err = compiled.verify(params, x)  # runs the interpreter internally
assert max_err == 0.0, f"compiled path diverged from the interpreter: {max_err}"
out = compiled.run(params, x, timed=True)
print("\ncompiled == interpreted:", {k: v.shape for k, v in out.items()}, f"(max |err| = {max_err})")
print(compiled.report())
if "--json" in sys.argv[1:]:
    print(json.dumps(compiled.report_dict(), indent=2, sort_keys=True))

# 3b. concurrent multi-module schedule + pipelined runtime (PR 5)
if "--pipeline" in sys.argv[1:]:
    from repro.pipeline import PipelinedModel

    mapped_ms = dispatch(g, "gap9", objective="makespan")
    pipelined = PipelinedModel(lower(mapped_ms))
    sched = pipelined.schedule
    print("\n" + sched.gantt())
    print("per-module occupancy:",
          {m: f"{o:.0%}" for m, o in sorted(sched.occupancy().items())})
    print(f"predicted: sequential {mapped_ms.total_cycles():.0f} cyc -> "
          f"makespan {sched.makespan:.0f} cyc ({sched.speedup():.2f}x)")
    err = pipelined.verify(params, x)
    assert err == 0.0, f"pipelined run diverged from sequential: {err}"
    print(f"pipelined == sequential (max |err| = {err})")

# 3c. whole-graph AOT executable (PR 6)
if "--aot" in sys.argv[1:]:
    aot = compiled.to_aot()
    aot.warmup(params, x)  # explicit trace + XLA compile, outside timing
    aot_err = aot.verify(params, x)
    assert aot_err == 0.0, f"AOT diverged from the per-segment path: {aot_err}"
    ov = aot.measure_dispatch_overhead(params, x)
    print(f"\nAOT == per-segment (max |err| = {aot_err})")
    print(f"per-segment path : {ov['per_segment_path_us']:9.1f} us "
          f"({ov['segments']} host dispatches)")
    print(f"one-jit AOT      : {ov['aot_us']:9.1f} us (1 dispatch)")
    print(f"dispatch overhead: {ov['dispatch_overhead_per_segment_us']:9.2f} us/segment "
          f"-> {ov['per_segment_path_us'] / max(ov['aot_us'], 1e-9):.2f}x speedup")
    entry = next(iter(aot._entries.values()))
    print(f"trace {entry.trace_us/1e3:.1f} ms, XLA compile {entry.compile_us/1e3:.1f} ms")

# 3c'. request-level serving over the compiled pipeline (PR 8)
if "--serve" in sys.argv[1:]:
    from repro.serve import ModelServer

    # fused fidelity keeps the demo fast; the segments/plan are identical
    served_model = lower(mapped, use_pallas=False, band_tiling=False)
    rng = np.random.default_rng(1)
    requests = [
        {k: rng.integers(-128, 128, s).astype("float32") for k, s in g.inputs.items()}
        for _ in range(10)
    ]
    priorities = [1.0, 1.0, 5.0, 1.0, 2.0, 1.0, 5.0, 1.0, 1.0, 2.0]
    with ModelServer(
        served_model, params, batch_slots=4, stream_depth=2, queue_capacity=16
    ) as server:
        server.warmup(requests[0])
        handles = [
            server.submit(r, priority=p) for r, p in zip(requests, priorities)
        ]
        served = [h.result(timeout=120) for h in handles]
    for r, out in zip(requests, served):
        ref = served_model.run(params, r)
        assert all(np.array_equal(np.asarray(ref[k]), np.asarray(out[k])) for k in ref)
    stats = served_model.report_dict()["serve"]
    eng = stats["engine"]
    print(f"\nserved {eng['completed']}/{eng['submitted']} requests bit-exact "
          f"(batch_slots={eng['batch_slots']}, {eng['rounds']} rounds, "
          f"{eng['rejected']} shed)")
    print(f"latency p50 {eng['latency_us']['p50']:.0f} us, "
          f"p99 {eng['latency_us']['p99']:.0f} us; last round order "
          f"{eng['last_round']['rids']} (priority jumps first)")
    print(f"predicted steady state: 1 request per "
          f"{stats['initiation_interval_cycles']:.0f} cyc on "
          f"{stats['bottleneck_module']} -> "
          f"{stats['predicted_requests_per_s']:.0f} req/s, stream speedup "
          f"x{stats['predicted_stream_speedup']:.2f}")

# 3c''. SLOs + incident flight recorder on a serving replica (PR 9)
if "--slo" in sys.argv[1:]:
    import warnings

    from repro import obs
    from repro.serve import ModelServer, QueueFullError

    dump_path = "match_incident.json"
    obs.arm_flight(dump_path)  # first trigger auto-writes the dump
    served_model = lower(mapped, use_pallas=False, band_tiling=False)
    specs = [
        # tight on purpose: the induced overload must breach both
        obs.SloSpec("p99_budget", "latency_p99_us", 2_000.0,
                    description="tail latency budget"),
        obs.SloSpec("rejections", "rejection_rate", 0.10,
                    description="shed-rate bound"),
    ]
    rng = np.random.default_rng(2)
    burst = [
        {k: rng.integers(-128, 128, s).astype("float32") for k, s in g.inputs.items()}
        for _ in range(24)
    ]
    rejected = 0
    with warnings.catch_warnings():
        # the breach warnings are this demo's point; show them once each
        warnings.simplefilter("always", obs.SloBreachWarning)
        with ModelServer(
            served_model, params, batch_slots=2, stream_depth=1,
            queue_capacity=2, policy="reject", replica="demo",
            slo=specs, slo_window_s=60.0,
        ) as server:
            server.warmup(burst[0])
            handles = []
            for r in burst:  # no pacing: the bounded queue must shed
                try:
                    handles.append(server.submit(r))
                except QueueFullError:
                    rejected += 1
            served = [h.result(timeout=120) for h in handles]
        slo = server.stats()["slo"]
    obs.disarm_flight()
    print(f"\nSLO demo: {len(served)} served, {rejected} rejected "
          f"(queue_capacity=2, reject policy)")
    for name, s in sorted(slo["specs"].items()):
        print(f"  {name:12s} {s['kind']:18s} value {s['value']:12.1f} "
              f"vs {s['threshold']:10.1f} burn {s['burn']:5.2f}x -> {s['state']}")
    doc = json.loads(Path(dump_path).read_text())
    print(f"incident dump: {len(doc['traceEvents'])} events -> {dump_path} "
          f"(reason={doc['metadata']['reason']!r}; load in ui.perfetto.dev)")
    print("offline views: python -m repro.obs flight match_incident.json")
    print("               python -m repro.obs slo <report.json>  "
          "(exit 1 on breach — CI-gateable)")

# 3d. end-to-end observability: one Chrome trace of the whole flow (PR 7)
if "--trace" in sys.argv[1:]:
    from repro import obs
    from repro.cnn import mlperf_tiny_networks
    from repro.pipeline import PipelinedModel

    trace_path = "match_trace.json"
    obs.enable_tracing()  # from here on every compile/runtime span records

    mn = mlperf_tiny_networks()["MobileNet"]
    mn_params = init_graph_params(mn)
    mn_x = {
        k: np.random.default_rng(0).integers(-128, 128, s).astype("float32")
        for k, s in mn.inputs.items()
    }
    # compile-phase spans: enumeration, DSE flush, Viterbi DP, makespan
    # re-rank, per-segment lowering routes, memory-planner packing
    mn_mapped = dispatch(mn, "gap9", objective="makespan")
    mn_compiled = lower(mn_mapped)
    mn_compiled.run(mn_params, mn_x)  # warmup (jit compile)
    mn_compiled.run(mn_params, mn_x, timed=True)  # measured run:* lanes
    pipelined = PipelinedModel(mn_compiled)
    pipelined.run(mn_params, mn_x)  # pipeline:* worker lanes
    # predicted Gantt lanes next to the measured ones (pid "predicted")
    obs.trace_predicted_schedule(pipelined.schedule, mn_compiled.target)

    obs.save_trace(trace_path)
    obs.disable_tracing()
    doc = json.loads(Path(trace_path).read_text())
    names = {}
    for e in doc["traceEvents"]:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            names[(e["pid"], e["tid"])] = e["args"]["name"]
    print(f"\ntrace: {len(doc['traceEvents'])} events -> {trace_path} "
          "(load in ui.perfetto.dev or chrome://tracing)")
    print("lanes:", ", ".join(sorted(set(names.values()))))
    drift = obs.drift_dict("gap9")
    print(f"drift (threshold {drift['threshold']:g}x):")
    for key, grp in sorted(drift["groups"].items()):
        print(f"  {key:14s} geomean {grp['geomean_ratio']:8.2f}x "
              f"over {grp['count']} segments"
              + ("  <- re-fit suggested" if grp["exceeds_threshold"] else ""))

# 4. L1 ablation (Fig. 9/10)
print("\nGAP9 L1 scaling (MACs/cycle):")
for kb in (128, 32, 8):
    tgt = get_target("gap9").scaled_l1(kb * 1024)
    print(f"  L1={kb:4d}kB -> {dispatch(g, tgt).macs_per_cycle():6.2f}")
