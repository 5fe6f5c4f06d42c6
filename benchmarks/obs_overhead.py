"""Tracing-overhead gate for repro.obs (observability subsystem, PR 7).

The tracer's contract is "zero overhead when disabled, negligible when
enabled".  This benchmark enforces the *enabled* half with teeth: it
**raises** if enabled tracing adds more than 3% wall-clock to the
``compiled_e2e`` execution shape (per-segment fused runs, HW-faithful
lowering) on the larger MLPerf-Tiny nets.

Method.  On the shared/virtualized boxes this runs on, identical
back-to-back arms differ by 10-30% (measured), so a gate built on the
*difference of two noisy end-to-end totals* flakes in both directions
no matter how the samples are paired.  The enabled path's delta is,
by construction, exactly the per-segment span-recording calls — the
jax work is identical — so the added wall-clock is measured directly:

* ``span_cost_us``: a tight-loop microbenchmark of the recording hot
  path (``now_us`` + ``complete`` with the same lane/attr shape the
  runtime emits), min over batches — the minimum converges to the true
  cost even under heavy preemption noise;
* ``spans_per_run``: counted from a real traced run (one per segment);
* overhead = ``spans_per_run * span_cost_us / median run_us``.

If span recording regresses (a lock on the hot path, attr-dict churn,
an allocation in ``now_us``), ``span_cost_us`` inflates and the gate
fails deterministically.  The paired on/off end-to-end ratio is also
reported for cross-checking, but not gated — it inherits the machine's
noise floor.

PR 9 extends the gate to the always-on serving observability:
``WindowedSketch.add``, the flight recorder's ``record_request``, and
``SloEngine.record_request`` are each
tight-loop measured the same way, and their summed per-request cost is
gated against the *same* 3% budget relative to one model run (a served
request costs at least one run, so this bounds the serve-side overhead
from above).  Note ``span_cost_us`` now transparently includes the
flight recorder's span mirror — ``Tracer._append`` feeds both deques.

Also writes the obs artifacts CI uploads: a Chrome trace holding one
full traced round per net (``obs_trace.json``) and a metrics snapshot
(``obs_metrics.json``).
"""

from __future__ import annotations

import gc
import json
import statistics
import time
from pathlib import Path

import jax
import numpy as np

from repro import obs
from repro.backend import lower
from repro.cnn import init_graph_params, mlperf_tiny_networks
from repro.core import dispatch
from repro.targets import get_target

from .common import emit, target_prefix

NETS = ("MobileNet", "DSCNN")
PAIRS = 7  # informational e2e cross-check only
SPAN_BATCH = 2000
SPAN_ROUNDS = 7
BUDGET = 3.0  # percent


def _span_cost_us(tracer) -> float:
    """Per-span cost of the runtime recording hot path, min over batches
    (the same ``now_us`` + ``complete`` shape ``CompiledModel.run``
    emits, lane and attrs included)."""
    best = float("inf")
    tracer.enabled = True
    try:
        for _ in range(SPAN_ROUNDS):
            t0 = time.perf_counter()
            for _ in range(SPAN_BATCH):
                t_us = tracer.now_us()
                tracer.complete(
                    "bench_segment", t_us, cat="runtime", lane="run:bench",
                    attrs={"route": "reference", "async": True},
                )
            dt = time.perf_counter() - t0
            best = min(best, dt / SPAN_BATCH * 1e6)
            tracer.clear()
    finally:
        tracer.enabled = False
    return best


def _per_event_us(fn, batch: int = SPAN_BATCH, rounds: int = SPAN_ROUNDS) -> float:
    """Min-over-rounds per-call cost of ``fn`` — the same estimator as
    ``_span_cost_us`` (the minimum converges under preemption noise)."""
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(batch):
            fn()
        dt = time.perf_counter() - t0
        best = min(best, dt / batch * 1e6)
    return best


def _serve_event_costs() -> dict[str, float]:
    """Tight-loop costs of the per-request observability hot path added
    in PR 9: rolling-window sketch add, flight-recorder request capture,
    and SLO window accounting."""
    from repro.obs.flight import FlightRecorder
    from repro.obs.sketch import WindowedSketch
    from repro.obs.slo import SloEngine, SloSpec

    win = WindowedSketch(window_s=60.0, intervals=12)
    fl = FlightRecorder()
    slo = SloEngine(
        [SloSpec("p99", "latency_p99_us", 1e9)], name="bench", register=False
    )
    vals = [float(v) for v in range(17, 2017, 2)]  # non-trivial spread
    idx = {"i": 0}

    def next_val() -> float:
        i = idx["i"]
        idx["i"] = (i + 1) % len(vals)
        return vals[i]

    costs = {
        "windowed_add_us": _per_event_us(lambda: win.add(next_val(), now_s=1.0)),
        "flight_record_request_us": _per_event_us(
            lambda: fl.record_request(
                rid=idx["i"], replica="bench", arrival_us=0.0,
                latency_us=next_val(), priority=0, status="ok", batch=8,
            )
        ),
        "slo_record_request_us": _per_event_us(
            lambda: slo.record_request(next_val(), now_s=1.0)
        ),
    }
    fl.clear()
    return costs


def run(
    out_path: str | None = "obs_overhead.json",
    target: str = "gap9",
    trace_path: str = "obs_trace.json",
    metrics_path: str = "obs_metrics.json",
    repeat: int = 0,
) -> list[str]:
    rows = []
    summary: dict[str, dict] = {}
    tgt = get_target(target)
    prefix, out_path = target_prefix(tgt.name, out_path, "obs_overhead.json")
    pairs = repeat if repeat > 0 else PAIRS

    was_enabled = obs.tracing_enabled()
    tracer = obs.get_tracer()
    tracer.enabled = False
    span_cost = _span_cost_us(tracer)
    serve_costs = _serve_event_costs()
    # a served request pays each of these exactly once (PR 9 hot path)
    serve_event_us = sum(serve_costs.values())

    worst = 0.0
    for name in NETS:
        g = mlperf_tiny_networks()[name]
        params = init_graph_params(g)
        x = {
            k: np.random.default_rng(0).integers(-128, 128, s).astype("float32")
            for k, s in g.inputs.items()
        }
        mapped = dispatch(g, tgt, budget=500)
        compiled = lower(mapped)

        def run_once():
            return jax.block_until_ready(list(compiled.run(params, x).values()))

        run_once()  # warmup: jit compile excluded from every sample

        # one real traced run: counts spans AND leaves the trace artifact
        tracer.clear()
        tracer.enabled = True
        run_once()
        tracer.enabled = False
        spans_per_run = len(tracer)

        # paired e2e samples — informational cross-check only (see module
        # docstring for why the machine's noise floor makes it ungateable)
        offs: list[float] = []
        ons: list[float] = []
        gc.collect()
        gc.disable()
        try:
            for i in range(pairs):
                for on in ([0, 1] if i % 2 == 0 else [1, 0]):
                    tracer.enabled = bool(on)
                    t0 = time.perf_counter()
                    run_once()
                    dt = time.perf_counter() - t0
                    (ons if on else offs).append(dt * 1e6)
                tracer.enabled = False
        finally:
            gc.enable()

        run_us = statistics.median(offs)
        added_us = spans_per_run * span_cost
        overhead_pct = added_us / run_us * 100.0
        # serving adds one sketch/flight/SLO hot-path hit per request; a
        # request costs at least one run, so this bounds serve overhead
        serve_overhead_pct = serve_event_us / run_us * 100.0
        e2e_ratio = statistics.median(ons) / run_us
        worst = max(worst, overhead_pct, serve_overhead_pct)
        summary[name] = {
            "run_us": run_us,
            "spans_per_run": spans_per_run,
            "span_cost_us": span_cost,
            "added_us": added_us,
            "overhead_pct": overhead_pct,
            "serve_event_us": serve_event_us,
            "serve_overhead_pct": serve_overhead_pct,
            "e2e_ratio_median": e2e_ratio,
            "segments": len(compiled.segments),
            "pairs": pairs,
        }
        rows.append(
            emit(
                f"obs_overhead_{prefix}{name}",
                run_us,
                f"spans={spans_per_run};span_cost_us={span_cost:.3f};"
                f"overhead={overhead_pct:.3f}%;budget={BUDGET:g}%;"
                f"e2e_ratio={e2e_ratio:.3f}",
            )
        )

    # artifacts for the CI smoke job: the traced rounds accumulated in
    # the process tracer — export them plus the metrics registry
    tracer.save(trace_path)
    Path(metrics_path).write_text(json.dumps(obs.metrics_dict(), indent=2))
    if was_enabled:
        obs.enable_tracing()

    summary["_gate"] = {
        "worst_overhead_pct": worst,
        "budget_pct": BUDGET,
        "span_cost_us": span_cost,
        "serve_event_us": serve_event_us,
        **serve_costs,
    }
    payload = json.dumps(summary, indent=2, sort_keys=True)
    print(f"obs_overhead JSON: {json.dumps(summary, sort_keys=True)}", flush=True)
    if out_path:
        Path(out_path).write_text(payload)
    if worst > BUDGET:
        raise AssertionError(
            f"observability adds {worst:.2f}% to compiled_e2e medians — "
            f"over the {BUDGET:g}% budget; the span hot path or the PR 9 "
            f"per-request path (sketch/flight/SLO) regressed"
        )
    return rows


if __name__ == "__main__":
    run()
