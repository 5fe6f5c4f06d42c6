"""The readers of the program's own spans and counters: on a synthetic
context with a known answer, on a program that cannot write its spans into
the profiler's trace, and in whole traced runs of each driver on the CPU."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import reduce, run

METRICS = run.HERE / "metrics"
NEW = {
    "resnet8_cifar10.offline_b256": ["batch_stack_ms.offline", "batch_dispatch_ms.offline", "h2d_per_inf.offline"],
    "mobilenetv1_025_vww.server_poisson": [
        "schedule_ms.server", "server_busy.server", "queue_wait_p90_ms.server", "idle_schedule_share.server",
    ],
    "resnet8_cifar10.single_stream": ["aot_coerce_us.single_stream", "aot_dispatch_us.single_stream"],
}


@pytest.fixture
def tracer():
    """Every reader turns the program's tracer on as it is imported: put
    the tracer back as it was (on/off, save path, profiler spans, events)
    so that no other test sees tracing on.  The test starts with no events:
    what an earlier test recorded would land in its synthetic window."""
    from repro import obs

    tr = obs.get_tracer()
    was = tr.enabled, tr.path, tr.annotate, list(tr._events)
    tr.clear()
    yield tr
    tr.enabled, tr.path, tr.annotate = was[:3]
    tr.clear()
    tr._events.extend(was[3])


def reader(name: str):
    return run.load_module(METRICS / f"{name}.py")


def test_every_new_metric_is_declared_for_its_cells():
    spec = json.loads(run.SPEC.read_text())
    for cell, names in NEW.items():
        _, per_layer = run.cell_metrics(spec, cell)
        assert set(names) <= {m["name"] for m in per_layer}
        assert all((METRICS / f"{n}.py").is_file() for n in names)


def _ctx(tr, shift: float) -> tuple[dict, float]:
    """A window of 20 s from 10 s after the tracer's epoch; the profiler's
    clock reads the same instants ``shift`` seconds later."""
    lo = tr.epoch + 10.0
    h0, h1 = lo - 0.001, lo + 20.001  # the window span, a little wider than the window
    trace = {
        "spans": {"window": np.array([[h0 + shift, h1 + shift]])},
        "busy": np.array([[lo + 1.004 + shift, lo + 1.006 + shift], [lo + 5.0 + shift, lo + 5.5 + shift]]),
        "window_s": h1 - h0,
        "busy_s": 0.502,
    }
    spans = SimpleNamespace(rows={"window": [(h0, h1)]})
    return {"run": {"window": (lo, lo + 20.0)}, "spans": spans, "trace": trace}, lo


def _record(tr, lo: float, name: str, at: float, dur: float, **attrs) -> None:
    tr._append(name, "", (lo + at - tr.epoch) * 1e6, dur * 1e6, 0, attrs or None)


def test_readers_on_a_synthetic_context(tracer):
    reads = {n: reader(n) for names in NEW.values() for n in names}  # turns tracing on
    assert tracer.enabled and tracer.annotate is not None
    ctx, lo = _ctx(tracer, shift=1234.5)
    _record(tracer, lo, "batch.stack", -1.0, 0.5, rows=256, h2d=256)  # before the window
    _record(tracer, lo, "batch.stack", 1.0, 0.2, rows=256, h2d=256)
    _record(tracer, lo, "batch.stack", 2.0, 0.1, rows=128, h2d=0)
    _record(tracer, lo, "batch.dispatch", 1.2, 0.0004)
    _record(tracer, lo, "batch.dispatch", 2.1, 0.0002)
    _record(tracer, lo, "serve.round", -0.01, 0.02, round=0, requests=3)  # half in the window
    _record(tracer, lo, "serve.round", 1.0, 0.02, round=1, requests=3)
    _record(tracer, lo, "serve.round", 19.99, 0.03, round=2, requests=2)  # a third in the window
    _record(tracer, lo, "serve.schedule", 1.0, 0.010)  # the device ran 2 ms of it
    _record(tracer, lo, "serve.schedule", 5.2, 0.006)  # the device ran all of it
    _record(tracer, lo, "serve.schedule", 20.0, 0.004)  # 1 ms in the window span
    for i, w in enumerate([0.001 * k for k in range(1, 11)]):
        _record(tracer, lo, "serve.queue_wait", 0.5 + i, w, rid=i, round=i)
    _record(tracer, lo, "aot.coerce", 3.0, 30e-6)
    _record(tracer, lo, "aot.coerce", 3.1, 50e-6)
    _record(tracer, lo, "aot.dispatch", 3.0, 100e-6)
    got = {n: r.read(ctx) for n, r in reads.items()}
    assert got["batch_stack_ms.offline"] == pytest.approx(150.0)
    assert got["batch_dispatch_ms.offline"] == pytest.approx(0.3)
    assert got["h2d_per_inf.offline"] == pytest.approx(256 / 384)
    assert got["schedule_ms.server"] == pytest.approx(8.0)
    assert got["server_busy.server"] == pytest.approx(100 * 0.04 / 20.0)
    assert got["queue_wait_p90_ms.server"] == pytest.approx(9.0)
    idle = ctx["trace"]["window_s"] - ctx["trace"]["busy_s"]
    assert got["idle_schedule_share.server"] == pytest.approx(100 * 0.009 / idle, rel=1e-6)
    assert got["aot_coerce_us.single_stream"] == pytest.approx(40.0)
    assert got["aot_dispatch_us.single_stream"] == pytest.approx(100.0)


def test_clock_mapping_follows_the_window_span(tracer):
    from benchmarks.chip import program

    ctx, lo = _ctx(tracer, shift=-7.25)
    (h0, h1), (p0, p1) = ctx["spans"].rows["window"][0], ctx["trace"]["spans"]["window"][0]
    assert program.to_profiler(ctx, h0) == pytest.approx(p0) and program.to_profiler(ctx, h1) == pytest.approx(p1)
    assert program.to_profiler(ctx, lo + 3.0) == pytest.approx(lo + 3.0 - 7.25)
    # a profiler clock that runs 1e-4 faster is followed, not just shifted
    ctx["trace"]["spans"]["window"] = np.array([[p0, p0 + (h1 - h0) * (1 + 1e-4)]])
    assert program.to_profiler(ctx, h0 + 10.0) == pytest.approx(p0 + 10.0 * (1 + 1e-4), abs=1e-9)


def test_a_program_without_profiler_spans_reads_nothing(tracer, monkeypatch):
    """On a program whose enable_tracing takes no ``profiler`` argument, the
    readers turn nothing on and return nothing, without raising."""
    from repro import obs

    obs.disable_tracing()
    monkeypatch.setattr(obs, "enable_tracing", lambda path=None, *, autosave=False: obs.get_tracer())
    reads = {n: reader(n) for names in NEW.values() for n in names}
    assert not tracer.enabled
    ctx, lo = _ctx(tracer, shift=0.0)
    assert all(r.read(ctx) is None for r in reads.values())


def _cpu_trace(trace_dir, chips):
    """The reduction a CPU trace allows: its host spans, and no device busy
    time (the CPU's trace has no device plane)."""
    t = reduce.load(sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))[-1])
    lo, hi = t["spans"]["window"][0]
    return {"window_s": hi - lo, "busy_s": 0.0, "busy": np.zeros((0, 2)), "spans": t["spans"],
            "idle_by_span": [], "top_ops": [], "modules": {}}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_run_prints_the_new_metrics(small_root, run_cell, monkeypatch, tracer, cell):
    monkeypatch.setattr(reduce, "reduce_dir", _cpu_trace)
    line = run_cell(small_root, cell, trace=1)
    assert line["correct"] is True
    got = {n: line["metrics"][n]["value"] for n in NEW[cell]}
    assert all(v > 0 for v in got.values()), got
    if "h2d_per_inf.offline" in got:
        assert got["h2d_per_inf.offline"] == 1.0
    if "server_busy.server" in got:
        assert got["server_busy.server"] <= 100.0 and got["idle_schedule_share.server"] <= 100.0
