"""repro.obs unit tests (PR 7): tracer contract (zero-overhead disabled,
Chrome-trace export, predicted lanes), metrics registry, drift monitor,
unified warning/logging routing, the offline CLI, divergence reporting,
and the timed-run synchronization regression."""

import json
import logging
import time
import types
import warnings

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs.trace import _NULL_SPAN, Tracer


@pytest.fixture(autouse=True)
def _isolate_obs_state():
    """Global tracer/drift state must not leak between tests (or into the
    rest of the suite, which asserts on report_dict contents)."""
    tracer = obs.get_tracer()
    was = tracer.enabled, tracer.path, tracer.annotate
    obs.reset_drift()
    yield
    tracer.enabled, tracer.path, tracer.annotate = was
    obs.reset_drift()


def _timing(module="cluster", predicted=100.0, us=10.0, hz=1e6, name="seg"):
    return types.SimpleNamespace(
        name=name,
        module=module,
        predicted_cycles=predicted,
        measured_us=us,
        frequency_hz=hz,
    )


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_disabled_tracer_hands_out_the_null_singleton():
    tr = Tracer()
    assert tr.span("a", cat="compile") is _NULL_SPAN
    assert tr.span("b") is tr.span("c")
    # the singleton is inert and chainable
    with tr.span("a") as sp:
        assert sp.set(foo=1) is sp
    tr.complete("a", 0.0)
    tr.instant("a")
    tr.slice("lane", "a", 0.0, 1.0)
    assert len(tr) == 0


def test_disabled_tracer_records_nothing_on_the_dispatch_hot_path():
    """The zero-overhead contract, end to end: a full dispatch with the
    process tracer disabled must not append a single event."""
    from repro.calibrate.microbench import dense_block_graph
    from repro.core import dispatch

    tracer = obs.get_tracer()
    tracer.enabled = False
    before = len(tracer)
    assert obs.span("x") is obs.span("y")  # module-level shorthand too
    dispatch(dense_block_graph(K=16, C=32), "gap9", budget=20)
    assert len(tracer) == before


def test_span_records_complete_events_with_attrs():
    tr = Tracer(enabled=True)
    with tr.span("phase", cat="compile", answer=42) as sp:
        sp.set(extra="yes")
    tr.complete("hot", tr.now_us() - 5.0, cat="runtime", lane="run:m")
    tr.instant("mark", cat="verify", detail="d")
    tr.slice("predicted:m", "seg", 10.0, 25.0, cycles=100)
    doc = tr.chrome_trace()
    json.loads(json.dumps(doc))  # Perfetto-loadable JSON
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs if e.get("ph") in ("X", "i")}

    span_ev = by_name["phase"]
    assert span_ev["ph"] == "X" and span_ev["cat"] == "compile"
    assert span_ev["dur"] >= 0.0
    assert span_ev["args"] == {"answer": 42, "extra": "yes"}

    assert by_name["hot"]["ph"] == "X"
    assert by_name["mark"]["ph"] == "i" and by_name["mark"]["s"] == "t"

    # predicted slices live in their own process row (pid 2), real spans
    # in pid 1 — that's what renders them side by side
    assert by_name["seg"]["pid"] == 2
    assert span_ev["pid"] == 1

    lane_names = {
        e["args"]["name"]
        for e in evs
        if e.get("ph") == "M" and e["name"] == "thread_name"
    }
    assert {"run:m", "predicted:m"} <= lane_names
    proc = {
        e["args"]["name"]
        for e in evs
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert proc == {"match", "predicted"}


def test_tracer_lanes_are_stable_and_clear_resets_events_only():
    tr = Tracer(enabled=True)
    assert tr._tid("lane_a") == tr._tid("lane_a")
    assert tr._tid("lane_a") != tr._tid("lane_b")
    tr.complete("x", 0.0, lane="lane_a")
    assert len(tr) == 1
    tr.clear()
    assert len(tr) == 0
    assert tr._tid("lane_a") == tr._tid("lane_a")  # lane table survives


def test_enable_disable_tracing_roundtrip(tmp_path):
    p = tmp_path / "t.json"
    tr = obs.enable_tracing(p)
    assert obs.tracing_enabled() and tr is obs.get_tracer()
    with obs.span("unit", cat="compile"):
        pass
    out = obs.save_trace()
    assert out == p
    doc = json.loads(p.read_text())
    assert any(e.get("name") == "unit" for e in doc["traceEvents"])
    obs.disable_tracing()
    assert not obs.tracing_enabled()


def test_profiler_spans_land_in_the_device_profilers_trace(tmp_path, host_events):
    """profiler=True: a scoped span enters a TraceAnnotation, so a
    recording jax.profiler trace holds it (nested as the spans were),
    while the in-memory record stays as it was."""
    tr = obs.enable_tracing(profiler=True)
    before = len(tr)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with obs.span("outer", cat="compile"):
            with tr.span("inner", answer=1):
                time.sleep(0.001)
    assert [e[0] for e in list(tr._events)[before:]] == ["inner", "outer"]
    ev = host_events(tmp_path)
    ((line, lo, hi),) = ev["match.outer"]
    ((line_in, lo_in, hi_in),) = ev["match.inner"]
    assert line_in == line and lo <= lo_in < hi_in <= hi


def test_profiler_spans_are_off_unless_asked_for(tmp_path, host_events):
    """MATCH_TRACE-style tracing (no profiler=True) writes nothing into the
    profiler's trace, and disabling tracing turns the annotations off."""
    obs.enable_tracing(profiler=True)
    assert obs.get_tracer().annotate is jax.profiler.TraceAnnotation
    obs.disable_tracing()
    assert obs.get_tracer().annotate is None and obs.span("x") is _NULL_SPAN
    obs.enable_tracing()
    assert obs.get_tracer().annotate is None
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("plain"):
            pass
    assert host_events(tmp_path) == {}


def test_tracer_epoch_maps_timestamps_to_perf_counter():
    tr = Tracer(enabled=True)
    t0 = time.perf_counter()
    with tr.span("s"):
        time.sleep(0.002)
    t1 = time.perf_counter()
    ((_, _, ts, dur, *_),) = tr._events
    start = tr.epoch + ts * 1e-6
    assert t0 <= start and start + dur * 1e-6 <= t1 and dur >= 2000.0


def test_obs_imports_jax_only_when_profiler_spans_are_asked_for():
    import subprocess
    import sys

    code = (
        "import sys; from repro import obs; assert 'jax' not in sys.modules; "
        "obs.enable_tracing(); assert 'jax' not in sys.modules; "
        "obs.enable_tracing(profiler=True); assert 'jax' in sys.modules"
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_trace_predicted_schedule_scales_cycles_to_module_clock():
    entries = [
        types.SimpleNamespace(
            name="seg0", module="m1", start=0.0, finish=100.0,
            compute_cycles=90.0, transfer_cycles=10.0,
        ),
        types.SimpleNamespace(
            name="seg1", module="m2", start=100.0, finish=150.0,
            compute_cycles=50.0, transfer_cycles=0.0,
        ),
    ]
    sched = types.SimpleNamespace(entries=entries)
    mods = {
        "m1": types.SimpleNamespace(frequency_hz=1e6),  # 1 cycle == 1 us
        "m2": types.SimpleNamespace(frequency_hz=2e6),
    }
    target = types.SimpleNamespace(module=lambda n: mods[n])

    tracer = obs.get_tracer()
    tracer.clear()
    tracer.enabled = True
    try:
        n = obs.trace_predicted_schedule(sched, target, t0_us=1000.0)
    finally:
        tracer.enabled = False
    assert n == 2
    evs = [e for e in tracer.chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    s0 = next(e for e in evs if e["name"] == "seg0")
    s1 = next(e for e in evs if e["name"] == "seg1")
    assert s0["ts"] == pytest.approx(1000.0) and s0["dur"] == pytest.approx(100.0)
    # m2 runs at 2 MHz: 50 cycles == 25 us, offset 100 cycles == 50 us
    assert s1["ts"] == pytest.approx(1050.0) and s1["dur"] == pytest.approx(25.0)
    assert all(e["pid"] == 2 for e in (s0, s1))
    tracer.clear()


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_metrics_counter_gauge_histogram():
    c = obs.counter("test_obs.counter")
    c.inc()
    c.inc(4)
    assert obs.counter("test_obs.counter") is c  # registry, not a factory
    obs.gauge("test_obs.gauge").set(2.5)
    h = obs.histogram("test_obs.hist")
    for v in (1.0, 2.0, 4.0, 1000.0):
        h.observe(v)
    d = obs.metrics_dict()
    assert d["counters"]["test_obs.counter"] == 5
    assert d["gauges"]["test_obs.gauge"] == 2.5
    hv = d["histograms"]["test_obs.hist"]
    assert hv["count"] == 4
    assert hv["sum"] == pytest.approx(1007.0)
    assert hv["min"] == 1.0 and hv["max"] == 1000.0
    assert sum(hv["buckets"].values()) == 4
    json.loads(json.dumps(d))


def test_reset_metrics_clears_the_registry():
    obs.counter("test_obs.reset_me").inc()
    obs.reset_metrics()
    assert "test_obs.reset_me" not in obs.metrics_dict()["counters"]


# ---------------------------------------------------------------------------
# Drift
# ---------------------------------------------------------------------------


def test_drift_warns_once_per_group_and_rearms_on_reset():
    timings = [_timing(us=1000.0, name=f"s{i}") for i in range(3)]  # 10x drift
    with pytest.warns(obs.CalibrationDriftWarning, match="tgt/cluster"):
        assert obs.observe_timings("tgt", timings) == 3
    # once per group: feeding more drifted samples stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.CalibrationDriftWarning)
        obs.observe_timings("tgt", timings)
    d = obs.drift_dict("tgt")
    g = d["groups"]["tgt/cluster"]
    assert g["count"] == 6
    assert g["geomean_ratio"] == pytest.approx(10.0)
    assert g["exceeds_threshold"] and g["warned"]
    obs.reset_drift()
    with pytest.warns(obs.CalibrationDriftWarning):
        obs.observe_timings("tgt", timings)


def test_drift_stays_silent_within_threshold_and_skips_unset_clocks():
    ok = [_timing(us=200.0, name=f"s{i}") for i in range(5)]  # 2x < 4x
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.CalibrationDriftWarning)
        assert obs.observe_timings("tgt", ok) == 5
    skipped = [
        _timing(hz=0.0),  # unset clock: never re-raises UnsetFrequencyWarning
        _timing(predicted=0.0),
        _timing(us=0.0),
    ]
    assert obs.observe_timings("tgt", skipped) == 0
    assert obs.drift_dict("tgt")["groups"]["tgt/cluster"]["count"] == 5


def test_drift_threshold_env_and_geomean_cancellation(monkeypatch):
    monkeypatch.setenv(obs.DRIFT_THRESHOLD_ENV, "1.5")
    assert obs.drift_threshold() == 1.5
    monkeypatch.setenv(obs.DRIFT_THRESHOLD_ENV, "0.2")
    assert obs.drift_threshold() == 1.0  # clamped
    monkeypatch.setenv(obs.DRIFT_THRESHOLD_ENV, "bogus")
    assert obs.drift_threshold() == 4.0
    monkeypatch.delenv(obs.DRIFT_THRESHOLD_ENV)
    # 4x over / 4x under must geomean to 1.0, not average to 2x
    pair = [_timing(us=400.0, name="over"), _timing(us=25.0, name="under")]
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.CalibrationDriftWarning)
        obs.observe_timings("tgt", pair * 3)
    g = obs.drift_dict("tgt")["groups"]["tgt/cluster"]
    assert g["geomean_ratio"] == pytest.approx(1.0)
    assert not g["exceeds_threshold"]


# ---------------------------------------------------------------------------
# Warnings + logging
# ---------------------------------------------------------------------------


def test_every_repo_warning_derives_from_match_warning():
    from repro.backend.runtime import UnsetFrequencyWarning
    from repro.calibrate.profile import CalibrationProfileWarning
    from repro.core.loma import ScheduleCacheWarning

    for w in (
        ScheduleCacheWarning,
        CalibrationProfileWarning,
        UnsetFrequencyWarning,
        obs.CalibrationDriftWarning,
    ):
        assert issubclass(w, obs.MatchWarning)
        assert issubclass(w, UserWarning)
    # pre-PR-7 filters keyed on RuntimeWarning keep matching
    assert issubclass(UnsetFrequencyWarning, RuntimeWarning)


def test_obs_warn_emits_both_a_warning_and_a_log_record(caplog):
    with caplog.at_level(logging.WARNING, logger="repro"):
        with pytest.warns(obs.MatchWarning, match="unified routing"):
            obs.warn("unified routing test", obs.MatchWarning, logger="unit")
    recs = [r for r in caplog.records if r.name == "repro.unit"]
    assert len(recs) == 1
    assert "MatchWarning: unified routing test" in recs[0].getMessage()


def test_log_level_parses_match_log_env(monkeypatch):
    monkeypatch.delenv(obs.LOG_ENV, raising=False)
    assert obs.log_level() == logging.WARNING
    monkeypatch.setenv(obs.LOG_ENV, "debug")
    assert obs.log_level() == logging.DEBUG
    monkeypatch.setenv(obs.LOG_ENV, "15")
    assert obs.log_level() == 15
    monkeypatch.setenv(obs.LOG_ENV, "nonsense")
    assert obs.log_level() == logging.WARNING


def test_library_import_never_configures_root_logging(monkeypatch):
    # library etiquette: without MATCH_LOG the repro logger carries only
    # a NullHandler (keeps logging.lastResort from spraying the warning
    # echoes to stderr) and still propagates to application handlers
    monkeypatch.delenv(obs.LOG_ENV, raising=False)
    logger = obs.get_logger()
    if logger.propagate:  # MATCH_LOG was never set in this process
        assert all(isinstance(h, logging.NullHandler) for h in logger.handlers)
    else:  # a prior MATCH_LOG run attached the stderr handler instead
        assert logger.handlers


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_summarize(tmp_path, capsys):
    from repro.obs.__main__ import main

    tr = Tracer(enabled=True)
    with tr.span("dispatch", cat="compile"):
        pass
    tr.complete("conv0", tr.now_us() - 3.0, cat="runtime", lane="run:cluster")
    tr.instant("divergence:conv0", cat="verify")
    tr.slice("predicted:cluster", "conv0", 0.0, 5.0)
    p = tr.save(tmp_path / "trace.json")
    assert main(["summarize", str(p)]) == 0
    out = capsys.readouterr().out
    assert "3 spans, 1 instants" in out  # the predicted slice is a span too
    assert "run:cluster" in out and "predicted:cluster" in out
    assert "dispatch" in out


def test_cli_drift_verdicts(tmp_path, capsys):
    from repro.obs.__main__ import main

    def row(module, us):
        return {
            "module": module,
            "predicted_cycles": 100.0,
            "measured_us": us,
            "frequency_hz": 1e6,
        }

    report = {
        "target": "tgt",
        "timings": [row("fast", 120.0)] * 3 + [row("slow", 1000.0)] * 3,
    }
    p = tmp_path / "report.json"
    p.write_text(json.dumps(report))
    assert main(["drift", str(p)]) == 0
    out = capsys.readouterr().out
    assert "DRIFTED" in out and "ok" in out

    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"target": "tgt", "segments": []}))
    assert main(["drift", str(empty)]) == 1

    with pytest.raises(SystemExit):
        main(["summarize", str(tmp_path / "missing.json")])


# ---------------------------------------------------------------------------
# Runtime integration: divergence reporting + timed-run synchronization
# ---------------------------------------------------------------------------


def _small_compiled():
    from repro.backend import lower
    from repro.calibrate.microbench import graph_io
    from repro.cnn import conv_block_graph
    from repro.core import dispatch

    g = conv_block_graph(IX=8, IY=8, C=8, K=8)
    compiled = lower(dispatch(g, "gap9", budget=30))
    params, x = graph_io(g)
    return compiled, params, x


def test_divergence_report_to_dict_and_trace_instant():
    compiled, params, x = _small_compiled()
    report = compiled.verify(params, x, per_segment=True)
    assert report.exact and report.first_divergent is None
    d = json.loads(json.dumps(report.to_dict()))
    assert d["exact"] is True and d["first_divergent"] is None
    assert len(d["segments"]) == len(compiled.segments)

    # corrupt one segment executor: the report must localize it and the
    # enabled tracer must carry the divergence as an instant event
    ls = compiled.segments[0]
    orig = ls.fn
    ls.fn = lambda p, *xs: orig(p, *xs) + 1.0
    tracer = obs.get_tracer()
    tracer.clear()
    tracer.enabled = True
    try:
        bad = compiled.verify(params, x, per_segment=True)
    finally:
        tracer.enabled = False
        ls.fn = orig
    assert not bad.exact
    assert bad.first_divergent is not None and bad.first_divergent.name == ls.name
    bd = bad.to_dict()
    assert bd["first_divergent"]["max_abs_err"] == bad.max_abs_err > 0.0
    instants = [
        e for e in tracer.chrome_trace()["traceEvents"] if e.get("ph") == "i"
    ]
    assert any(
        e["name"] == f"divergence:{ls.name}"
        and e["cat"] == "verify"
        and e["args"]["first_divergent"]["name"] == ls.name
        for e in instants
    )
    tracer.clear()


def test_timed_run_blocks_until_ready_before_stopping_the_clock():
    """Regression for the timed-run contract: ``measured_us`` must cover
    the blocked device compute, not just the async host dispatch.  On a
    deliberately large segment the blocked wall-clock is orders of
    magnitude above dispatch cost, so an un-synchronized timer would
    report a tiny fraction of the real run time."""
    from repro.backend import lower
    from repro.calibrate.microbench import graph_io
    from repro.cnn import conv_block_graph
    from repro.core import dispatch

    g = conv_block_graph(IX=32, IY=32, C=32, K=64)  # ~60M MACs
    compiled = lower(dispatch(g, "gap9", budget=30))
    params, x = graph_io(g)
    outs = compiled.run(params, x)  # warmup: jit compile out of the way
    jax.block_until_ready(list(outs.values()))

    # the best case of each regime, so that load from other processes
    # tips neither side: the fastest blocked untimed run against the
    # slowest timed total
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(list(compiled.run(params, x).values()))
        walls.append((time.perf_counter() - t0) * 1e6)
    wall_us = min(walls)

    totals = []
    for _ in range(3):
        compiled.run(params, x, timed=True)
        timings = compiled.last_timings
        assert timings and all(tm.measured_us > 0.0 for tm in timings)
        totals.append(sum(tm.measured_us for tm in timings))
    total_us = max(totals)
    # an async (non-blocking) timer measures host dispatch only — a few
    # percent of the blocked wall-clock; 20% is far outside that regime
    # yet robust to scheduler noise in the other direction
    assert total_us >= 0.2 * wall_us, (
        f"timed run measured {total_us:.0f}us total vs {wall_us:.0f}us "
        "blocked wall-clock: run(timed=True) is not synchronizing"
    )


def test_timed_run_feeds_metrics_and_drift():
    compiled, params, x = _small_compiled()
    obs.reset_drift()
    compiled.run(params, x)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", obs.MatchWarning)
        compiled.run(params, x, timed=True)
    d = obs.drift_dict(compiled.target.name)
    assert d["groups"], "timed run did not feed the drift monitor"
    total = sum(g["count"] for g in d["groups"].values())
    assert total == len(compiled.last_timings)
    mods = {tm.module for tm in compiled.last_timings}
    hists = obs.metrics_dict()["histograms"]
    for m in mods:
        assert hists[f"runtime.segment_us.{m}"]["count"] >= 1
