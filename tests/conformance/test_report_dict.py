"""CompiledModel.report_dict() is the machine-readable contract CI and
the calibration fitter consume: it must stay JSON-serializable on every
registered target, round-trip losslessly, and carry the pipeline
timeline (PR 5), AOT stats (PR 6), observability (PR 7) and SLO (PR 9)
payloads."""

import json
import warnings

import pytest

from repro import obs

from .harness import NETS, TARGETS, aot_for, compiled_for, io_for

pytestmark = pytest.mark.parametrize("tname", TARGETS)

# one net keeps the matrix cheap; the payload shape is net-independent
NET = "DSCNN"


def test_report_dict_json_roundtrip(tname):
    cm = compiled_for(NET, tname)
    d = cm.report_dict()
    back = json.loads(json.dumps(d, sort_keys=True))
    assert back == json.loads(json.dumps(back, sort_keys=True))  # stable
    assert back["graph"] == cm.graph.name
    assert back["target"] == cm.target.name
    assert len(back["segments"]) == len(cm.segments)
    assert back["predicted_total_cycles"] == pytest.approx(cm.predicted_cycles())
    assert back["memory_plan"]["fits"] in (True, False)


def test_report_dict_carries_pipeline_timeline(tname):
    cm = compiled_for(NET, tname)
    d = json.loads(json.dumps(cm.report_dict()))
    tl = d["pipeline"]
    assert tl["graph"] == cm.graph.name
    assert 0.0 < tl["makespan_cycles"] <= tl["sequential_cycles"] + 1e-6
    assert tl["speedup"] >= 1.0 - 1e-9
    n_scheduled = sum(len(m["segments"]) for m in tl["modules"].values())
    assert n_scheduled == len(cm.segments)
    for m, lane in tl["modules"].items():
        for seg in lane["segments"]:
            assert set(seg) >= {"name", "module", "start", "finish"}
            assert seg["module"] == m


def test_report_dict_roundtrips_with_measured_timings(tname):
    cm = compiled_for(NET, tname)
    params, x = io_for(NET)
    with warnings.catch_warnings():
        # timed runs feed the drift monitor; its (deliberately generous)
        # warning is not this test's subject
        warnings.simplefilter("ignore", obs.MatchWarning)
        cm.run(params, x, timed=True)
    d = cm.report_dict()
    back = json.loads(json.dumps(d, sort_keys=True))
    assert "timings" in back and len(back["timings"]) >= 1
    for row in back["timings"]:
        assert row["frequency_hz"] > 0.0
        assert row["measured_cycles"] >= 0.0


def test_report_dict_carries_obs_metrics_and_drift(tname):
    cm = compiled_for(NET, tname)
    params, x = io_for(NET)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", obs.MatchWarning)
        cm.run(params, x, timed=True)
    d = json.loads(json.dumps(cm.report_dict()))
    o = d["obs"]
    assert set(o) == {"metrics", "drift", "slo"}
    assert set(o["metrics"]) >= {"counters", "gauges", "histograms"}
    # the timed run above must show up in the per-module latency
    # histograms and in this target's drift groups
    mods = {ls.module for ls in cm.segments}
    for m in mods:
        h = o["metrics"]["histograms"][f"runtime.segment_us.{m}"]
        assert h["count"] >= 1
        # PR 9: sketch-backed approximate quantiles ride every non-empty
        # histogram, JSON-round-trippable and ordered
        assert 0.0 < h["p50"] <= h["p90"] <= h["p99"]
        assert h["p99"] <= h["max"] * (1.0 + h["quantile_accuracy"])
    assert o["drift"]["threshold"] >= 1.0
    assert set(o["drift"]["groups"]) >= {f"{cm.target.name}/{m}" for m in mods}
    for g in o["drift"]["groups"].values():
        assert g["count"] >= 1 and g["geomean_ratio"] > 0.0
    # PR 9: the SLO payload is always present and JSON-safe; engines
    # appear once a ModelServer(slo=[...]) registers one
    assert set(o["slo"]) >= {"engines", "breached"}
    assert isinstance(o["slo"]["engines"], dict)
    assert o["slo"]["breached"] in (True, False)
    for eng in o["slo"]["engines"].values():
        assert set(eng) >= {"name", "window_s", "worst_state", "breached", "specs"}
        for spec in eng["specs"].values():
            assert spec["state"] in ("ok", "warn", "breach")
            assert spec["kind"] in obs.SLO_KINDS


def test_report_dict_carries_serve_payload(tname):
    cm = compiled_for(NET, tname)
    d = json.loads(json.dumps(cm.report_dict(), sort_keys=True))
    s = d["serve"]
    assert set(s) >= {
        "initiation_interval_cycles",
        "bottleneck_module",
        "predicted_requests_per_s",
        "predicted_stream_speedup",
        "stream",
        "engine",
    }
    # the bottleneck module bounds steady-state throughput: one request
    # retires per initiation interval, never faster than end-to-end
    ii = s["initiation_interval_cycles"]
    assert 0.0 < ii <= d["pipeline"]["makespan_cycles"] + 1e-6
    assert s["bottleneck_module"] in d["cycles_by_module"]
    assert s["predicted_requests_per_s"] > 0.0
    assert s["predicted_stream_speedup"] >= 1.0 - 1e-9
    st = s["stream"]
    assert st["requests"] >= 1
    # streaming K requests costs at least one request's makespan and at
    # most K sequential runs
    assert st["makespan_cycles"] >= d["pipeline"]["makespan_cycles"] - 1e-6
    assert (
        st["makespan_cycles"]
        <= st["requests"] * d["predicted_total_cycles"] + 1e-6
    )
    assert st["weighted_completion_cycles"] > 0.0
    assert sorted(st["request_order"]) == list(range(st["requests"]))
    assert s["engine"] is None  # no replica served this memoized model


def test_report_dict_carries_aot_stats(tname):
    aot = aot_for(NET, tname)  # memoized: to_aot() pins cm._aot
    params, x = io_for(NET)
    aot.warmup(params, x)
    d = json.loads(json.dumps(compiled_for(NET, tname).report_dict()))
    a = d["aot"]
    assert a["segments"] == len(compiled_for(NET, tname).segments)
    assert len(a["entries"]) >= 1  # warmup traced + compiled one signature
    assert 0.0 <= a["intermediate_share"] <= 1.0
