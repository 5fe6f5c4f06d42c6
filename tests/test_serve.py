"""repro.serve contract: batch packing stays bit-exact with sequential
execution, admission control bounds the queue, priority jumps the
validated stream schedule's lane order, and the wct dispatch objective
plumbs through.  Compiles once (DSCNN x gap9, fused fidelity) and
shares the process-wide schedule cache with the other suites."""

import json
import threading
from functools import lru_cache
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro import obs
from repro.backend import lower
from repro.cnn import init_graph_params, mlperf_tiny_networks
from repro.core import (
    ComputeModel,
    CostBreakdown,
    ExecutionModule,
    Graph,
    MappedGraph,
    MappedSegment,
    MatchTarget,
    MemoryLevel,
    Node,
    ScheduleResult,
    TemporalMapping,
    dispatch,
)
from repro.pipeline import schedule_pipeline, schedule_stream
from repro.serve import (
    AdmissionQueue,
    BatchedModel,
    ModelServer,
    QueueFullError,
    ServeRequest,
)

BUDGET = 300  # shares the schedule cache with tests/test_backend.py
NET = "DSCNN"
TARGET = "gap9"


@lru_cache(maxsize=None)
def _compiled():
    g = mlperf_tiny_networks()[NET]
    mapped = dispatch(g, TARGET, budget=BUDGET)
    return lower(mapped, use_pallas=False, band_tiling=False)


@lru_cache(maxsize=None)
def _io():
    cm = _compiled()
    params = init_graph_params(cm.graph)
    rng = np.random.default_rng(7)
    reqs = tuple(
        {
            k: rng.integers(-128, 128, s).astype("float32")
            for k, s in cm.graph.inputs.items()
        }
        for _ in range(6)
    )
    return params, reqs


# ---------------------------------------------------------------------------
# Batch packing
# ---------------------------------------------------------------------------


def test_run_batch_bit_exact_with_sequential_run():
    cm = _compiled()
    params, reqs = _io()
    bm = BatchedModel(cm)
    rows = bm.run_batch(params, list(reqs[:4]))
    for i in range(4):
        ref = cm.run(params, reqs[i])
        assert set(rows[i]) == set(ref)
        for k in ref:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(rows[i][k]))


def _stub(shape=(2, 3)) -> BatchedModel:
    """A BatchedModel over one input ``x``: enough to stack rows."""
    return BatchedModel(SimpleNamespace(graph=SimpleNamespace(inputs={"x": shape})))


def _per_row(rows) -> "jax.Array":
    """The stack as it was made before host rows were stacked on the host:
    each row coerced to the device on its own, then stacked there."""
    import jax.numpy as jnp

    from repro.backend.runtime import as_input_array

    return jnp.stack([as_input_array(r) for r in rows])


def _assert_same(got, want) -> None:
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", ["int8", "uint8", "float32", "float64", "int64", "list"])
def test_host_rows_stack_once_as_the_per_row_path(dtype):
    rng = np.random.default_rng(3)
    rows = [rng.integers(-100, 100, (2, 3)) for _ in range(5)]
    rows = [r.tolist() for r in rows] if dtype == "list" else [r.astype(dtype) for r in rows]
    stacked, copies = _stub()._stack([{"x": r} for r in rows])
    _assert_same(stacked["x"], _per_row(rows))
    assert copies == 1
    # uncommitted on the default device, as jnp.asarray leaves an array
    assert not stacked["x"].committed and stacked["x"].devices() == {jax.devices()[0]}


def test_host_scalars_stack_once_as_the_per_row_path():
    rows = [np.float32(1.5), np.float32(-2.0), np.float32(3.25)]
    stacked, copies = _stub(())._stack([{"x": r} for r in rows])
    _assert_same(stacked["x"], _per_row(rows))
    assert copies == 1


@pytest.mark.parametrize("batch", ["one_device_row", "mixed_dtypes", "mixed_shapes"])
def test_other_batches_keep_the_per_row_path(batch):
    rng = np.random.default_rng(4)
    rows = [rng.integers(-100, 100, (2, 3)).astype("float32") for _ in range(4)]
    if batch == "one_device_row":
        rows[1] = jax.numpy.asarray(rows[1])
        copies = 3
    elif batch == "mixed_dtypes":
        rows[2] = rows[2].astype("int8")
        copies = 4
    else:
        rows = [rows[0].reshape(2, 3), rows[1].reshape(3, 2)]
        with pytest.raises(ValueError):
            _stub()._stack([{"x": r} for r in rows])
        with pytest.raises(ValueError):
            _per_row(rows)
        return
    stacked, n = _stub()._stack([{"x": r} for r in rows])
    _assert_same(stacked["x"], _per_row(rows))
    assert n == copies


def test_run_batch_bit_exact_with_a_device_row():
    cm = _compiled()
    params, reqs = _io()
    batch = list(reqs[:3])
    batch[1] = {k: jax.numpy.asarray(v) for k, v in batch[1].items()}
    rows = BatchedModel(cm).run_batch(params, batch)
    for i in range(3):
        ref = cm.run(params, reqs[i])
        for k in ref:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(rows[i][k]))


def test_one_aot_entry_per_batch_shape():
    cm = _compiled()
    params, reqs = _io()
    bm = BatchedModel(cm)
    bm.run_batch(params, list(reqs[:3]))
    bm.run_batch(params, list(reqs[3:6]))  # same shape: cache hit
    assert len(bm.entry_stats()) == 1
    bm.run_batch(params, list(reqs[:2]))  # new batch size: new entry
    stats = bm.entry_stats()
    assert sorted(row["batch"] for row in stats) == [2, 3]
    for row in stats:
        assert row["trace_us"] > 0.0 and row["compile_us"] > 0.0


# ---------------------------------------------------------------------------
# ModelServer end to end
# ---------------------------------------------------------------------------


def test_server_bit_exact_per_request_and_reports():
    cm = _compiled()
    params, reqs = _io()
    with ModelServer(
        cm, params, batch_slots=3, stream_depth=2, queue_capacity=16
    ) as srv:
        handles = [srv.submit(r, priority=float(i % 3)) for i, r in enumerate(reqs)]
        outs = [h.result(timeout=120) for h in handles]
    for i, out in enumerate(outs):
        ref = cm.run(params, reqs[i])
        for k in ref:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k]))
    # replica stats land in report_dict()["serve"]["engine"], JSON-safe
    d = json.loads(json.dumps(cm.report_dict(), sort_keys=True))
    eng = d["serve"]["engine"]
    assert eng["submitted"] == len(reqs)
    assert eng["completed"] == len(reqs)
    assert eng["rejected"] == 0
    assert eng["latency_us"]["count"] == len(reqs)
    assert eng["latency_us"]["p99"] >= eng["latency_us"]["p50"] > 0.0
    # PR 9: quantiles come from the rolling sketch (declared accuracy),
    # the drain result and shed count are first-class stats
    assert eng["latency_us"]["relative_accuracy"] == 0.01
    assert eng["drained"] is True
    assert eng["shed"] == 0
    assert eng["last_round"]["weighted_completion_cycles"] > 0.0
    cm.attrs.pop("serve")  # don't leak replica state into other suites


def test_failed_round_resolves_its_requests_and_serves_on(monkeypatch):
    """A round whose executable raises resolves every handle of that round
    with the exception, and the replica serves the next request."""
    cm = _compiled()
    params, reqs = _io()
    srv = _pinned_server(cm, params, batch_slots=2, stream_depth=1)
    real, failed = srv.batched.run_batch_async, []

    def first_call_raises(p, inputs_list):
        if not failed:
            failed.append(len(inputs_list))
            raise RuntimeError("executable failed")
        return real(p, inputs_list)

    monkeypatch.setattr(srv.batched, "run_batch_async", first_call_raises)
    lost = [srv.submit(r) for r in reqs[:2]]  # queued before the loop runs
    srv._thread = None
    with srv:
        for h in lost:
            with pytest.raises(RuntimeError, match="executable failed"):
                h.result(timeout=120)
        out = srv.submit(reqs[2]).result(timeout=120)
    assert failed == [2]  # both requests were in the failed round
    ref = cm.run(params, reqs[2])
    for k in ref:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k]))
    cm.attrs.pop("serve")


@pytest.mark.parametrize("bad", [{"batch_slots": 0}, {"stream_depth": 0}], ids=["batch_slots", "stream_depth"])
def test_server_rejects_bad_slots_and_depth(bad):
    cm = _compiled()
    params, _ = _io()
    with pytest.raises(ValueError, match=f"{next(iter(bad))} must be >= 1"):
        ModelServer(cm, params, **bad)


def test_priority_jumps_lane_order_in_a_round():
    cm = _compiled()
    params, reqs = _io()
    srv = ModelServer(cm, params, batch_slots=4, stream_depth=2)
    # pin the worker so this test, not the loop, drives the round
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    srv._thread = t
    handles = {}
    for i, pr in enumerate((1.0, 1.0, 5.0, 2.0)):
        handles[i] = srv.submit(reqs[i], priority=pr)
    batch = srv.queue.take(8, timeout=0)
    assert [r.rid for r in batch] == [2, 3, 0, 1]  # Smith order, FIFO ties
    srv._serve_round(batch)
    assert srv.stats()["last_round"]["rids"] == [2, 3, 0, 1]
    for i, h in handles.items():
        out = h.result(timeout=120)
        ref = cm.run(params, reqs[i])
        for k in ref:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k]))
    cm.attrs.pop("serve")


def _pinned_server(cm, params, **kw) -> ModelServer:
    """A server whose worker is a finished thread, so the test, not the
    loop, drives the rounds."""
    srv = ModelServer(cm, params, **kw)
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    srv._thread = t
    return srv


def _serve_one_round(srv, reqs, priorities) -> list[ServeRequest]:
    handles = [srv.submit(reqs[i], priority=p) for i, p in enumerate(priorities)]
    batch = srv.queue.take(8, timeout=0)
    srv._serve_round(batch)
    for h in handles:
        h.result(timeout=120)
    return batch


def test_rounds_build_no_schedule_and_last_round_builds_it(monkeypatch):
    import repro.pipeline.schedule as schedule

    cm = _compiled()
    params, reqs = _io()
    real, calls = schedule.schedule_stream, []

    def counting(*args, **kwargs):
        calls.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(schedule, "schedule_stream", counting)
    srv = _pinned_server(cm, params, batch_slots=2, stream_depth=2)
    _serve_one_round(srv, reqs, (1.0, 3.0, 2.0))
    batch = _serve_one_round(srv, reqs, (2.0, 1.0, 1.0, 4.0))
    assert calls == []
    assert "last_round" not in cm.attrs["serve"]  # the round's stamp builds nothing
    want = real(cm.mapped, [r.priority for r in batch], order="smith")
    want.validate()
    assert srv.stats()["last_round"] == {
        "requests": 4,
        "rids": [r.rid for r in batch],
        "weighted_completion_cycles": want.attrs["weighted_completion"],
        "makespan_cycles": want.makespan,
    }
    assert [r.rid for r in batch] == [6, 3, 4, 5]  # Smith order, FIFO ties
    assert len(calls) == 1
    cm.attrs.pop("serve")


def test_last_round_builds_once_a_round():
    cm = _compiled()
    params, reqs = _io()
    builds = obs.counter("serve.last_round_builds")
    srv = _pinned_server(cm, params, batch_slots=2, stream_depth=2)
    before = builds.value
    assert srv.stats()["last_round"] == {} and builds.value == before
    _serve_one_round(srv, reqs, (1.0, 2.0))
    first, second = srv.stats()["last_round"], srv.stats()["last_round"]
    assert builds.value - before == 1 and first == second
    _serve_one_round(srv, reqs, (1.0,))
    srv.stats()
    srv.close()
    assert builds.value - before == 2
    assert cm.attrs["serve"]["last_round"]["rids"] == [2]
    cm.attrs.pop("serve")


# ---------------------------------------------------------------------------
# Tracing: the serving round, the batching path and the AOT call
# ---------------------------------------------------------------------------


@pytest.fixture
def tracer():
    """The process tracer, put back as it was (on/off, save path,
    profiler spans) after the test."""
    tr = obs.get_tracer()
    was = tr.enabled, tr.path, tr.annotate
    yield tr
    tr.enabled, tr.path, tr.annotate = was


def _serve_all(cm, params, reqs):
    with ModelServer(cm, params, batch_slots=3, stream_depth=2) as srv:
        for h in [srv.submit(r) for r in reqs]:
            h.result(timeout=120)
    cm.attrs.pop("serve")


def test_disabled_tracing_records_no_event(tracer):
    cm = _compiled()
    params, reqs = _io()
    aot = cm.to_aot()
    obs.disable_tracing()
    before = len(tracer)
    _serve_all(cm, params, reqs)
    BatchedModel(cm).run_batch_async(params, list(reqs[:2]))
    aot.run(params, reqs[0])
    assert len(tracer) == before


def test_profiler_trace_holds_the_round_and_what_it_runs(tmp_path, tracer, host_events):
    """With profiler=True the program's spans land on the host plane of the
    profiler's trace, nested in the serving thread's round; in memory, a
    request's queue wait joins its round and its request span."""
    import jax

    cm = _compiled()
    params, reqs = _io()
    aot = cm.to_aot()
    obs.enable_tracing(profiler=True)
    before = len(tracer)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _serve_all(cm, params, reqs)
        aot.run(params, reqs[0])
    ev = host_events(tmp_path)
    assert ev.keys() >= {
        "match.serve.round", "match.serve.schedule", "match.batch.stack", "match.aot.dispatch",
    }
    rounds = ev["match.serve.round"]
    for name in ("match.serve.schedule", "match.batch.stack", "match.batch.dispatch", "match.serve.resolve"):
        for line, lo, hi in ev[name]:
            assert any(ln == line and a <= lo and hi <= b for ln, a, b in rounds), name

    events = list(tracer._events)[before:]
    round_ids = {e[6]["round"] for e in events if e[0] == "serve.round"}
    waits = [e[6] for e in events if e[0] == "serve.queue_wait"]
    served = {e[6]["rid"] for e in events if e[0].startswith("req")}
    assert len(waits) == len(reqs)
    assert {w["round"] for w in waits} <= round_ids and {w["rid"] for w in waits} == served


def test_batch_stack_counts_host_to_device_transfers(tracer):
    cm = _compiled()
    params, reqs = _io()
    bm = BatchedModel(cm)
    obs.enable_tracing()
    bm.stack(list(reqs[:4]))
    bm.stack([{k: jax.numpy.asarray(v) for k, v in r.items()} for r in reqs[:3]])
    stacks = [e[6] for e in list(tracer._events) if e[0] == "batch.stack"]
    assert stacks[-2:] == [{"rows": 4, "h2d": 4, "copies": 1}, {"rows": 3, "h2d": 0, "copies": 0}]


def test_whole_batch_names_each_segment():
    cm = _compiled()
    params, reqs = _io()
    bm = BatchedModel(cm)
    text = bm.entry(params, bm.stack(list(reqs[:2]))).as_text()
    assert f"seg0.{cm.segments[0].module}" in text


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


def _req(rid, priority=1.0, deadline_us=None):
    return ServeRequest(rid=rid, inputs={}, priority=priority, deadline_us=deadline_us)


def test_admission_rejects_past_the_bound():
    q = AdmissionQueue(capacity=2, policy="reject")
    q.put(_req(0))
    q.put(_req(1))
    with pytest.raises(QueueFullError):
        q.put(_req(2))
    assert q.depth == 2  # the shed request was not enqueued


def test_admission_block_policy_times_out():
    q = AdmissionQueue(capacity=1, policy="block")
    q.put(_req(0))
    with pytest.raises(QueueFullError):
        q.put(_req(1), timeout=0.05)
    # a take frees the slot and unblocks the producer
    assert [r.rid for r in q.take(1, timeout=0)] == [0]
    q.put(_req(2), timeout=0.05)
    assert q.depth == 1


def test_take_orders_by_priority_then_deadline_then_arrival():
    q = AdmissionQueue(capacity=8)
    q.put(_req(0, priority=1.0))
    q.put(_req(1, priority=3.0))
    q.put(_req(2, priority=3.0, deadline_us=50.0))
    q.put(_req(3, priority=1.0))
    got = [r.rid for r in q.take(8, timeout=0)]
    # weight-descending; EDF between equal weights; FIFO last
    assert got == [2, 1, 0, 3]


def test_server_rejects_when_queue_full():
    cm = _compiled()
    params, reqs = _io()
    srv = ModelServer(cm, params, batch_slots=1, queue_capacity=1)
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    srv._thread = t  # no worker: the queue cannot drain
    srv.submit(reqs[0])
    with pytest.raises(QueueFullError):
        srv.submit(reqs[1])
    assert srv.stats()["rejected"] == 1


# ---------------------------------------------------------------------------
# schedule_stream invariants (hand-built two-module diamond)
# ---------------------------------------------------------------------------


def _module(name):
    return ExecutionModule(
        name=name,
        memories=(MemoryLevel("L2", 1 << 20, 8.0),),
        spatial={},
        compute=ComputeModel(),
    )


def _seg(node, module, cycles):
    cost = CostBreakdown(True, cycles, cycles, 0.0, {}, {}, 1.0)
    sched = ScheduleResult("w", "m", TemporalMapping({}, ()), cost, 1)
    return MappedSegment((node,), module, sched, None, pattern="fallback")


def _diamond_mapped():
    geom = {"B": 1, "K": 1, "C": 1, "OY": 1, "OX": 1, "elem_bytes": 1}
    nodes = [
        Node("a", "conv2d", ("x",), dict(geom)),
        Node("b", "conv2d", ("a",), dict(geom)),
        Node("c", "conv2d", ("a",), dict(geom)),
        Node("d", "add", ("b", "c"), dict(geom)),
    ]
    g = Graph("diamond", nodes, {"x": (1, 1, 1, 1)}, ("d",))
    target = MatchTarget(name="toy", modules=[_module("acc")], fallback=_module("cpu"))
    segs = [
        _seg(g.node("a"), "cpu", 10.0),
        _seg(g.node("b"), "cpu", 6.0),
        _seg(g.node("c"), "acc", 4.0),
        _seg(g.node("d"), "cpu", 2.0),
    ]
    return MappedGraph(g, target, segs)


def test_stream_single_request_reproduces_pipeline_makespan():
    mg = _diamond_mapped()
    ss = schedule_stream(mg, (1.0,))
    ss.validate()
    assert ss.makespan == schedule_pipeline(mg).makespan == 18.0
    assert ss.attrs["weighted_completion"] == 18.0
    assert ss.attrs["request_order"] == [0]


def test_stream_smith_orders_by_weight_and_beats_fifo():
    mg = _diamond_mapped()
    ws = (1.0, 3.0, 1.0, 2.0)
    smith = schedule_stream(mg, ws, order="smith")
    fifo = schedule_stream(mg, ws, order="fifo")
    smith.validate()
    fifo.validate()
    assert smith.attrs["request_order"] == [1, 3, 0, 2]
    assert fifo.attrs["request_order"] == [0, 1, 2, 3]
    # same work, same lanes: makespan unaffected by order, but weighted
    # completion is what Smith's rule minimises
    assert smith.makespan == pytest.approx(fifo.makespan)
    assert (
        smith.attrs["weighted_completion"] <= fifo.attrs["weighted_completion"]
    )
    # the heaviest request completes first
    comp = smith.attrs["completion"]
    assert comp["1"] == min(comp.values())


def test_stream_happens_before_survives_priority_jump():
    mg = _diamond_mapped()
    ss = schedule_stream(mg, (1.0, 10.0))
    ss.validate()  # deps + per-module serialisation both hold
    # request 1 jumped ahead: every one of its segments finishes before
    # the corresponding segment of request 0
    fin = {e.name: e.finish for e in ss.entries}
    for nm in ("a", "b", "c", "d"):
        assert fin[f"{nm}@r1"] <= fin[f"{nm}@r0"]


def test_stream_rejects_bad_weights_and_order():
    mg = _diamond_mapped()
    with pytest.raises(ValueError, match="order"):
        schedule_stream(mg, (1.0,), order="lifo")
    with pytest.raises(ValueError, match="weight"):
        schedule_stream(mg, ())
    with pytest.raises(ValueError, match="weight"):
        schedule_stream(mg, (1.0, -2.0))


# ---------------------------------------------------------------------------
# dispatch objective plumbing
# ---------------------------------------------------------------------------


def test_dispatch_wct_objective_plumbs_through():
    from repro.targets import get_target

    geom = dict(B=1, K=8, C=8, OY=8, OX=8, FY=3, FX=3, stride=1, elem_bytes=1)
    nodes = [
        Node("a", "conv2d", ("x",), dict(geom)),
        Node("b", "conv2d", ("a",), dict(geom)),
        Node("c", "conv2d", ("a",), dict(geom)),
        Node("d", "add", ("b", "c"), dict(geom)),
    ]
    g = Graph("branchy_wct", nodes, {"x": (1, 8, 8, 8)}, ("d",))
    t = get_target("gap9")
    by_wct = dispatch(g, t, budget=200, objective="wct")
    assert by_wct.attrs["objective"] == "wct"
    k = by_wct.attrs["wct_stream_depth"]
    wct = by_wct.attrs["predicted_weighted_completion"]
    assert k >= 1 and wct > 0.0
    # the reranker's number is reproducible from the mapping it chose
    ss = schedule_stream(by_wct, (1.0,) * k)
    assert ss.attrs["weighted_completion"] == pytest.approx(wct)
    # never worse than the cycles objective under the same metric
    by_cycles = dispatch(g, t, budget=200)
    wct_cycles = schedule_stream(by_cycles, (1.0,) * k).attrs["weighted_completion"]
    assert wct <= wct_cycles + 1e-6
