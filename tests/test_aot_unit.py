"""Unit tests for the whole-graph AOT executor (repro.backend.aot),
input dtype preservation in CompiledModel.run, warm-before-sample timed
runs and the memory plan's aliasing summary.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import compile_aot, lower
from repro.core import Graph, Node, dispatch


# ---------------------------------------------------------------------------
# Fixtures: a small dispatched graph (reference route, cheap to compile)
# ---------------------------------------------------------------------------


def relu_chain(n=4, width=16, name="unit_chain"):
    nodes, prev = [], "x"
    for i in range(n):
        nodes.append(
            Node(
                f"r{i}",
                "relu",
                (prev,),
                {"B": 1, "C": width, "OY": 1, "OX": 1, "elem_bytes": 1},
            )
        )
        prev = f"r{i}"
    return Graph(name, nodes, {"x": (1, width)}, (prev,))


@pytest.fixture(scope="module")
def compiled():
    return lower(dispatch(relu_chain(), "gap9"))


@pytest.fixture(scope="module")
def io():
    x = np.random.default_rng(0).normal(size=(1, 16)).astype("float32")
    return {}, {"x": x}


# ---------------------------------------------------------------------------
# Satellite: CompiledModel.run preserves integer/quantized input dtypes
# ---------------------------------------------------------------------------


class _DtypeRecorder:
    """Stub executor that records the dtype of the activation it saw."""

    def __init__(self):
        self.seen = []

    def __call__(self, seg_params, x):
        self.seen.append(str(x.dtype))
        return x


def test_run_preserves_int8_inputs(compiled):
    """int8 feeds must reach segment executors as int8 — the old
    ``jnp.asarray(v, jnp.float32)`` coercion silently widened them."""
    rec = _DtypeRecorder()
    orig = [ls.fn for ls in compiled.segments]
    try:
        compiled.segments[0].fn = rec
        xi = {"x": np.arange(-8, 8, dtype=np.int8).reshape(1, 16)}
        compiled.run({}, xi)
        assert rec.seen == ["int8"]
    finally:
        for ls, fn in zip(compiled.segments, orig):
            ls.fn = fn


def test_run_int8_end_to_end_stays_int8(compiled):
    xi = {"x": np.arange(-8, 8, dtype=np.int8).reshape(1, 16)}
    out = compiled.run({}, xi)
    (y,) = out.values()
    assert str(np.asarray(y).dtype) == "int8"
    np.testing.assert_array_equal(np.asarray(y), np.maximum(xi["x"], 0))


def test_run_float_inputs_unchanged(compiled, io):
    """Float paths are untouched: list/scalar inputs still default to
    float32, float arrays keep their dtype."""
    params, x = io
    out_arr = compiled.run(params, x)
    out_list = compiled.run(params, {"x": x["x"].tolist()})
    (a,), (b,) = out_arr.values(), out_list.values()
    assert a.dtype == b.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Satellite: timed=True warms each segment before sampling
# ---------------------------------------------------------------------------


def test_timed_run_excludes_cold_first_call(compiled, io):
    """A segment whose first call is pathologically slow (stand-in for
    jit trace+compile) must not leak that cost into measured_us."""
    params, x = io

    class ColdStart:
        def __init__(self, inner):
            self.inner = inner
            self.calls = 0

        def __call__(self, seg_params, *xs):
            self.calls += 1
            if self.calls == 1:
                time.sleep(0.25)
            return self.inner(seg_params, *xs)

    orig = [ls.fn for ls in compiled.segments]
    try:
        cold = ColdStart(compiled.segments[0].fn)
        compiled.segments[0].fn = cold
        compiled.run(params, x, timed=True)
        assert cold.calls == 2  # warm call + sampled call
        row = compiled.last_timings[0]
        assert row.measured_us < 0.25e6 / 2, (
            f"cold-start cost leaked into the sample: {row.measured_us}us"
        )
    finally:
        for ls, fn in zip(compiled.segments, orig):
            ls.fn = fn


# ---------------------------------------------------------------------------
# AotModel basics
# ---------------------------------------------------------------------------


def test_aot_bit_exact_and_cached(compiled, io):
    params, x = io
    am = compile_aot(compiled)
    assert am.verify(params, x) == 0.0
    e1 = am.warmup(params, x)
    e2 = am.warmup(params, x)
    assert e1 is e2  # same (params, signature) -> held executable reused
    assert e1.trace_us > 0.0 and e1.compile_us > 0.0
    # a different input signature compiles a second executable
    xi = {"x": x["x"].astype(np.int8)}
    e3 = am.warmup(params, xi)
    assert e3 is not e1


def test_to_aot_caches_and_feeds_report_dict(compiled, io):
    params, x = io
    am = compiled.to_aot()
    assert compiled.to_aot() is am
    am.warmup(params, x)
    d = compiled.report_dict()
    assert d["aot"]["segments"] == len(compiled.segments)
    assert d["aot"]["plan_aliasing"] == compiled.memory_plan.aliasing_summary()
    assert 0.0 <= d["aot"]["intermediate_share"] <= 1.0


def test_aliasing_summary_consistent(compiled):
    plan = compiled.memory_plan
    s = plan.aliasing_summary()
    assert s["sum_buffer_bytes"] >= s["arena_peak_bytes"] > 0
    assert s["arena_peak_bytes"] == plan.arena_bytes[plan.home_level]
    for b in plan.buffers.values():  # every buffer inside the arena
        assert b.offset >= 0 and b.nbytes > 0
        assert b.offset + b.nbytes <= s["arena_peak_bytes"]
    assert s["bytes_saved_by_aliasing"] == s["sum_buffer_bytes"] - s["arena_peak_bytes"]
    assert s["aliased_pairs"] >= 0
