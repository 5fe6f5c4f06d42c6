"""repro.backend: lowering, static memory planning, compiled runtime.

Acceptance (ISSUE 2): lower(dispatch(g, target), target).run(params, x)
is bit-exact with execute_graph(g, params, x) on all four MLPerf-Tiny
graphs for both make_gap9_target() and make_diana_target(), and MemoryPlan
arena bytes per level never exceed the declared MemoryLevel capacities.
"""

import dataclasses
from functools import lru_cache

import numpy as np
import pytest

from repro.backend import CompiledModel, LoweringError, MemoryPlanError, lower, plan_memory
from repro.cnn import conv_block_graph, init_graph_params, mlperf_tiny_networks
from repro.core import MappedGraph, TemporalMapping, dispatch
from repro.kernels import matmul_requant, tiled_conv2d
from repro.kernels.ref import matmul_requant_ref
from repro.targets import make_diana_target, make_gap9_target

NETS = ["MobileNet", "ResNet", "DSCNN", "DAE"]
TARGETS = {"gap9": make_gap9_target, "diana": make_diana_target}


@lru_cache(maxsize=None)
def _compiled(net: str, tgt: str) -> CompiledModel:
    g = mlperf_tiny_networks()[net]
    mapped = dispatch(g, TARGETS[tgt](), budget=300)
    return lower(mapped)


def _io(g):
    params = init_graph_params(g)
    x = {
        k: np.random.default_rng(0).integers(-128, 128, s).astype("float32")
        for k, s in g.inputs.items()
    }
    return params, x


# ---------------------------------------------------------------------------
# Acceptance: bit-exact vs the interpreter, plans within capacities
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tgt", list(TARGETS))
@pytest.mark.parametrize("net", NETS)
def test_compiled_bit_exact(net, tgt):
    cm = _compiled(net, tgt)
    params, x = _io(cm.graph)
    assert cm.verify(params, x) == 0.0


@pytest.mark.parametrize("tgt", list(TARGETS))
@pytest.mark.parametrize("net", NETS)
def test_memory_plan_within_capacities(net, tgt):
    plan = _compiled(net, tgt).memory_plan
    for lvl, used in plan.arena_bytes.items():
        assert used <= plan.capacities[lvl], (lvl, used, plan.capacities[lvl])
    plan.validate()  # must not raise
    assert plan.check_no_overlap()


def test_every_segment_lowered_and_outputs_reachable():
    cm = _compiled("ResNet", "gap9")
    assert cm.fused_node_count() == len(cm.graph.nodes)
    produced = {ls.output_name for ls in cm.segments}
    assert set(cm.graph.outputs) <= produced
    # conv anchors took the tiled kernel route, the dense head the GEMM one
    routes = cm.routes()
    assert routes.get("tiled_conv", 0) >= 8
    assert routes.get("pallas_gemm", 0) >= 1


def test_timed_run_and_report():
    cm = _compiled("DSCNN", "gap9")
    params, x = _io(cm.graph)
    out = cm.run(params, x, timed=True)
    assert set(out) == set(cm.graph.outputs)
    assert len(cm.last_timings) == len(cm.segments)
    assert all(t.measured_us >= 0.0 for t in cm.last_timings)
    rep = cm.report()
    assert "MemoryPlan" in rep and "predicted total" in rep and "meas us" in rep


# ---------------------------------------------------------------------------
# Memory planner mechanics
# ---------------------------------------------------------------------------


def test_plan_liveness_spans_consumers():
    cm = _compiled("ResNet", "gap9")
    plan = cm.memory_plan
    g = cm.graph
    for i, ls in enumerate(cm.segments):
        for src in ls.input_names:
            buf = plan.buffers[src]
            assert buf.start <= i < buf.end, (src, buf, i)
    # graph outputs stay live past the last segment
    for o in g.outputs:
        assert plan.buffers[o].end > len(cm.segments)


def test_plan_spill_and_error_paths():
    g = conv_block_graph(IX=32, IY=32, C=64, K=64)
    mapped = dispatch(g, make_gap9_target(), budget=300)
    seg = next(s for s in mapped.segments if s.workload is not None)
    # inflate the winning schedule to a whole-array-resident mapping that
    # cannot fit the 128 kB L1 (the constraint LOMA priced)
    full = dict(seg.workload.dim_sizes)
    bad_sched = dataclasses.replace(
        seg.schedule, mapping=TemporalMapping(full, seg.schedule.mapping.outer_order)
    )
    bad_seg = dataclasses.replace(seg, schedule=bad_sched)
    segments = [bad_seg if s is seg else s for s in mapped.segments]
    broken = MappedGraph(mapped.graph, mapped.target, segments)

    plan = plan_memory(broken)  # spills by default
    assert seg.anchor.name in plan.spills
    plan.validate()  # spilled segment excluded from L1 peaks: still fits
    with pytest.raises(MemoryPlanError):
        plan_memory(broken, allow_spill=False)


def test_lower_rejects_mismatched_target():
    cm_target = make_diana_target()
    g = conv_block_graph(IX=8, IY=8, C=8, K=8)
    mapped = dispatch(g, make_gap9_target(), budget=300)
    with pytest.raises(LoweringError):
        lower(mapped, cm_target)


# ---------------------------------------------------------------------------
# Interpreter op semantics the backend shares (un-folded requant chains)
# ---------------------------------------------------------------------------


def test_unfolded_requant_chain_ops_compute():
    """mul/div/rshift/clip execute real arithmetic (not passthrough), so
    non-integerized graphs produce correct goldens pre-fold."""
    from repro.cnn import execute_graph
    from repro.core import Graph, Node

    nodes = [
        Node("m", "mul", ("x",), {"scale": 3.0}),
        Node("d", "div", ("m",), {"divisor": 4.0}),
        Node("s", "rshift", ("d",), {"shift": 1.0}),
        Node("c", "clip", ("s",), {"clip_min": -8, "clip_max": 8}),
    ]
    g = Graph("chain", nodes, {"x": (4,)}, ("c",))
    x = np.array([40.0, -40.0, 4.0, 2.0], "float32")
    out = np.asarray(execute_graph(g, {}, {"x": x})["c"])
    # x*3 -> /4 -> floor(/2) -> clip[-8, 8]
    want = np.clip(np.floor((x * 3.0 / 4.0) / 2.0), -8, 8)
    assert np.array_equal(out, want)
    # params override attrs (the constants live with the weights)
    out2 = np.asarray(execute_graph(g, {"m": {"scale": np.float32(1.0)}}, {"x": x})["c"])
    want2 = np.clip(np.floor((x / 4.0) / 2.0), -8, 8)
    assert np.array_equal(out2, want2)


def test_fold_requant_div_carries_chain_constants():
    """Folding a mul-add-shift chain keeps the affine constants, so the
    folded requant computes the same transform (round-half-even)."""
    from repro.cnn import execute_graph
    from repro.core import Graph, Node
    from repro.core.graph import fold_requant_div

    nodes = [
        Node("m", "mul", ("x",), {"scale": 3.0}),
        Node("a", "add", ("m",), {"addend": 4.0}),
        Node("s", "rshift", ("a",), {"shift": 2.0}),
    ]
    g = Graph("chain", nodes, {"x": (3,)}, ("s",))
    folded = fold_requant_div(g)
    assert [n.op for n in folded.nodes] == ["requant"]
    x = np.array([10.0, -9.0, 100.0], "float32")
    got = np.asarray(execute_graph(folded, {}, {"x": x})["s"])
    want = np.clip(np.asarray(jnp_round((x * 3.0 + 4.0) / 4.0)), -128, 127)
    assert np.array_equal(got, want)

    # a div by a non-power-of-two cannot become a shift: chain kept
    nodes2 = [
        Node("m", "mul", ("x",), {"scale": 3.0}),
        Node("a", "add", ("m",), {"addend": 4.0}),
        Node("d", "div", ("a",), {"divisor": 3.0}),
    ]
    g2 = Graph("chain2", nodes2, {"x": (3,)}, ("d",))
    assert [n.op for n in fold_requant_div(g2).nodes] == ["mul", "add", "div"]

    # init_graph_params must honor the carried shift, not clobber it with 5
    from repro.cnn import init_graph_params

    nodes3 = [
        Node("m", "mul", ("x",), {"scale": 1.0}),
        Node("a", "add", ("m",), {"addend": 0.0}),
        Node("d", "div", ("a",), {"divisor": 8.0}),
    ]
    g3 = fold_requant_div(Graph("chain3", nodes3, {"x": (3,)}, ("d",)))
    assert [n.op for n in g3.nodes] == ["requant"]
    params = init_graph_params(g3)
    got3 = np.asarray(execute_graph(g3, params, {"x": x})["d"])
    want3 = np.clip(np.asarray(jnp_round(x / 8.0)), -128, 127)
    assert np.array_equal(got3, want3)


def jnp_round(v):
    import jax.numpy as jnp

    return jnp.round(jnp.asarray(v, jnp.float32))


# ---------------------------------------------------------------------------
# Kernel-level checks backing the lowering routes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_oy", [1, 3, 5, 25])
def test_tiled_conv_banding_matches_whole_conv(block_oy):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    x = rng.integers(-128, 128, (1, 49, 10, 1)).astype("float32")
    w = rng.integers(-4, 5, (10, 4, 1, 16)).astype("float32")  # DSCNN 4x10
    whole = jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w), (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    banded = tiled_conv2d(x, w, stride=2, block_oy=block_oy)
    assert np.array_equal(np.asarray(whole), np.asarray(banded))


def test_matmul_requant_round_even_matches_interpreter_requant():
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    a = rng.integers(-128, 128, (4, 64)).astype(np.int8)
    w = rng.integers(-4, 5, (64, 32)).astype(np.int8)
    bias = rng.integers(-16, 17, 32).astype(np.int32)
    mult = np.ones(32, np.int32)
    got = matmul_requant(a, w, mult, bias, shift=5, rounding="even")
    # the interpreter's requant: round(x / 2^S) half-to-even, then clip
    acc = a.astype(np.float32) @ w.astype(np.float32) + bias.astype(np.float32)
    want = np.clip(np.asarray(jnp.round(acc / 32.0)), -128, 127).astype(np.int8)
    assert np.array_equal(np.asarray(got), want)
    # floor mode stays the HW arithmetic-shift oracle
    got_floor = matmul_requant(a, w, mult, bias, shift=5, rounding="floor")
    want_floor = matmul_requant_ref(a, w, mult, bias, shift=5)
    assert np.array_equal(np.asarray(got_floor), np.asarray(want_floor))
