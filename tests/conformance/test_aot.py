"""The whole-graph AOT executable contract (PR 6).

Parametrized over ``list_targets()`` x the four MLPerf-Tiny networks:
``compile_aot(lower(dispatch(g, t), t))`` must be bit-exact against BOTH
the per-segment ``CompiledModel.run`` loop and the ``repro.cnn``
interpreter on every pair, and the ``report_dict()["aot"]`` payload must
JSON round-trip.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cnn import execute_graph

from .harness import NETS, TARGETS, aot_for, compiled_for, graph_for, io_for

pytestmark = pytest.mark.parametrize("tname", TARGETS)

# one net keeps the single-target checks cheap; payloads are net-independent
NET = "DSCNN"


@pytest.mark.parametrize("net", NETS)
def test_aot_bit_exact_with_per_segment_and_interpreter(net, tname):
    am = aot_for(net, tname)
    params, x = io_for(net)
    # vs the per-segment CompiledModel.run loop (same fused bodies, inlined)
    assert am.verify(params, x) == 0.0
    # vs the interpreter, directly — not just transitively through the
    # per-segment path's own golden check
    ref = execute_graph(graph_for(net), params, x)
    got = am.run(params, x)
    assert set(got) == set(ref)
    for k in ref:
        assert float(jnp.max(jnp.abs(ref[k] - got[k]))) == 0.0


def test_aot_report_dict_json_roundtrip(tname):
    cm = compiled_for(NET, tname)
    am = aot_for(NET, tname)
    params, x = io_for(NET)
    am.warmup(params, x)
    am.measure_dispatch_overhead(params, x, repeats=3)
    d = cm.report_dict()
    back = json.loads(json.dumps(d, sort_keys=True))
    aot = back["aot"]
    assert aot["segments"] == len(cm.segments)
    assert aot["entries"], "warmup must have registered an executable"
    for e in aot["entries"]:
        assert e["trace_us"] > 0.0 and e["compile_us"] > 0.0
    assert 0.0 <= aot["intermediate_share"] <= 1.0
    assert aot["dispatch_overhead"]["segments"] == len(cm.segments)


def test_aot_preserves_integer_input_dtypes(tname):
    """Quantized feeds stay quantized: an int8 input reaches the AOT
    executable as int8 (signature records it, the output carries it),
    not silently widened to float32 — on an int8-capable graph (relu
    chain; the conv nets declare float32 weights, so int8 activations
    cannot flow through them on any path)."""
    from repro.backend import lower
    from repro.core import Graph, Node, dispatch

    nodes, prev = [], "x"
    for i in range(3):
        nodes.append(
            Node(f"r{i}", "relu", (prev,), {"B": 1, "C": 8, "OY": 1, "OX": 1, "elem_bytes": 1})
        )
        prev = f"r{i}"
    g = Graph("int8_chain", nodes, {"x": (1, 8)}, (prev,))
    cm = lower(dispatch(g, tname))
    am = cm.to_aot()
    xi = {"x": np.arange(-4, 4, dtype=np.int8).reshape(1, 8)}
    entry = am.warmup({}, xi)
    sig_dtypes = {name: dt for name, _, dt in entry.signature}
    assert sig_dtypes == {"x": "int8"}
    out = am.run({}, xi)
    ref = cm.run({}, xi)
    for k in ref:
        assert got_dtype(out[k]) == got_dtype(ref[k]) == "int8"
        assert float(jnp.max(jnp.abs(ref[k] - out[k]))) == 0.0


def got_dtype(v) -> str:
    return str(np.asarray(v).dtype)
