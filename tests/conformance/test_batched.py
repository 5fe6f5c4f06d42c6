"""The packed-batch executable contract.

Parametrized over ``list_targets()`` x the four MLPerf-Tiny networks:
``BatchedModel(cm).run_batch`` — the one executable the offline and server
paths run — must give, row for row, exactly what ``CompiledModel.run``
gives for each request alone, and keep integer inputs integer.
"""

import numpy as np
import pytest

from repro.serve import BatchedModel

from .harness import NETS, TARGETS, compiled_for, io_for

pytestmark = pytest.mark.parametrize("tname", TARGETS)


def _distinct_rows(graph, n: int = 3) -> list[dict]:
    rng = np.random.default_rng(11)
    return [
        {k: rng.integers(-128, 128, s).astype("float32") for k, s in graph.inputs.items()}
        for _ in range(n)
    ]


@pytest.mark.parametrize("net", NETS)
def test_batched_run_bit_exact(net, tname):
    cm = compiled_for(net, tname)
    params, _ = io_for(net)
    rows = _distinct_rows(cm.graph)
    got = BatchedModel(cm).run_batch(params, rows)
    assert len(got) == len(rows)
    for x, out in zip(rows, got):
        ref = cm.run(params, x)
        assert set(out) == set(ref)
        for k in ref:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k]))


def test_batched_preserves_integer_input_dtypes(tname):
    """The batched mirror of ``test_aot_preserves_integer_input_dtypes``:
    int8 rows reach the batch executable as int8 and come back int8, on
    the same int8 relu chain."""
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
    rows = [{"x": np.arange(-4, 4, dtype=np.int8).reshape(1, 8) * s} for s in (1, -1, 3)]
    bm = BatchedModel(cm)
    got = bm.run_batch({}, rows)
    (entry,) = bm.entry_stats()
    assert entry["signature"] == [["x", "(3, 1, 8)", "int8"]]
    for x, out in zip(rows, got):
        ref = cm.run({}, x)
        for k in ref:
            assert str(np.asarray(out[k]).dtype) == str(np.asarray(ref[k]).dtype) == "int8"
            assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k]))
