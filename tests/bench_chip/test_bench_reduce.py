"""The trace reduction, on a trace recorded on a TPU v5 lite: a 0.35 s
traced window of ``mobilenetv1_025_vww.offline_b256`` (two batches), pruned
to the device's ``XLA Ops`` / ``XLA Modules`` lines and the benchmark's own
host spans."""

from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import reduce, run, work

FIXTURE = Path(__file__).resolve().parents[2] / "benchmarks" / "chip" / "fixtures" / "offline_mobilenet.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace():
    return reduce.reduce(reduce.load(FIXTURE), chips=1)


def test_busy_and_idle_add_up(trace):
    assert trace["window_s"] == pytest.approx(0.354250503)
    assert trace["busy_s"] == pytest.approx(0.002632985)
    idle = dict(trace["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(trace["window_s"] - trace["busy_s"])
    assert max(idle, key=idle.get) == "stack"  # the host was stacking inputs


def test_top_ops_and_executables(trace):
    ops = trace["top_ops"]
    assert len(ops) == reduce.TOP and [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert ops[0][0] == "jit_concatenate/concatenate.1"
    assert {k: len(v) for k, v in trace["modules"].items()} == {"jit_broadcast_in_dim": 512, "jit_concatenate": 34, "jit_whole_batch": 2}


def test_roofline_reader_stays_under_100(trace):
    cfg = run.load_named(run.HERE, "configs", "mobilenetv1_025_vww")
    traffic = run.load_named(run.HERE, "traffic", "offline_b256")
    ctx = {"trace": trace, "work": work.work(cfg), "peak": work.peak_for("TPU v5 lite"), "traffic": traffic}
    value = run.load_module(run.HERE / "metrics" / "whole_batch_roofline.py").read(ctx)
    assert 0 < value < 100


def test_merge_and_overlap():
    iv = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0], [2.5, 2.7]])
    assert reduce.merge(iv).tolist() == [[0.0, 4.0], [5.0, 6.0]]
    assert reduce.overlap(reduce.merge(iv), 1.0, 5.5) == pytest.approx(3.5)


def test_no_window_no_reading():
    assert reduce.reduce({"devices": {}, "spans": {}}, chips=1) is None
