"""The reader of ``h2d_copies_per_inf.offline``: the host→device copies the
``batch.stack`` spans made, over their rows, from synthetic spans."""

import json

import pytest

from benchmarks.chip import run

from test_bench_program import METRICS, _ctx, _record, reader, tracer  # noqa: F401

NAME = "h2d_copies_per_inf.offline"
CELLS = ["mobilenetv1_025_vww.offline_b256", "resnet8_cifar10.offline_b256"]


def test_the_metric_is_declared_for_the_offline_cells():
    spec = json.loads(run.SPEC.read_text())
    for cell in CELLS:
        _, per_layer = run.cell_metrics(spec, cell)
        assert NAME in {m["name"] for m in per_layer}
    assert (METRICS / f"{NAME}.py").is_file()


def test_copies_over_rows_inside_the_window(tracer):  # noqa: F811
    read = reader(NAME).read  # turns tracing on
    ctx, lo = _ctx(tracer, shift=-3.5)
    _record(tracer, lo, "batch.stack", -1.0, 0.5, rows=256, h2d=256, copies=256)  # before the window
    _record(tracer, lo, "batch.stack", 1.0, 0.01, rows=256, h2d=256, copies=1)
    _record(tracer, lo, "batch.stack", 2.0, 0.01, rows=256, h2d=256, copies=1)
    _record(tracer, lo, "batch.stack", 3.0, 0.2, rows=128, h2d=0, copies=0)
    assert read(ctx) == pytest.approx(2 / 640)


def test_spans_without_the_count_read_nothing(tracer):  # noqa: F811
    """A program that records ``h2d`` but not ``copies`` gives no value,
    and no error."""
    read = reader(NAME).read
    ctx, lo = _ctx(tracer, shift=0.0)
    assert read(ctx) is None  # no span in the window
    _record(tracer, lo, "batch.stack", 1.0, 0.2, rows=256, h2d=256)
    assert read(ctx) is None
