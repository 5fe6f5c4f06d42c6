"""The yardstick's work counter and peak table."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import work

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"


@pytest.mark.parametrize(
    "name, macs, weight_bytes",
    [("mobilenetv1_025_vww", 7_489_664, 219_064), ("resnet8_cifar10", 12_501_632, 78_744), ("dscnn_kws", 2_656_768, 24_368)],
)
def test_work_counts(name, macs, weight_bytes):
    w = work.work(json.loads((BENCH / "configs" / f"{name}.json").read_text()))
    assert (w["macs"], w["weight_bytes"]) == (macs, weight_bytes)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks"):
        work.peak_for("TPU v99")


def test_least_time_takes_the_larger_bound():
    peak = work.peak_for("TPU v5 lite")
    w = {"macs": 1000, "weight_bytes": 10**9, "input_bytes": 1, "output_bytes": 1}
    assert work.least_time_s(w, peak, 1) == pytest.approx((10**9 + 2) / peak["hbm_bytes_per_s"])
    w = {"macs": 10**12, "weight_bytes": 1, "input_bytes": 1, "output_bytes": 1}
    assert work.least_time_s(w, peak, 2) == pytest.approx(4e12 / peak["int8_ops_per_s"])
