"""The harness end to end on the CPU: refusal without a chip, lookup by
name, and whole runs of each driver with the device gate stubbed."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.chip import run

REPO = Path(__file__).resolve().parents[2]


def test_refuses_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "resnet8_cifar10.single_stream", "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_fails_with_only_the_benchmark_files(tmp_path):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"} | {"JAX_PLATFORMS": "cpu"}
    argv = spec["command"] + ["--workload", "resnet8_cifar10.single_stream", "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run([sys.executable if a == "python3" else a for a in argv], cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_files_are_found_by_name(small_root, run_cell, monkeypatch):
    (small_root / "configs" / "resnet8_copy.json").write_text((small_root / "configs" / "resnet8_cifar10.json").read_text())
    traffic = json.loads((small_root / "traffic" / "single_stream.json").read_text())
    (small_root / "traffic" / "single_stream_short.json").write_text(json.dumps(traffic | {"check_rows": 4}))
    for name in ("queries_per_s", "queries_per_s.single_stream"):
        (small_root / "metrics" / f"{name}.py").write_text(
            "def read(ctx):\n    t0, t1 = ctx['run']['window']\n    return ctx['run']['completed'] / (t1 - t0)\n"
        )
    spec = json.loads((small_root / "BENCHMARK.json").read_text())
    cell = "resnet8_copy.single_stream_short"
    spec["configs"].append(dict(spec["configs"][1], name="resnet8_copy", file="benchmarks/chip/configs/resnet8_copy.json"))
    spec["workloads"].append({"name": cell, "config": "resnet8_copy", "traffic": "single_stream_short", "chips": 1, "why": "a test"})
    spec["end_to_end"].append({"name": "queries_per_s", "unit": "1/s", "better": "higher", "bound": 0.1, "source": "host_clock", "workloads": [cell]})
    spec["per_layer"].append({"name": "queries_per_s.single_stream", "unit": "1/s", "better": "higher", "source": "host_clock",
                              "layer": "AOT runtime: repro.backend.aot", "moves": "queries_per_s", "workloads": [cell]})
    (small_root / "BENCHMARK.json").write_text(json.dumps(spec))
    e2e, per_layer = run.cell_metrics(spec, cell)
    assert [m["name"] for m in e2e] == ["setup_s", "queries_per_s"]
    assert [m["name"] for m in per_layer] == ["queries_per_s.single_stream"]
    line = run_cell(small_root, cell)
    assert line["correct"] and set(line["metrics"]) == {"setup_s", "queries_per_s"}
    assert line["metrics"]["queries_per_s"]["value"] > 0
    # a traced run reports the per-layer metric (the CPU trace has no device
    # plane, so the reduction is stood in for)
    from benchmarks.chip import reduce

    stand_in = {"window_s": 1.0, "busy_s": 0.5, "spans": {}, "idle_by_span": [], "top_ops": [], "modules": {}}
    monkeypatch.setattr(reduce, "reduce_dir", lambda trace_dir, chips: stand_in)
    line = run_cell(small_root, cell, trace=1)
    assert set(line["metrics"]) == {"queries_per_s.single_stream"} and line["device"]["busy_s"] == 0.5


@pytest.mark.parametrize(
    "cell, metric",
    [
        ("resnet8_cifar10.offline_b256", "throughput_ips"),
        ("resnet8_cifar10.single_stream", "latency_p90_us"),
        ("mobilenetv1_025_vww.server_poisson", "server_p90_ms"),
        ("dscnn_kws.single_stream", "latency_p90_us"),
    ],
)
def test_whole_run_is_correct(small_root, run_cell, capsys, cell, metric):
    line = run_cell(small_root, cell)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", metric}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks" and line["checks"]["max_abs_err"] == {"value": 0.0, "limit": 0.0}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 0}
