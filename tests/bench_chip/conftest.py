"""Helpers for the chip benchmark's tests, which run on the CPU.

``small_root`` is a copy of the benchmark's data and code directories with
traffic mixes cut to a size a test run holds; ``run_cell`` drives a whole
run of the harness from it with the device gate stubbed to name a TPU.
"""

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

BENCH = REPO / "benchmarks" / "chip"
# every answer is compared (check_rows above any window's count), so a
# fault in one answer is always seen
SMALL = {
    "offline_b256": {"batch": 8, "pool": 32, "check_rows": 10**6},
    "single_stream": {"pool": 16, "check_rows": 10**6},
    "server_poisson": {"rate_rps": 40, "batch_slots": 4, "pool": 16, "check_rows": 10**6},
}


@pytest.fixture
def small_root(tmp_path: Path) -> Path:
    for d in ("configs", "drivers", "families", "metrics", "traffic"):
        shutil.copytree(BENCH / d, tmp_path / d)
    for name, cut in SMALL.items():
        path = tmp_path / "traffic" / f"{name}.json"
        path.write_text(json.dumps(json.loads(path.read_text()) | cut))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


@pytest.fixture
def run_cell(monkeypatch, capsys):
    from benchmarks.chip import run

    monkeypatch.setattr(run, "device_gate", lambda chips: {"platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    # the persistent compile cache is process-wide state: keep tests off it
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")

    def go(root: Path, cell: str, seed: int = 2**31 + 7, seconds: float = 1.0, trace: int = 0) -> dict:
        argv = ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
        assert run.main(argv, root=root, spec_path=root / "BENCHMARK.json") == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go
