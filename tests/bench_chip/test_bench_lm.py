"""The LM family (``families/lm.py``), its driver and readers: a whole run
of a granite-4.0-h-small-shaped configuration at a tiny width through
``run.main`` on the CPU; ``work`` against hand counts at the cell's
configuration; the check against the reference; the reference's copy."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.chip import run

LM_CONFIG = run.HERE / "configs" / "granite_4_0_h_small.json"
TINY = {
    "hidden_size": 64, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "attention_multiplier": 0.0625,
    "intermediate_size": 32, "shared_intermediate_size": 48, "vocab_size": 512,
    "experts_routed": 8, "num_local_experts": 2, "expert_offset": 2, "num_experts_per_tok": 2,
}
TRAFFIC = {"driver": "lm_decode", "batch": 4, "prompt_len": 12, "max_len": 2048, "prefill_rows": 3,
           "check_seqs": 2, "check_rows": 8}


def _family():
    return run.load_family(run.HERE, json.loads(LM_CONFIG.read_text()))


def _tiny(**over) -> dict:
    return json.loads(LM_CONFIG.read_text()) | TINY | {"name": "granite_tiny"} | over


def _add_tiny(root: Path, **over) -> str:
    (root / "configs" / "granite_tiny.json").write_text(json.dumps(_tiny(**over)))
    (root / "traffic" / "decode_tiny.json").write_text(json.dumps(TRAFFIC))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = "granite_tiny.decode_tiny"
    spec["configs"].append({"name": "granite_tiny", "source": "a test", "file": "benchmarks/chip/configs/granite_tiny.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": cell, "config": "granite_tiny", "traffic": "decode_tiny", "chips": 1, "why": "a test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "granite_4_0_h_small.decode_b64" in m.get("workloads", []):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def test_a_granite_shaped_lm_runs_end_to_end(small_root, run_cell):
    cell = _add_tiny(small_root)
    line = run_cell(small_root, cell, seconds=1.0)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "throughput_ips"}
    assert line["metrics"]["throughput_ips"]["value"] > 0
    assert line["attempted"] >= 4 * 8 and line["failed"] == 0
    assert set(line["checks"]) == {"logits_rel_l2_median", "logits_rel_l2_max", "wrong_tokens"}
    assert line["checks"]["wrong_tokens"]["value"] == 0


def test_work_gives_the_hand_counts_of_the_cell():
    """4.83 GB of weights (2.414 B parameters, nearly all bf16), 2.42 GB of
    SSD state at 64 sequences, the held experts' 1.70 GB; and the weights'
    bytes are those of the tree the program holds."""
    from repro.models import LM

    config = json.loads(LM_CONFIG.read_text())
    fam = _family()
    w = fam.work(config)
    assert abs(w["weight_bytes"] / 4.83e9 - 1) < 2e-3
    assert w["ssd_state_bytes"] * 64 == 9 * 128 * 64 * 128 * 4 * 64  # 2.416e9
    assert w["expert_weight_bytes"] == 10 * 9 * 3 * 4096 * 768 * 2
    assert w["kv_bytes_per_position"] == 1 * 2 * 8 * 128 * 2
    model = LM(fam.model_config(config))
    tree = jax.eval_shape(lambda: fam.program_weights(config, model))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree)) == w["weight_bytes"]
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_shapes())
    assert jax.tree.map(lambda s: (s.shape, s.dtype), tree) == want
    # one step at position 1024: about 10.1 GB, 12.3 ms at 819 GB/s
    assert abs(fam.step_bytes(w, 64, 1024) / 10.1e9 - 1) < 0.02


@pytest.mark.parametrize("moved, correct", [(0.0, True), (0.1, False)])
def test_check_rejects_logits_moved_past_a_limit(moved, correct):
    fam = _family()
    config = _tiny()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, config["vocab_size"], (2, 14)).astype(np.int32)
    positions = np.array([[11, 12, 13]] * 2)
    want = fam.reference_logits(config, tokens, positions)
    got = want + moved * np.abs(want).max() * rng.standard_normal(want.shape)
    answers = {"tokens": tokens, "positions": positions, "logits": got.astype(np.float32), "chosen": got.argmax(-1)}
    compared, checks = fam.check(config, None, answers, 0, rng, 6)
    assert compared
    assert all(c["value"] <= c["limit"] for c in checks.values()) is correct


def test_check_rejects_a_token_that_is_not_the_greedy_pick():
    """The next token must be the argmax of the engine's logits up to one
    bf16 rounding: a tie within it passes, a token from another row or
    another position fails."""
    fam = _family()
    config = _tiny()
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, config["vocab_size"], (2, 14)).astype(np.int32)
    positions = np.array([[11, 12, 13]] * 2)
    logits = fam.reference_logits(config, tokens, positions).astype(np.float32)
    best = logits.argmax(-1)
    answers = {"tokens": tokens, "positions": positions, "logits": logits, "chosen": best}
    assert fam.wrong_tokens(answers) == 0
    # a runner-up within one bf16 rounding of the largest is a tie
    tied = logits.copy()
    runner = (best + 1) % config["vocab_size"]
    np.put_along_axis(tied, runner[..., None], (logits.max(-1) * (1 - 2.0**-9))[..., None], axis=-1)
    assert fam.wrong_tokens(answers | {"logits": tied, "chosen": runner}) == 0
    assert fam.wrong_tokens(answers | {"chosen": best[::-1]}) > 0  # rows swapped
    assert fam.wrong_tokens(answers | {"chosen": np.roll(best, 1, axis=1)}) > 0  # a step late
    compared, checks = fam.check(config, None, answers | {"chosen": best[::-1]}, 0, rng, 6)
    assert checks["wrong_tokens"]["value"] > checks["wrong_tokens"]["limit"]


def test_the_benchmark_reference_is_a_copy_of_the_program_reference():
    program = run.CHECKOUT / "src" / "repro" / "models" / "reference_granite.py"
    copy = run.HERE / "lm_reference.py"
    assert copy.read_text() == program.read_text()
    assert "repro" not in "".join(ln for ln in copy.read_text().splitlines() if ln.startswith(("import", "from")))


@pytest.fixture
def tracer():
    """The span readers turn the program's tracer on as they are imported:
    put it back as it was afterwards, with the events it held."""
    from repro import obs

    tr = obs.get_tracer()
    was = tr.enabled, tr.path, tr.annotate, list(tr._events)
    tr.clear()
    yield tr
    tr.enabled, tr.path, tr.annotate = was[:3]
    tr.clear()
    tr._events.extend(was[3])


LM_METRICS = ["decode_roofline.lm_decode", "idle_share.lm_decode", "mfu.lm_decode",
              "decode_dispatch_us.lm_decode", "fetch_us.lm_decode", "expert_rows.lm_decode"]


def test_every_lm_metric_is_declared_for_the_cell():
    spec = json.loads(run.SPEC.read_text())
    e2e, per_layer = run.cell_metrics(spec, "granite_4_0_h_small.decode_b64")
    assert {m["name"] for m in e2e} == {"setup_s", "throughput_ips"}
    assert {m["name"] for m in per_layer} == set(LM_METRICS)
    assert all((run.HERE / "metrics" / f"{n}.py").is_file() for n in LM_METRICS)


def test_traced_run_reads_the_engine_spans(small_root, run_cell, monkeypatch, tracer):
    """A traced run on the CPU, whose trace has no device plane: the span
    and counter readers read the engine's ``lm.decode`` and ``lm.fetch``;
    the device readers get one synthetic run of the decode executable."""
    from benchmarks.chip import reduce

    def cpu_trace(trace_dir, chips):
        t = reduce.load(sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))[-1])
        lo, hi = t["spans"]["window"][0]
        return {"window_s": hi - lo, "busy_s": 0.25 * (hi - lo), "busy": np.zeros((0, 2)), "spans": t["spans"],
                "idle_by_span": [], "top_ops": [], "modules": {"jit_lm_decode": [1.0]}}

    monkeypatch.setattr(reduce, "reduce_dir", cpu_trace)
    cell = _add_tiny(small_root)
    line = run_cell(small_root, cell, trace=1)
    assert line["correct"] is True
    got = {n: line["metrics"][n]["value"] for n in LM_METRICS}
    assert got["idle_share.lm_decode"] == pytest.approx(75.0)
    assert 0 < got["decode_roofline.lm_decode"] < 1e-3  # a tiny step against one second
    assert got["decode_dispatch_us.lm_decode"] > 0 and got["fetch_us.lm_decode"] > 0 and got["mfu.lm_decode"] > 0
    # 4 tokens x top 2 routed over 8 experts, 2 held: 2 rows a held expert on average
    assert 0 < got["expert_rows.lm_decode"] <= 4 * 2 / 2


def test_decode_roofline_on_a_known_step():
    """One 20 ms run of the decode step at the cell's configuration: the
    least time is the step's bytes at 819 GB/s (about 12.3 ms)."""
    fam = _family()
    config = json.loads(LM_CONFIG.read_text())
    traffic = json.loads((run.HERE / "traffic" / "decode_b64.json").read_text())
    w = fam.work(config)
    peak = json.loads((run.HERE / "peaks.json").read_text())["kinds"]["TPU v5 lite"]
    ctx = {"trace": {"modules": {"jit_lm_decode": [0.02, 0.02]}}, "work": w, "peak": peak, "traffic": traffic,
           "run": {"positions": (1024, 1024)}}
    got = run.load_module(run.HERE / "metrics" / "decode_roofline.lm_decode.py").read(ctx)
    assert got == pytest.approx(100 * fam.step_bytes(w, 64, 1024) / 819e9 / 0.02)
    assert 55 < got < 70
    assert 64 * fam.token_flops(w, 1024) / 197e12 < fam.step_bytes(w, 64, 1024) / 819e9  # bandwidth-bound


def test_controls_move_the_compared_error(small_root, monkeypatch, capsys):
    """``lm_control`` at a tiny width on the CPU: weights rounded to 3
    mantissa bits raise the median row error well above the program's,
    and leaving out the shared expert raises it by far more; the harness's
    decision reads both controls as not correct, the program as correct."""
    from benchmarks.chip import lm_control

    # as at the cell's width: each embedding entry at the cell's size
    # (12 / sqrt(4096)), so that the layers, not the embedding, carry the
    # residual stream and float8's rounding adds up through them; and every
    # expert picked, so that bf16 rounding cannot swap one (at top 2 of 8 a
    # swap moves a row of this width by up to 0.18)
    cell = _add_tiny(small_root, embedding_multiplier=12 * (64 / 4096) ** 0.5, num_experts_per_tok=8)
    monkeypatch.setattr(run, "HERE", small_root)
    monkeypatch.setattr(run, "SPEC", small_root / "BENCHMARK.json")
    monkeypatch.setattr(run, "device_gate", lambda chips: {"platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    assert lm_control.main(["--workload", cell, "--seeds", "2147483659", "--seconds", "0.5",
                            "--controls", "none,float8,no_shared"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    median = {ln["control"]: ln["checks"]["logits_rel_l2_median"]["value"] for ln in lines}
    assert median["float8"] > 2 * median["none"] and median["no_shared"] > 10 * median["none"]
    assert {ln["control"]: ln["correct"] for ln in lines} == {"none": True, "float8": False, "no_shared": False}
    assert all(len(ln["rel_l2"]) == TRAFFIC["check_seqs"] for ln in lines)
