"""The model-family seam: a configuration names its family, and the harness
finds that family's build, inputs, check and work by name.  A family made
of new files alone runs end to end; the harness, not the family, holds
each compared number to its limit; the CNN family feeds as the harness did
before it had families and checks exactly; a configuration without a
usable family is refused."""

import json

import numpy as np
import pytest

from benchmarks.chip import model, reference, run, work

TOY_FAMILY = '''
"""A float family: y = tanh(x @ w), float32 on the device, float64 reference."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

TOL = 1e-4  # float32 against float64 over a 16-term dot product


def _weights(config):
    return np.random.default_rng(config["seed"]).standard_normal((config["width"], config["width"])) / 4


def build(config):
    w = jnp.asarray(_weights(config), jnp.float32)
    return SimpleNamespace(run=jax.jit(lambda x: jnp.tanh(x @ w))), {"toy_build_s": 0.5}


def inputs(config, traffic, rng):
    pool = rng.standard_normal((traffic["pool"], config["width"])).astype(np.float32)
    return pool, [pool[i] for i in range(traffic["pool"])]


def check(config, pool, answers, unanswered, rng, rows):
    idx = np.array([i for i, _ in answers])
    got = np.stack([a for _, a in answers]).astype(np.float64)
    want = np.tanh(pool[idx].astype(np.float64) @ _weights(config))
    checks = {
        "max_abs_err": {"value": float(np.abs(got - want).max()), "limit": TOL},
        "unanswered": {"value": unanswered, "limit": 0},
    }
    return len(idx) > 0, checks


def work(config):
    return {"flops": 2 * config["width"] ** 2}
'''

TOY_DRIVER = '''
"""Closed loop over the pool; ``nudge`` moves the first answer by that much."""
import time

import numpy as np


def setup(model, traffic, requests, spans):
    np.asarray(model.run(requests[0]))
    return {"model": model, "traffic": traffic, "requests": requests, "compile_s": 0.25}


def window(state, seconds, rng):
    answers = []
    t0 = time.perf_counter()
    for i in rng.permutation(len(state["requests"])):
        answers.append((int(i), np.asarray(state["model"].run(state["requests"][i]))))
    answers[0] = (answers[0][0], answers[0][1] + state["traffic"].get("nudge", 0.0))
    n = len(answers)
    return {"window": (t0, time.perf_counter()), "attempted": n, "completed": n, "failed": 0,
            "unanswered": 0, "answers": answers}


def close(state):
    state.clear()
'''

TOY_READER = '''
def read(ctx):
    t0, t1 = ctx["run"]["window"]
    return ctx["run"]["completed"] * ctx["work"]["flops"] / (t1 - t0)
'''


# a family whose check returns what its configuration says, to see what the
# harness makes of a family's verdict
VERDICT_CHECK = '''

def check(config, pool, answers, unanswered, rng, rows):
    compared, checks = config["verdict"]
    return compared, checks
'''


def _add_toy(root, traffic_extra: dict | None = None, verdict: list | None = None) -> str:
    """The toy family, configuration, traffic, driver and reader, written as
    new files into ``root``; the new cell's name.  With ``verdict`` the
    family's check returns it as ``(compared, checks)``."""
    (root / "families" / "toy_float.py").write_text(TOY_FAMILY + (VERDICT_CHECK if verdict is not None else ""))
    (root / "drivers" / "toy_loop.py").write_text(TOY_DRIVER)
    (root / "metrics" / "toy_flops_per_s.py").write_text(TOY_READER)
    config = {"name": "toy", "family": "toy_float", "width": 16, "seed": 3} | ({"verdict": verdict} if verdict is not None else {})
    (root / "configs" / "toy.json").write_text(json.dumps(config))
    traffic = {"driver": "toy_loop", "pool": 12, "check_rows": 12} | (traffic_extra or {})
    (root / "traffic" / "toy_loop.json").write_text(json.dumps(traffic))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = "toy.toy_loop"
    spec["configs"].append({"name": "toy", "source": "a test", "file": "benchmarks/chip/configs/toy.json", "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": cell, "config": "toy", "traffic": "toy_loop", "chips": 1, "why": "a test"})
    spec["end_to_end"].append({"name": "toy_flops_per_s", "unit": "flop/s", "better": "higher", "bound": 0.1,
                               "source": "host_clock", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def test_a_family_of_new_files_runs_end_to_end(small_root, run_cell):
    cell = _add_toy(small_root)
    line = run_cell(small_root, cell)
    assert line["correct"] is True and line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "toy_flops_per_s"}
    assert line["metrics"]["toy_flops_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_abs_err"]["limit"] == 1e-4
    assert 0 < line["checks"]["max_abs_err"]["value"] <= 1e-4


def test_a_family_check_rejects_an_answer_moved_past_its_tolerance(small_root, run_cell):
    cell = _add_toy(small_root, {"nudge": 1e-3})
    line = run_cell(small_root, cell)
    assert line["correct"] is False
    assert line["checks"]["max_abs_err"]["value"] > line["checks"]["max_abs_err"]["limit"]


@pytest.mark.parametrize(
    "verdict, correct",
    [
        ([True, {"gap": {"value": 2.0, "limit": 1.0}}], False),
        ([True, {"gap": {"value": 0.5, "limit": 1.0}, "late": {"value": 1, "limit": 0}}], False),
        ([True, {}], False),
        ([False, {"gap": {"value": 0.0, "limit": 1.0}}], False),
        ([True, {"gap": {"value": 1.0, "limit": 1.0}, "late": {"value": 0, "limit": 0}}], True),
    ],
    ids=["over_its_limit", "one_of_two_over", "no_checks", "nothing_compared", "at_the_limits"],
)
def test_the_harness_holds_each_check_to_its_limit(small_root, run_cell, verdict, correct):
    """A family gives the numbers and their limits; ``correct`` is the
    harness's, whatever the family says it compared."""
    line = run_cell(small_root, _add_toy(small_root, verdict=verdict))
    assert line["correct"] is correct
    assert line["checks"] == verdict[1] and list(line)[-1] == "checks"


def test_build_parts_join_the_set_up(small_root, run_cell, monkeypatch):
    cell = _add_toy(small_root)
    spec = json.loads((small_root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "toy_build_s", "unit": "s", "better": "lower", "source": "host_clock",
                              "layer": "toy", "moves": "setup_s", "workloads": [cell]})
    (small_root / "BENCHMARK.json").write_text(json.dumps(spec))
    (small_root / "metrics" / "toy_build_s.py").write_text("def read(ctx):\n    return ctx['setup']['toy_build_s']\n")
    from benchmarks.chip import reduce

    stand_in = {"window_s": 1.0, "busy_s": 0.5, "spans": {}, "idle_by_span": [], "top_ops": [], "modules": {}}
    monkeypatch.setattr(reduce, "reduce_dir", lambda trace_dir, chips: stand_in)
    line = run_cell(small_root, cell, trace=1)
    assert line["metrics"] == {"toy_build_s": {"value": 0.5, "unit": "s"}}


def _rngs(seed):
    return [np.random.default_rng(s) for s in np.random.SeedSequence(seed).spawn(3)]


@pytest.mark.parametrize("name", ["resnet8_cifar10", "dscnn_kws"])
def test_cnn_family_feeds_and_checks_as_before(name):
    config = run.load_named(run.HERE, "configs", name)
    cnn = run.load_family(run.HERE, config)
    traffic = {"pool": 24}
    seed = 2**31 + 11
    pool, requests = cnn.inputs(config, traffic, _rngs(seed)[0])
    old_pool = model.int8_pool(config, traffic["pool"], _rngs(seed)[0])
    assert pool.keys() == old_pool.keys() and all(np.array_equal(pool[k], old_pool[k]) for k in pool)
    old_requests = [{k: v[i] for k, v in old_pool.items()} for i in range(traffic["pool"])]
    assert len(requests) == len(old_requests)
    assert all(r.keys() == o.keys() and all(np.array_equal(r[k], o[k]) for k in r) for r, o in zip(requests, old_requests))

    # answers as the drivers deliver them: (indices, [output dicts]); one of them wrong
    want = reference.forward(config, reference.make_weights(config), pool)
    order = _rngs(seed)[1].integers(traffic["pool"], size=30)
    answers = [(np.array([i]), [{"out": want[i].copy()}]) for i in order]
    answers[3][1][0]["out"][0] += 2
    compared, checks = cnn.check(config, pool, answers, 1, _rngs(seed)[2], 1000)
    assert compared is True
    assert checks == {
        "max_abs_err": {"value": 2.0, "limit": 0.0},
        "wrong_rows": {"value": 1, "limit": 0},
        "unanswered": {"value": 1, "limit": 0},
    }
    compared, checks = cnn.check(config, pool, answers[4:], 0, _rngs(seed)[2], 8)
    assert compared is True and all(c["value"] == 0 for c in checks.values())
    assert cnn.work(config) == work.work(config)


@pytest.mark.parametrize("case", ["no_family", "unknown_family", "no_check"])
def test_a_configuration_without_a_usable_family_is_refused(small_root, run_cell, capsys, monkeypatch, case):
    path = small_root / "configs" / "resnet8_cifar10.json"
    config = json.loads(path.read_text())
    if case == "no_family":
        del config["family"]
    elif case == "unknown_family":
        config["family"] = "no_such_family"
    else:
        text = (small_root / "families" / "cnn_int8.py").read_text()
        (small_root / "families" / "cnn_lacking.py").write_text(text.replace("def check(", "def _check("))
        config["family"] = "cnn_lacking"
    path.write_text(json.dumps(config))
    argv = ["--workload", "resnet8_cifar10.single_stream", "--seed", "5", "--seconds", "1"]
    with pytest.raises((ValueError, FileNotFoundError)):
        run.main(argv, root=small_root, spec_path=small_root / "BENCHMARK.json")
    assert capsys.readouterr().out == ""


def test_run_reaches_the_cnn_modules_only_through_the_family():
    text = (run.HERE / "run.py").read_text()
    assert "repro.cnn" not in text and "import model" not in text and "import reference" not in text


@pytest.mark.parametrize("path", sorted((run.HERE / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_every_configuration_loads_its_family(path):
    config = json.loads(path.read_text())
    family = run.load_family(run.HERE, config)
    assert all(callable(getattr(family, part)) for part in run.FAMILY_PARTS)
