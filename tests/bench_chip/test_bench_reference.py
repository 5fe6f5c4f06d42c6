"""The benchmark's plain reference: agreement with the program's own
interpreter, the configurations' calibrated shifts, and the controls that
the comparison must reject."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import model, reference

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"

CONFIGS = ("mobilenetv1_025_vww", "resnet8_cifar10", "dscnn_kws")


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_execute_graph(name):
    from repro.cnn import execute_graph, nets
    from repro.core import apply_transforms
    from repro.core.graph import dead_node_elimination, integerize, layout_to

    cfg = _config(name)
    g = apply_transforms(getattr(nets, cfg["network"])(1), [dead_node_elimination, integerize(1), layout_to("NHWC")])
    weights = reference.make_weights(cfg)
    params = model.program_params(g, cfg, weights)
    x = model.int8_pool(cfg, 3, np.random.default_rng(7))
    want = reference.forward(cfg, weights, x)
    got = np.stack([np.asarray(execute_graph(g, params, {"x": x["x"][i]})[g.outputs[0]]) for i in range(3)])
    assert reference.compare(got, want) == {"max_abs_err": 0.0, "wrong_rows": 0}
    assert np.abs(want).max() > 8, "the outputs carry signal, not a network decayed to zeros"


@pytest.mark.parametrize("name", CONFIGS)
def test_configured_shifts_are_the_calibrated_ones(name):
    cfg = _config(name)
    cal = cfg["calibration"]
    x = model.int8_pool(cfg, cal["examples"], np.random.default_rng(cal["seed"]))
    shifts = reference.calibrate_shifts(cfg, reference.make_weights(cfg), x, cal["quantile"])
    assert shifts == {L["name"]: L["shift"] for L in cfg["layers"] if "shift" in L}


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("precision", ["int4", "bf16_acc"])
def test_lower_precision_control_is_rejected(name, precision):
    cfg = _config(name)
    weights = reference.make_weights(cfg)
    x = model.int8_pool(cfg, 32, np.random.default_rng(11))
    c = reference.compare(reference.forward(cfg, weights, x, precision), reference.forward(cfg, weights, x))
    assert c["max_abs_err"] > 0 and c["wrong_rows"] > 0


def test_program_graph_must_match_configuration():
    from repro.cnn import nets

    cfg = _config("resnet8_cifar10")
    bad = json.loads(json.dumps(cfg))
    bad["layers"][0]["stride"] = 2
    with pytest.raises(ValueError, match="stem"):
        model.program_params(nets.resnet8_graph(1), bad, reference.make_weights(cfg))
    bad = json.loads(json.dumps(cfg))
    bad["layers"][3]["relu"] = True  # the published ReLU after the residual add
    with pytest.raises(ValueError, match="s1add"):
        model.program_params(nets.resnet8_graph(1), bad, reference.make_weights(cfg))


def test_rounding_is_half_to_even():
    x = np.array([48.0, 80.0, -48.0, 47.0, 49.0, 2**23 + 16.0])
    assert reference._round_half_even_div(x, 32).tolist() == [2.0, 2.0, -2.0, 1.0, 2.0, 2**18 + 0.0]
