"""The system under test, built from a configuration file.

``build`` runs the README flow of the program: the network from
``repro.cnn.nets`` (``config["network"]``), the transforms
``dead_node_elimination``, ``integerize(1)``, ``layout_to("NHWC")``, then
``dispatch`` onto ``config["target"]`` and ``lower``.  It hands the program
the benchmark's own weights (``reference.make_weights``), placed on the
program's nodes after checking that the program's graph is the network the
configuration describes: the same layers, in the same order, with the same
geometry and the same edges.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from . import reference

# program op -> configuration op, for the nodes that start a layer
ANCHORS = {"conv2d": "conv", "dwconv2d": "dwconv", "dense": "dense", "add": "add", "avgpool": "avgpool"}
ANCHOR_OP = {v: k for k, v in ANCHORS.items()}


@dataclass
class Model:
    graph: object  # repro.core.Graph after the transforms
    compiled: object  # repro.backend.CompiledModel
    params: dict  # the program's params, made by the benchmark
    dispatch_s: float  # host clock around dispatch()


def _check_geometry(node, layer: dict, shape: tuple) -> None:
    want = {"stride": layer.get("stride"), "FY": layer.get("FY"), "FX": layer.get("FX")}
    if layer["op"] in ("conv", "dwconv"):
        want.update(OY=shape[0], OX=shape[1], K=shape[2] if layer["op"] == "conv" else None)
    elif layer["op"] == "dense":
        want["K"] = layer["K"]
    for k, v in want.items():
        if v is not None and int(node.attr(k, 1) or 1) != int(v):
            raise ValueError(f"layer {layer['name']}: the program's {node.name} has {k}={node.attr(k)}, the configuration {v}")


def program_params(graph, config: dict, weights: dict) -> dict:
    """Params for the program's nodes, keyed by its node names.

    Walks the program's nodes in order; each anchor node starts the next
    configuration layer, and the bias and requant nodes that follow it take
    that layer's bias and shift.  Raises where the program's graph is not
    the configured network."""
    layers = iter(config["layers"])
    shapes = reference.layer_shapes(config)
    if dict(graph.inputs) != {k: tuple(v) for k, v in config["inputs"].items()}:
        raise ValueError(f"program inputs {dict(graph.inputs)} differ from the configuration's {config['inputs']}")
    layer_of = {k: k for k in graph.inputs}  # program tensor -> configuration layer
    params: dict[str, dict] = {}
    ops_of: dict[str, list[str]] = {}
    layer = None
    for n in graph.nodes:
        if n.op in ANCHORS:
            layer = next(layers, None)
            if layer is None or ANCHORS[n.op] != layer["op"]:
                raise ValueError(f"the program's node {n.name} ({n.op}) does not match the configuration's layer {layer}")
            srcs = [layer_of[i] for i in n.inputs]
            if srcs != layer["inputs"]:
                raise ValueError(f"layer {layer['name']}: the program feeds it {srcs}, the configuration {layer['inputs']}")
            _check_geometry(n, layer, shapes[layer["name"]])
            if n.op in ("conv2d", "dwconv2d", "dense"):
                params[n.name] = {"w": weights[layer["name"]]["w"]}
        elif layer is None:
            raise ValueError(f"the program's node {n.name} ({n.op}) precedes every layer")
        elif n.op == "bias_add":
            params[n.name] = {"b": weights[layer["name"]]["b"]}
        elif n.op == "requant":
            params[n.name] = {"shift": np.float32(layer["shift"])}
        ops_of.setdefault(layer["name"], []).append(n.op)
        layer_of[n.name] = layer["name"]
    if next(layers, None) is not None or [layer_of[o] for o in graph.outputs] != [config["output"]]:
        raise ValueError("the program's graph ends before the configuration's output layer")
    for L in config["layers"]:
        want = [ANCHOR_OP[L["op"]]]
        want += ["bias_add"] if L["op"] in reference.WEIGHTED else []
        want += ["requant"] if L["op"] != "avgpool" else []
        want += ["relu"] if L.get("relu") else []
        if ops_of[L["name"]] != want:
            raise ValueError(f"layer {L['name']}: the program computes {ops_of[L['name']]}, the configuration {want}")
    return params


def build(config: dict) -> Model:
    from repro.backend import lower
    from repro.cnn import nets
    from repro.core import apply_transforms, dispatch
    from repro.core.graph import dead_node_elimination, integerize, layout_to

    graph = apply_transforms(
        getattr(nets, config["network"])(1),
        [dead_node_elimination, integerize(1), layout_to("NHWC")],
    )
    params = program_params(graph, config, reference.make_weights(config))
    t0 = time.perf_counter()
    mapped = dispatch(graph, config["target"])
    dispatch_s = time.perf_counter() - t0
    return Model(graph, lower(mapped), params, dispatch_s)


def int8_pool(config: dict, n: int, rng: np.random.Generator) -> dict[str, np.ndarray]:
    """``n`` seeded int8-valued inputs per graph input, float32 as the
    program takes them: ``{name: (n, *input_shape)}``."""
    return {
        k: rng.integers(-128, 128, size=(n,) + tuple(s)).astype(np.float32)
        for k, s in config["inputs"].items()
    }
