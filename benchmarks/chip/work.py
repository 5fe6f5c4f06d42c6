"""Work per inference, counted from the configuration's layer geometry, and
the chip's peaks.

These are the yardstick's denominators: the least time any implementation
can take.  ``macs`` counts the multiply-accumulates of conv, depthwise conv
and dense layers; ``least_time_s`` is the larger of ``2 * MACs`` over the
int8 peak (the chip's highest) and the least bytes over HBM bandwidth.  The
least bytes are the weights once per batch (int8 weights, int32 biases) plus
the network's inputs and outputs at the declared 1 byte per element;
activations are left out, so no implementation can read above 100 %.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from .reference import layer_shapes

PEAKS = Path(__file__).resolve().parent / "peaks.json"
BIAS_BYTES = 4  # int32 biases, as int8 deployments store them


def work(config: dict) -> dict:
    """MACs and bytes of one inference."""
    shapes = layer_shapes(config)
    macs = weight_bytes = 0
    for L in config["layers"]:
        src, out = shapes[L["inputs"][0]], shapes[L["name"]]
        if L["op"] == "conv":
            w = L["FY"] * L["FX"] * src[2] * L["K"]
            m = out[0] * out[1] * w
        elif L["op"] == "dwconv":
            w = L["FY"] * L["FX"] * src[2]
            m = out[0] * out[1] * w
        elif L["op"] == "dense":
            w = m = int(np.prod(src)) * L["K"]
        else:
            continue
        macs += m
        weight_bytes += w + BIAS_BYTES * out[-1]
    return {
        "macs": macs,
        "weight_bytes": weight_bytes,
        "input_bytes": sum(int(np.prod(s)) for s in config["inputs"].values()),
        "output_bytes": int(np.prod(shapes[config["output"]])),
    }


def least_time_s(w: dict, peak: dict, batch: int) -> float:
    """The least time one call on ``batch`` inferences can take."""
    compute = 2 * w["macs"] * batch / peak["int8_ops_per_s"]
    moved = w["weight_bytes"] + batch * (w["input_bytes"] + w["output_bytes"])
    return max(compute, moved / peak["hbm_bytes_per_s"])


def peak_for(kind: str, path: Path = PEAKS) -> dict:
    """The peak row of a device kind; an unknown kind is an error."""
    kinds = json.loads(Path(path).read_text())["kinds"]
    if kind not in kinds:
        raise ValueError(f"no peaks for device kind {kind!r} in {path}; known: {sorted(kinds)}")
    return kinds[kind]
