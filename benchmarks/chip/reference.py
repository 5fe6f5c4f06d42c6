"""The benchmark's plain reference: an int8 network forward pass in numpy.

It reads the network from the configuration file alone (``layers``), makes
the weights from the configuration's own seed, and computes every layer in
float64 on the host.  All values are integers far below 2**53, so float64
sums are exact and the result is the exact int8/int32 arithmetic the
configuration states:

* ``conv`` / ``dwconv``: SAME-padded convolution (the extra pad row and
  column at the bottom and right, as XLA and TensorFlow pad), NHWC, HWIO;
* ``dense``: flatten, then ``x @ w.T``;
* bias add, then requant ``(x * M + B) >> S`` with ``M = 1``, ``B = 0``,
  rounded half to even and clipped to ``[-128, 127]`` (Table II of the
  paper); ``relu`` where the layer says so;
* ``add``: the sum of its inputs, then requant;
* ``avgpool``: global mean over the spatial axes, rounded half to even.

Nothing here imports the program under test.  ``precision`` selects the
lower-precision controls that the comparison has to reject:
``"int4"`` rounds every conv/dense operand to the int4 grid, and
``"bf16_acc"`` rounds every accumulator to bfloat16 before requant.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "PRECISIONS",
    "calibrate_shifts",
    "compare",
    "forward",
    "layer_shapes",
    "make_weights",
]

PRECISIONS = ("int8", "int4", "bf16_acc")
WEIGHTED = ("conv", "dwconv", "dense")
BLOCK_ROWS = 64  # inputs per block, so a pool of any size fits in memory


def layer_shapes(config: dict) -> dict[str, tuple[int, ...]]:
    """Per-example output shape of every layer, from the input shape."""
    shapes = {k: tuple(v[1:]) for k, v in config["inputs"].items()}
    for L in config["layers"]:
        src = shapes[L["inputs"][0]]
        op = L["op"]
        if op in ("conv", "dwconv"):
            s = L["stride"]
            k = L["K"] if op == "conv" else src[2]
            shapes[L["name"]] = (-(-src[0] // s), -(-src[1] // s), k)
        elif op == "dense":
            shapes[L["name"]] = (L["K"],)
        elif op == "avgpool":
            shapes[L["name"]] = (1, 1, src[2])
        else:  # add
            shapes[L["name"]] = src
    return shapes


def make_weights(config: dict) -> dict[str, dict[str, np.ndarray]]:
    """Seeded int8-valued weights and biases, in the layouts the layers use:
    conv HWIO, dwconv HW1C, dense (K, C).  Drawn in layer order from
    ``config["weights"]["seed"]``."""
    spec = config["weights"]
    rng = np.random.default_rng(spec["seed"])
    w_lo, w_hi = spec["w_range"]
    b_lo, b_hi = spec["bias_range"]
    shapes = layer_shapes(config)
    out: dict[str, dict[str, np.ndarray]] = {}
    for L in config["layers"]:
        op = L["op"]
        if op not in WEIGHTED:
            continue
        c = shapes[L["inputs"][0]][-1]
        if op == "conv":
            wshape = (L["FY"], L["FX"], c, L["K"])
        elif op == "dwconv":
            wshape = (L["FY"], L["FX"], 1, c)
        else:
            c = int(np.prod(shapes[L["inputs"][0]]))
            wshape = (L["K"], c)
        k = wshape[-1] if op != "dense" else wshape[0]
        out[L["name"]] = {
            "w": rng.integers(w_lo, w_hi + 1, size=wshape).astype(np.float32),
            "b": rng.integers(b_lo, b_hi + 1, size=(k,)).astype(np.float32),
        }
    return out


def _round_half_even_div(x: np.ndarray, d: float) -> np.ndarray:
    """round(x / d), ties to even, for integer-valued x and integer d.

    ``np.round`` rounds half to even.  The quotient is exact where d is a
    power of two; otherwise a tie needs d even, and then the quotient q + 1/2
    is exact too, while any other quotient lies at least 1/(2d) from a tie,
    far beyond float64's error at these magnitudes."""
    return np.round(x / d)


def _to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float64 values to the nearest bfloat16 (ties to even)."""
    f = x.astype(np.float32).view(np.uint32).astype(np.uint64)
    lsb = (f >> 16) & 1
    f = ((f + 0x7FFF + lsb) >> 16) << 16
    return f.astype(np.uint32).view(np.float32).astype(np.float64)


def _int4(x: np.ndarray) -> np.ndarray:
    """An int8 value rounded to the int4 grid (steps of 16), kept at int8 scale."""
    return np.clip(_round_half_even_div(x, 16.0), -8, 7) * 16.0


def _conv(x, w, stride, depthwise):
    n, h, wd, c = x.shape
    fy, fx = w.shape[0], w.shape[1]
    oy, ox = -(-h // stride), -(-wd // stride)
    py = max((oy - 1) * stride + fy - h, 0)
    px = max((ox - 1) * stride + fx - wd, 0)
    xp = np.pad(x, ((0, 0), (py // 2, py - py // 2), (px // 2, px - px // 2), (0, 0)))
    k = c if depthwise else w.shape[3]
    acc = np.zeros((n, oy, ox, k))
    for i in range(fy):
        for j in range(fx):
            tap = xp[:, i : i + (oy - 1) * stride + 1 : stride, j : j + (ox - 1) * stride + 1 : stride]
            if depthwise:
                acc += tap * w[i, j, 0]
            else:
                acc += (tap.reshape(-1, c) @ w[i, j]).reshape(n, oy, ox, k)
    return acc


def _requant(acc, shift, relu):
    y = np.clip(_round_half_even_div(acc, 2.0**shift), -128, 127)
    return np.maximum(y, 0) if relu else y


def _accumulate(L: dict, weights: dict, xs: list, precision: str) -> np.ndarray:
    """A requantised layer's accumulator: everything before the requant."""
    if L["op"] == "add":
        return sum(xs[1:], xs[0])
    a = xs[0]
    w = weights[L["name"]]["w"].astype(np.float64)
    if precision == "int4":
        a, w = _int4(a), _int4(w)
    if L["op"] == "dense":
        acc = a.reshape(a.shape[0], -1) @ w.T
    else:
        acc = _conv(a, w, L["stride"], L["op"] == "dwconv")
    acc = acc + weights[L["name"]]["b"]
    return _to_bf16(acc) if precision == "bf16_acc" else acc


def _layer(L: dict, weights: dict, xs: list, precision: str) -> np.ndarray:
    if L["op"] == "avgpool":
        a = xs[0]
        return _round_half_even_div(a.sum(axis=(1, 2), keepdims=True), a.shape[1] * a.shape[2])
    if L["op"] not in WEIGHTED + ("add",):
        raise ValueError(f"layer {L['name']}: unknown op {L['op']!r}")
    return _requant(_accumulate(L, weights, xs, precision), L["shift"], L["relu"])


def _inputs(x: dict) -> dict:
    # drop the batch-1 axis that each example's declared shape carries
    return {k: np.asarray(v, np.float64).reshape((v.shape[0],) + tuple(v.shape[2:])) for k, v in x.items()}


def forward(config: dict, weights: dict, x: dict, precision: str = "int8") -> np.ndarray:
    """Network output for a stack of examples.

    ``x`` maps each input name to an array ``(N, *input_shape)`` (the input
    shape includes the batch-1 axis the configuration declares); the result
    is ``(N, *output_shape)``, float64 holding integers.  Computed in blocks
    of ``BLOCK_ROWS`` examples."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    n = next(iter(x.values())).shape[0]
    parts = []
    for i in range(0, n, BLOCK_ROWS):
        env = _inputs({k: v[i : i + BLOCK_ROWS] for k, v in x.items()})
        for L in config["layers"]:
            env[L["name"]] = _layer(L, weights, [env[j] for j in L["inputs"]], precision)
        parts.append(env[config["output"]])
    return np.concatenate(parts)


def calibrate_shifts(config: dict, weights: dict, x: dict, quantile: float) -> dict[str, int]:
    """The requant shift of each layer, set as a post-training calibration
    would: the least ``S >= 0`` with ``quantile(|acc|) / 2**S <= 127`` on the
    calibration inputs ``x``, each layer fed by the layers before it already
    requantised with their own calibrated shifts."""
    env = _inputs(x)
    shifts: dict[str, int] = {}
    for L in config["layers"]:
        xs = [env[j] for j in L["inputs"]]
        if L["op"] != "avgpool":
            q = float(np.quantile(np.abs(_accumulate(L, weights, xs, "int8")), quantile))
            shifts[L["name"]] = max(0, int(np.ceil(np.log2(q / 127.0)))) if q > 127 else 0
            L = {**L, "shift": shifts[L["name"]]}
        env[L["name"]] = _layer(L, weights, xs, "int8")
    return shifts


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """The numbers ``correct`` is decided on: the largest absolute gap over
    every compared output element, and how many compared rows differ at all."""
    got = np.asarray(got, np.float64).reshape(want.shape)
    gap = np.abs(got - want).reshape(want.shape[0], -1)
    gap = np.where(np.isnan(gap), np.inf, gap)
    return {
        "max_abs_err": float(gap.max()) if gap.size else 0.0,
        "wrong_rows": int(np.count_nonzero(gap.max(axis=1) > 0)) if gap.size else 0,
    }
