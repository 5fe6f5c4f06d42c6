"""Trace reduction: from the profiler's ``.xplane.pb`` to the numbers the
metric readers take.

* The window is the benchmark's own ``bench.window`` host span.
* Busy time is the union of the intervals in which an XLA op ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
  the window and averaged over the chips used; idle is the rest.
* Each idle gap is put down to the innermost ``bench.*`` host span that
  covers its midpoint (``none`` where no span does): what the host was
  doing while the device waited.
* ``top_ops``: device time summed by ``<executable>/<HLO op>``, the largest
  ten.
* ``modules``: the duration of each run of each executable (the ``XLA
  Modules`` line), by executable name, ``name(id)`` suffix dropped.

Times come out in seconds on the profiler's clock, which host and device
events share.
"""

from __future__ import annotations

import gzip
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

from .spans import PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
MODULE_ID = re.compile(r"\(-?\d+\)$")


def merge(iv: np.ndarray) -> np.ndarray:
    """The union of ``(start, end)`` intervals, as sorted disjoint intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.append(new[1:], True))
    return np.stack([starts, ends[last]], axis=1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def overlap(merged: np.ndarray, lo: float, hi: float) -> float:
    """Seconds of the disjoint intervals ``merged`` inside ``[lo, hi]``."""
    c = clip(merged, lo, hi)
    return float((c[:, 1] - c[:, 0]).sum())


def _events(line) -> tuple[list[str], np.ndarray]:
    names, iv = [], []
    for e in line.events:
        names.append(e.name)
        iv.append((e.start_ns, e.start_ns + e.duration_ns))
    return names, np.asarray(iv, np.float64).reshape(-1, 2) * 1e-9


def load(path: Path) -> dict:
    """The parts of one trace file the reduction reads."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    pd = ProfileData.from_serialized_xspace(gzip.decompress(raw) if str(path).endswith(".gz") else raw)
    out: dict = {"devices": {}, "spans": defaultdict(list)}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: _events(ln) for ln in plane.lines if ln.name in (OPS_LINE, MODULES_LINE)}
            if OPS_LINE in lines:
                out["devices"][int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        out["spans"][e.name[len(PREFIX):]].append((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    out["spans"] = {k: np.asarray(v) for k, v in out["spans"].items()}
    return out


def reduce(t: dict, chips: int) -> dict | None:
    """Busy and idle time, idle gaps by host span, top ops and executable
    runs over the ``window`` span.  ``None`` where the trace holds no window
    or no device ops."""
    if "window" not in t["spans"] or not t["devices"]:
        return None
    lo, hi = t["spans"]["window"][0]
    devices = [t["devices"][d] for d in sorted(t["devices"])[:chips]]
    busy = [merge(clip(d[OPS_LINE][1], lo, hi)) for d in devices]
    busy_s = float(np.mean([(b[:, 1] - b[:, 0]).sum() for b in busy]))

    b0 = busy[0]
    gap_lo = np.concatenate([[lo], b0[:, 1]])
    gap_hi = np.concatenate([b0[:, 0], [hi]])
    keep = gap_hi > gap_lo
    gaps = np.stack([gap_lo[keep], gap_hi[keep]], axis=1)
    mid = gaps.mean(axis=1)
    names = ["none"] + [n for n in t["spans"] if n != "window"]
    # per span kind, the start of the latest span of that kind covering each
    # midpoint (-inf where none does); the innermost covering span wins
    starts = np.full((len(names), len(mid)), -np.inf)
    starts[0] = np.finfo(float).min
    for k, name in enumerate(names[1:], 1):
        iv = t["spans"][name][np.argsort(t["spans"][name][:, 0])]
        j = np.searchsorted(iv[:, 0], mid, side="right") - 1
        hit = (j >= 0) & (iv[np.maximum(j, 0), 1] >= mid)
        starts[k, hit] = iv[j[hit], 0]
    owner = np.argmax(starts, axis=0)
    idle: dict[str, float] = defaultdict(float)
    for k, dur in zip(owner, gaps[:, 1] - gaps[:, 0]):
        idle[names[k]] += float(dur)

    ops: dict[str, float] = defaultdict(float)
    modules: dict[str, list[float]] = defaultdict(list)
    for d in devices:
        mod_names, mods = d.get(MODULES_LINE, ([], np.zeros((0, 2))))
        mod_names = [MODULE_ID.sub("", n) for n in mod_names]
        for n, (s, e) in zip(mod_names, mods):
            if lo <= s and e <= hi:
                modules[n].append(e - s)
        op_names, iv = d[OPS_LINE]
        inside = np.flatnonzero((iv[:, 0] >= lo) & (iv[:, 1] <= hi))
        # each op is named by the executable it ran in and its HLO name
        j = np.searchsorted(mods[:, 0], iv[inside, 0], side="right") - 1
        in_mod = (j >= 0) & (iv[inside, 0] < mods[np.maximum(j, 0), 1]) if len(mods) else np.zeros(len(inside), bool)
        keys = [
            (mod_names[m] if hit else "?") + "/" + op_names[i].split(" = ", 1)[0].lstrip("%")
            for i, m, hit in zip(inside, j, in_mod)
        ]
        uniq, inv = np.unique(np.asarray(keys, dtype=object).astype(str), return_inverse=True)
        for n, dur in zip(uniq, np.bincount(inv, weights=iv[inside, 1] - iv[inside, 0])):
            ops[str(n)] += float(dur) / len(devices)
    return {
        "window_s": float(hi - lo),
        "busy_s": busy_s,
        "busy": b0,
        "spans": t["spans"],
        "idle_by_span": [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]],
        "top_ops": [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "modules": dict(modules),
    }


def reduce_dir(trace_dir: str, chips: int) -> dict | None:
    """Reduce the newest trace the profiler wrote under ``trace_dir``."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return reduce(load(files[-1]), chips) if files else None
