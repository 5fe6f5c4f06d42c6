"""Share of its roofline that the decode step (``ServeEngine``'s
``lm_decode`` executable) reaches: the least time of one step over the
mean device time of one run of it.  The least time is the longer of the
step's FLOPs at the bf16 peak and its least bytes at HBM bandwidth
(``families/lm.py``: ``token_flops``, ``step_bytes``), at the window's
mean position."""

import numpy as np

from benchmarks.chip.families.lm import step_bytes, token_flops


def read(ctx):
    t = ctx["trace"]
    runs = [] if t is None else [d for name, ds in t["modules"].items() if "lm_decode" in name for d in ds]
    if not runs:
        return None
    w, peak, batch = ctx["work"], ctx["peak"], ctx["traffic"]["batch"]
    pos = float(np.mean(ctx["run"]["positions"]))
    least = max(batch * token_flops(w, pos) / peak["bf16_flops_per_s"], step_bytes(w, batch, pos) / peak["hbm_bytes_per_s"])
    return 100.0 * least / float(np.mean(runs))
