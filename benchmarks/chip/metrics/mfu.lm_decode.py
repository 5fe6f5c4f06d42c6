"""The whole step's share of the chip's bf16 peak: generated tokens per
second (as ``throughput_ips`` counts them) x FLOPs per token at the
chip's share (``families/lm.py``: ``token_flops``, routed experts at their
expected top_k x held / routed) at the window's mean position."""

import numpy as np

from benchmarks.chip.families.lm import token_flops


def read(ctx):
    run = ctx["run"]
    tps = run["completed"] / (run["last_done"] - run["window"][0])
    return 100.0 * tps * token_flops(ctx["work"], float(np.mean(run["positions"]))) / ctx["peak"]["bf16_flops_per_s"]
