"""Offline scenario: a closed loop of full batches through ``BatchedModel``.

Each batch holds ``traffic["batch"]`` distinct inputs drawn from the pool.
``traffic["in_flight"]`` batches are dispatched ahead with
``run_batch_async`` before the oldest is blocked on and unstacked to the
host, as ``ModelServer`` keeps ``stream_depth`` batches in flight.  An
inference counts when its batch's outputs reached the host inside the
window; as in MLPerf Offline, the run lasts until the last counted batch
reached the host.
"""

from __future__ import annotations

import time
from collections import deque

import jax
import numpy as np


def setup(model, traffic: dict, requests: list[dict], spans) -> dict:
    from repro.serve import BatchedModel

    bm = BatchedModel(model.compiled)
    state = {"bm": bm, "model": model, "traffic": traffic, "requests": requests, "spans": spans}
    warm = requests[: traffic["batch"]]
    for _ in range(traffic["in_flight"]):  # the entry compiles on the first
        bm.unstack(jax.block_until_ready(bm.run_batch_async(model.params, warm)), len(warm))
    state["compile_s"] = sum(e["compile_us"] for e in bm.entry_stats()) * 1e-6
    return state


def window(state: dict, seconds: float, rng: np.random.Generator) -> dict:
    bm, params, spans = state["bm"], state["model"].params, state["spans"]
    requests, b, depth = state["requests"], state["traffic"]["batch"], state["traffic"]["in_flight"]
    inflight: deque = deque()
    answers, completed, attempted = [], 0, 0
    last = [0.0]  # when the last batch counted reached the host

    def finish(t_end):
        idx, outs = inflight.popleft()
        with spans("block"):
            jax.block_until_ready(outs)
        with spans("unstack"):
            rows = bm.unstack(outs, len(idx))
        answers.append((idx, rows))
        now = time.perf_counter()
        if now > t_end:
            return 0
        last[0] = now
        return len(idx)

    t0 = time.perf_counter()
    t_end = t0 + seconds
    with spans("window"):
        while time.perf_counter() < t_end:
            if len(inflight) >= depth:
                completed += finish(t_end)
            idx = rng.choice(len(requests), size=b, replace=False)
            batch = [requests[i] for i in idx]
            with spans("stack"):
                outs = bm.run_batch_async(params, batch)
            inflight.append((idx, outs))
            attempted += b
    while inflight:
        completed += finish(t_end)
    return {
        "window": (t0, t_end),
        "attempted": attempted,
        "completed": completed,
        "last_done": last[0],
        "failed": 0,
        "unanswered": 0,
        "answers": answers,
    }


def close(state: dict) -> None:
    state.clear()
