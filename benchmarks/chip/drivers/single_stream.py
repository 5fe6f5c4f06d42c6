"""Single-stream scenario: one query outstanding at a time, batch 1, through
the whole-graph AOT executable (``CompiledModel.to_aot().run``).  A query
runs from a numpy input to its numpy output on the host."""

from __future__ import annotations

import time

import numpy as np


def setup(model, traffic: dict, requests: list[dict], spans) -> dict:
    aot = model.compiled.to_aot()
    entry = aot.warmup(model.params, requests[0])
    for x in requests[:2]:
        {k: np.asarray(v) for k, v in aot.run(model.params, x).items()}
    return {"aot": aot, "model": model, "requests": requests, "spans": spans, "compile_s": entry.compile_us * 1e-6}


def window(state: dict, seconds: float, rng: np.random.Generator) -> dict:
    aot, params, spans, requests = state["aot"], state["model"].params, state["spans"], state["requests"]
    answers, latencies = [], []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with spans("window"):
        while time.perf_counter() < t_end:
            i = int(rng.integers(len(requests)))
            t = time.perf_counter()
            with spans("query"):
                out = {k: np.asarray(v) for k, v in aot.run(params, requests[i]).items()}
            latencies.append(time.perf_counter() - t)
            answers.append((np.array([i]), [out]))
    return {
        "window": (t0, t_end),
        "attempted": len(latencies),
        "completed": len(latencies),
        "failed": 0,
        "unanswered": 0,
        "answers": answers,
        "latencies_s": np.asarray(latencies),
    }


def close(state: dict) -> None:
    state.clear()
