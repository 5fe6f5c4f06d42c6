"""Server scenario: open-loop Poisson arrivals into one ``ModelServer``.

The arrival schedule is absolute: request ``i`` is due at ``t0 + a_i`` and
a late submit never stretches the gaps after it.  Every seed gets the same
set of inter-arrival gaps, drawn once from ``traffic["schedule_seed"]`` and
scaled to fill the window, in its own order, so runs differ in order and
inputs but not in the amount of work.  A request is timed from its due time
to when a collector thread, waiting on the handles in submission order, has
its outputs on the host.  A request that fails, or has not come back a
minute after the window closed, counts as failed.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

WAIT_AFTER_S = 60.0  # how long answers may come in after the window closes
MAX_WINDOW_S = 60  # the admission queue holds a whole window of arrivals
POLL_S = 0.5


def setup(model, traffic: dict, requests: list[dict], spans) -> dict:
    from repro.serve import ModelServer

    slots, depth = traffic["batch_slots"], traffic["stream_depth"]
    capacity = int(traffic["rate_rps"] * MAX_WINDOW_S) + slots * depth
    server = ModelServer(
        model.compiled, model.params, batch_slots=slots, stream_depth=depth,
        queue_capacity=capacity, policy="reject",
    )
    server.start()
    server.warmup(requests[0])
    for h in [server.submit(x) for x in requests[: slots * depth]]:  # a whole round
        h.result(timeout=WAIT_AFTER_S)
    compile_s = sum(e["compile_us"] for e in server.stats()["entries"]) * 1e-6
    return {"server": server, "traffic": traffic, "requests": requests, "spans": spans, "compile_s": compile_s}


def schedule(traffic: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times in seconds from the window's start: the fixed set of gaps
    in this seed's order."""
    n = max(1, round(traffic["rate_rps"] * seconds))
    gaps = np.random.default_rng(traffic["schedule_seed"]).exponential(1.0, size=n)
    return np.cumsum(rng.permutation(gaps * (seconds / gaps.sum())))


def _collect(handles: queue.Queue, done: np.ndarray, outs: list, deadline: list) -> None:
    """Wait on each handle in submission order; stop waiting on one that
    has not answered by ``deadline[0]`` (set once the window closes)."""
    while (item := handles.get()) is not None:
        i, h = item
        while time.perf_counter() < deadline[0]:
            try:
                outs[i] = h.result(timeout=POLL_S)
            except TimeoutError:
                continue
            except Exception:  # a failed request: counted, never raised
                break
            done[i] = time.perf_counter()
            break


def window(state: dict, seconds: float, rng: np.random.Generator) -> dict:
    server, spans, requests = state["server"], state["spans"], state["requests"]
    due_s = schedule(state["traffic"], seconds, rng)
    n = len(due_s)
    pick = rng.integers(len(requests), size=n)
    done = np.full(n, np.nan)
    outs: list = [None] * n
    lag = np.zeros(n)
    rejected = 0
    deadline = [float("inf")]
    handles: queue.Queue = queue.Queue()
    collector = threading.Thread(target=_collect, args=(handles, done, outs, deadline), daemon=True)
    collector.start()
    before = server.stats()
    t0 = time.perf_counter() + 0.01
    due = t0 + due_s
    with spans("window"):
        for i in range(n):
            delay = due[i] - time.perf_counter()
            if delay > 0:
                with spans("sleep"):
                    time.sleep(delay)
            t = time.perf_counter()
            lag[i] = t - due[i]
            try:
                with spans("submit"):
                    h = server.submit(requests[pick[i]])
            except Exception:  # rejected by admission: a failed request
                rejected += 1
                continue
            handles.put((i, h))
        t_end = max(t0 + seconds, time.perf_counter())
    in_window = int(np.count_nonzero(done <= t_end))
    end_stats = server.stats()
    deadline[0] = t_end + WAIT_AFTER_S
    handles.put(None)
    collector.join(WAIT_AFTER_S + 5)
    answered = ~np.isnan(done)
    latency = np.where(answered, done - due, deadline[0] - due)
    after = server.stats()
    return {
        "window": (t0, t_end),
        "attempted": n,
        "completed": int(answered.sum()),
        "completed_in_window": in_window,
        "queue_at_end": int(end_stats["submitted"] - end_stats["completed"]),
        "failed": int(n - answered.sum()),
        "unanswered": int(n - answered.sum() - rejected),
        "answers": [(pick[answered], [outs[i] for i in np.flatnonzero(answered)])],
        "latencies_s": latency,
        "lags_s": lag,
        "server": {k: after[k] - before[k] for k in ("submitted", "completed", "batches", "rounds")}
        | {"batch_slots": after["batch_slots"]},
    }


def close(state: dict) -> None:
    state["server"].close()
    state.clear()
