"""LM decode: a batch prefilled in set-up, then decoded greedily in lock
step through ``ServeEngine.steps`` for the window.

Set-up compiles the prefill and decode step (``compile_s``), prefills the
batch's prompts in row groups (``prefill_s``) and reads the first tokens.
The window streams steps, each dispatched before the host waits on the
one before it.  An inference is one generated token whose id reached the
host inside the window; the run lasts until the last counted step reached
the host.  The check's logits stay on the device until the window ends:
the prefill's last position and ``check_rows / check_seqs - 1`` decode
steps drawn evenly from the window's steps (a reservoir sample from the
run's seed), for the ``check_seqs`` rows, with the token the engine chose
at each of those positions.  A window that would decode past
``max_len`` fails the run.
"""

from __future__ import annotations

import sys
import time

import jax
import numpy as np
from jax._src import monitoring


def _memory(stage: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    held, peak = (stats.get(k, 0) / 1e9 for k in ("bytes_in_use", "peak_bytes_in_use"))
    print(f"benchmark: device memory {stage}: {held:.3f} GB held, peak {peak:.3f} GB", file=sys.stderr)


def setup(model, traffic: dict, requests: dict, spans) -> dict:
    _memory("after the weights")
    eng = model.engine(traffic)
    B, S = requests["prompts"].shape
    keep = requests["check_seqs"]
    t0 = time.perf_counter()
    eng.warm(B, S, len(keep))
    t1 = time.perf_counter()
    ls = eng.prefill(requests["prompts"], keep=keep)
    first = np.asarray(ls.tokens)
    model.parts["prefill_s"] = time.perf_counter() - t1
    _memory("after the prefill")
    return {"engine": eng, "lock": ls, "first": first, "keep": keep, "prompts": requests["prompts"], "traffic": traffic,
            "spans": spans, "prefill_logits": ls.logits[ls.keep], "compile_s": t1 - t0}


def window(state: dict, seconds: float, rng: np.random.Generator) -> dict:
    eng, ls, spans = state["engine"], state["lock"], state["spans"]
    keep = state["keep"]
    B, start = ls.tokens.shape[0], ls.pos
    k = state["traffic"]["check_rows"] // len(keep) - 1
    kept: list = []  # (step, logits on the device), a reservoir of k steps
    tokens: list[np.ndarray] = []
    compiles: list[str] = []

    def on_compile(event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    dispatched = eng.decode_steps
    stream = eng.steps(ls)
    monitoring.register_event_duration_secs_listener(on_compile)
    t0 = last = time.perf_counter()
    t_end = t0 + seconds
    try:
        with spans("window"):
            for step, (tok, lg) in enumerate(stream):
                now = time.perf_counter()
                if now > t_end:
                    break
                tokens.append(tok)
                last = now
                j = step if step < k else int(rng.integers(step + 1))
                if j < k:
                    kept[j : j + 1] = [(step, lg)]
            else:
                raise RuntimeError(f"decode reached max_len={eng.max_len} inside the window; raise max_len or shorten it")
    finally:
        monitoring.unregister_event_duration_listener(on_compile)
    stream.close()
    jax.block_until_ready(ls.cache)
    steps = len(tokens)
    if compiles:
        print(f"benchmark: {len(compiles)} compilations inside the window", file=sys.stderr)
    kept.sort(key=lambda sl: sl[0])
    rows = np.concatenate([state["first"][keep, None], np.stack(tokens, axis=1)[keep]], axis=1) if steps else None
    last_step = kept[-1][0] if kept else -1
    seq = np.concatenate([state["prompts"][keep], rows[:, : last_step + 1]], axis=1) if steps else None
    logits = np.stack([np.asarray(state["prefill_logits"])] + [np.asarray(lg) for _, lg in kept], axis=1)
    positions = np.broadcast_to(np.array([start - 1] + [start + s for s, _ in kept]), (len(keep), len(kept) + 1))
    # the token the engine chose at each compared position: the next one in
    chosen = np.stack([state["first"][keep]] + [tokens[s][keep] for s, _ in kept], axis=1)
    return {
        "window": (t0, t_end),
        "attempted": B * (eng.decode_steps - dispatched),
        "completed": B * steps,
        "last_done": last,
        "failed": 0,
        "unanswered": 0,  # lock step: a step whose tokens reached the host answered every row
        "positions": (start, start + steps),
        "window_compiles": len(compiles),
        "answers": {"tokens": seq, "positions": positions, "logits": logits, "chosen": chosen},
    }


def close(state: dict) -> None:
    state.clear()
