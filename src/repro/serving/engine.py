"""Batched serving engine: slot-based continuous batching (lite).

* Requests queue up; the engine packs up to ``batch_slots`` prompts,
  left-pads to a common prefill length, prefills once, then decodes all
  slots in lock-step with per-slot stop handling.
* Finished slots are refilled from the queue between decode steps
  (continuous batching without paged attention — cache slots are
  per-batch-row, so a new request reuses a finished row by re-prefilling
  its row into the shared cache via the single-row prefill path).  A
  queued prompt longer than the batch's current position cannot join
  lock-step mid-flight; it parks in ``_pending`` and opens the next
  batch instead.
* A request that hits ``max_len`` before ``max_new_tokens`` is returned
  with ``truncated=True`` and a :class:`TruncationWarning` (silently
  under-producing tokens is how decode bugs hide).
* Greedy or temperature sampling.  Greedy tokens are picked on the
  device inside the decode step and feed the next step there; each step
  sends its (B,) int32 tokens to the host, with the routed rows its held
  experts computed, in one transfer.  Logits cross to the host only for
  the rows a caller keeps (``Lockstep.keep``): the temperature-sampled
  rows, or the rows a check compares.
* The decode step donates its cache.  Prefill goes in row groups of
  ``prefill_rows`` (one pass for the whole batch by default), each written
  into the batch's cache, so a batch whose one-pass prefill would not fit
  the device still prefills.
* :meth:`ServeEngine.steps` streams the lock-step decode, one step per
  iteration, so that a caller can stop after a deadline; :meth:`run` goes
  through it too.  Streaming dispatches step t+1 before it waits on step
  t's tokens, unless the caller edits rows between steps (refill,
  sampling), as ``run`` does.
* Traced (``repro.obs``) as ``lm.prefill`` (``rows``, ``tokens``) a row
  group, ``lm.decode`` (``slots``, ``pos``, ``kv_read``: the cached
  positions each global attention layer reads, :func:`repro.models.kv_read`)
  the dispatch of a step, and
  ``lm.fetch`` (``tokens``, ``expert_rows``) the host's wait for a step's
  tokens.

This is the serving driver used by the decode/long-context dry-run
cells; at pod scale the same engine runs under pjit with the
autosharded rules (weights TP/EP-sharded, cache batch-sharded).
Request-level (whole-graph, non-autoregressive) serving lives in
:mod:`repro.serve`.
"""

from __future__ import annotations

import queue
from dataclasses import dataclass, field
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import LM, kv_read
from repro.obs.log import MatchWarning
from repro.obs.log import warn as obs_warn

__all__ = ["Lockstep", "Request", "ServeEngine", "TruncationWarning"]


class TruncationWarning(MatchWarning):
    """A request ran out of cache headroom (``pos >= max_len``) before
    producing ``max_new_tokens``; its ``truncated`` flag is set."""


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False
    truncated: bool = False


@dataclass
class Lockstep:
    """A batch decoding in lock step, all on the device but ``pos``."""

    cache: Any
    tokens: jax.Array  # (B,) int32: each row's next input
    pos: int  # the position the next step decodes
    logits: jax.Array  # (B, V) float32 at the prefill's last position
    keep: jax.Array  # (K,) int32 rows whose logits each step returns


class ServeEngine:
    def __init__(
        self,
        model: LM,
        params,
        *,
        batch_slots: int = 4,
        max_len: int = 256,
        rng_seed: int = 0,
        prefill_rows: int = 0,
    ):
        self.model = model
        self.params = params
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.prefill_rows = prefill_rows  # rows a prefill pass takes; 0 -> all
        self.rng = np.random.default_rng(rng_seed)
        self._queue: "queue.Queue[Request]" = queue.Queue()
        self._pending: list[Request] = []  # popped but not yet slotted
        axes = model.cache_axes()
        # the global attention layers' cache length (0 without one)
        self._kv_len = max_len if "attn" in model.cfg.layer_pattern() else 0

        def lm_prefill(params, cache, tokens, row):
            """Prefill ``tokens`` (g, S) into rows [row, row + g) of the
            batch's cache; their last-position logits in float32."""
            logits, part = model.prefill(params, tokens, max_len=max_len)
            return logits.astype(jnp.float32), _put_rows(cache, part, row, axes)

        def lm_decode(params, cache, tokens, pos, keep):
            """One greedy step: the next tokens (device), the same tokens
            with the held experts' routed rows (for the host), the kept
            rows' logits, and the cache."""
            logits, cache, rows = model.decode(params, cache, tokens, pos)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = jnp.concatenate([nxt, rows.astype(jnp.int32)])
            return nxt, out, logits[keep].astype(jnp.float32), cache

        self._prefill = jax.jit(lm_prefill, donate_argnums=(1,))
        self._step = jax.jit(lm_decode, donate_argnums=(1,))
        # serving counters: decode iterations paid and slots recycled —
        # the refill regression test pins their relationship
        self.decode_steps = 0
        self.refills = 0

    def submit(self, req: Request) -> None:
        self._queue.put(req)

    def _pop(self) -> Request | None:
        """One queued request, or None — never empty()-then-get(): with
        concurrent submitters the queue can drain between the two calls,
        and get() would then block forever."""
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            return None

    def _take_batch(self) -> list[Request]:
        out = self._pending[: self.batch_slots]
        del self._pending[: len(out)]
        while len(out) < self.batch_slots:
            r = self._pop()
            if r is None:
                break
            out.append(r)
        return out

    def _next_fitting(self, pos: int) -> Request | None:
        """A waiting request whose prompt fits the lock-step position
        (left-padded to width ``pos``); longer prompts park in
        ``_pending`` for the next batch."""
        for j, r in enumerate(self._pending):
            if len(r.prompt) <= pos:
                return self._pending.pop(j)
        while True:
            r = self._pop()
            if r is None:
                return None
            if len(r.prompt) <= pos:
                return r
            self._pending.append(r)

    def run(self) -> list[Request]:
        """Serve everything currently queued; returns finished requests."""
        finished: list[Request] = []
        while True:
            batch = self._take_batch()
            if not batch:
                return finished
            finished.extend(self._serve_batch(batch))

    # -- prefill and the lock-step stream --------------------------------
    def prefill(self, prompts: np.ndarray, keep=()) -> Lockstep:
        """Prefill ``prompts`` (B, S) int32, ``prefill_rows`` rows a pass,
        into a new cache of ``max_len`` positions; the batch ready to
        decode at position S with its greedy first tokens.  ``keep``: the
        rows whose logits each decode step returns."""
        B, S = prompts.shape
        cache = self.model.init_cache(B, self.max_len)
        g = self.prefill_rows or B
        tr = obs.get_tracer()
        logits = []
        for r in range(0, B, g):
            rows = prompts[r : r + g]
            with tr.span("lm.prefill", rows=len(rows), tokens=rows.size):
                lg, cache = self._prefill(self.params, cache, jnp.asarray(rows), np.int32(r))
            logits.append(lg)
        logits = jnp.concatenate(logits)
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return Lockstep(cache, tokens, S, logits, jnp.asarray(keep, jnp.int32))

    def warm(self, batch: int, prompt_len: int, keep: int = 0) -> None:
        """Compile the prefill passes and the decode step for ``batch``
        prompts of ``prompt_len`` (``keep`` rows' logits kept), without
        running them or allocating their cache."""
        cache = jax.eval_shape(lambda: self.model.init_cache(batch, self.max_len))
        g = self.prefill_rows or batch
        for n in {min(g, batch - r) for r in range(0, batch, g)}:
            rows = jax.ShapeDtypeStruct((n, prompt_len), jnp.int32)
            self._prefill.lower(self.params, cache, rows, np.int32(0)).compile()
        tokens, kept = (jax.ShapeDtypeStruct((n,), jnp.int32) for n in (batch, keep))
        self._step.lower(self.params, cache, tokens, np.int32(prompt_len), kept).compile()

    def steps(self, ls: Lockstep, *, ahead: bool = True) -> Iterator[tuple[np.ndarray, jax.Array]]:
        """Decode ``ls`` in lock step until ``max_len``; yields each step's
        (B,) tokens on the host and its kept rows' logits (K, V) on the
        device.  ``ahead`` dispatches the next step before waiting on this
        one's tokens; without it the caller may edit ``ls`` (tokens, cache,
        keep) between steps."""
        B = ls.tokens.shape[0]
        tr = obs.get_tracer()

        def dispatch():
            with tr.span("lm.decode", slots=B, pos=ls.pos, kv_read=kv_read(ls.pos, self._kv_len)):
                ls.tokens, out, lg, ls.cache = self._step(
                    self.params, ls.cache, ls.tokens, np.int32(ls.pos), ls.keep
                )
            ls.pos += 1
            self.decode_steps += 1
            return out, lg

        nxt = None
        while nxt is not None or ls.pos < self.max_len:
            out, lg = nxt or dispatch()
            nxt = dispatch() if ahead and ls.pos < self.max_len else None
            with tr.span("lm.fetch", tokens=B) as sp:
                host = np.asarray(out)
                sp.set(expert_rows=int(host[B:].sum()))
            yield host[:B], lg

    def _sample_hot(self, ls: Lockstep, rows, reqs: list[Request], tok: np.ndarray, logits) -> np.ndarray:
        """``tok``: the device's greedy tokens of ``reqs``, which sit in
        rows ``rows`` of ``ls``.  The temperature requests' tokens are
        sampled on the host from ``logits`` (one row each, in order) and
        written back into ``ls.tokens``."""
        hot = [j for j, r in enumerate(reqs) if r.temperature > 0]
        if hot:
            tok = np.array(tok)
            lg = np.asarray(logits, np.float32)
            for n, j in enumerate(hot):
                tok[j] = self._sample(lg[n], reqs[j].temperature)
            ls.tokens = ls.tokens.at[np.asarray(rows)[hot]].set(tok[hot])
        return tok

    @staticmethod
    def _keep(slots: list[Request]) -> jax.Array:
        """The rows whose logits the host samples from."""
        return jnp.asarray([i for i, r in enumerate(slots) if r.temperature > 0], jnp.int32)

    # -- single-row prefill path (slot refill) --------------------------
    def _refill_slot(self, req: Request, i: int, ls: Lockstep) -> int:
        """Prefill ``req`` as a single row (left-padded to the lock-step
        width ``ls.pos``) into slot ``i`` of the cache, and return its
        first token."""
        row = np.zeros((1, ls.pos), np.int32)
        row[0, ls.pos - len(req.prompt) :] = req.prompt
        with obs.span("lm.prefill", rows=1, tokens=row.size):
            lg, ls.cache = self._prefill(self.params, ls.cache, jnp.asarray(row), np.int32(i))
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        ls.tokens = ls.tokens.at[i].set(tok[0])
        self.refills += 1
        return int(self._sample_hot(ls, [i], [req], np.asarray(tok), lg)[0])

    def _serve_batch(self, reqs: list[Request]) -> list[Request]:
        B = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        # left-pad with token 0; positions still 0..plen-1 (pad tokens
        # attend causally but contribute negligibly for smoke-scale tests)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt) :] = r.prompt

        slots = list(reqs)
        ls = self.prefill(toks, keep=self._keep(slots))
        live = [True] * B
        served: list[Request] = []
        cur = self._sample_hot(ls, range(B), slots, np.asarray(ls.tokens), ls.logits[ls.keep])
        for i, r in enumerate(slots):
            r.out_tokens.append(int(cur[i]))
        stream = self.steps(ls, ahead=False)

        while True:
            # retire finished slots and refill them from the queue before
            # paying the next lock-step decode; fixpoint, because a
            # refilled request can itself already be satisfied
            changed = True
            while changed:
                changed = False
                for i, r in enumerate(slots):
                    if live[i] and len(r.out_tokens) >= r.max_new_tokens:
                        live[i] = False
                        r.done = True
                        served.append(r)
                        changed = True
                        if ls.pos < self.max_len:
                            nxt = self._next_fitting(ls.pos)
                            if nxt is not None:
                                tok = self._refill_slot(nxt, i, ls)
                                slots[i] = nxt
                                live[i] = True
                                nxt.out_tokens.append(tok)
                                ls.keep = self._keep(slots)
            if not any(live):
                return served
            if ls.pos >= self.max_len:
                trunc = [slots[i].rid for i in range(B) if live[i]]
                for i in range(B):
                    if live[i]:
                        slots[i].truncated = True
                        slots[i].done = True
                        served.append(slots[i])
                obs_warn(
                    f"requests {trunc} hit max_len={self.max_len} at "
                    f"position {ls.pos} before max_new_tokens; returned "
                    "truncated (raise max_len or shorten prompts)",
                    TruncationWarning,
                )
                return served
            cur, lg = next(stream)
            cur = self._sample_hot(ls, range(B), slots, cur, lg)
            for i, r in enumerate(slots):
                if live[i] and len(r.out_tokens) < r.max_new_tokens:
                    r.out_tokens.append(int(cur[i]))

    def _sample(self, logits: np.ndarray, temperature: float) -> int:
        p = logits / temperature
        p = np.exp(p - p.max())
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))


def _put_rows(cache, part, row, axes):
    """Write ``part`` (a cache of g rows) into rows [row, row + g) of
    ``cache``.  Leaves without a batch axis (attention's position count)
    agree by construction and are taken from ``part``."""
    is_axes = lambda a: isinstance(a, tuple)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    ax = jax.tree_util.tree_leaves(axes, is_leaf=is_axes)
    out = []
    for leaf, new, a in zip(leaves, jax.tree_util.tree_leaves(part), ax):
        if "batch" in a:
            out.append(jax.lax.dynamic_update_slice_in_dim(leaf, new.astype(leaf.dtype), row, a.index("batch")))
        else:
            out.append(new)
    return jax.tree_util.tree_unflatten(treedef, out)
