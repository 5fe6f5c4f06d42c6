"""Cross-request batch packing: vmap the fused segment executors.

One compiled schedule serves ``B`` concurrent users by stacking their
inputs along a leading *slot* axis and mapping every
:class:`~repro.backend.lower.LoweredSegment` executor over it with
``jax.vmap`` — the per-example shapes inside each executor are exactly
the unbatched ones, so the winning LOMA tiles, the fused epilogues and
the memory plan all apply unchanged, and per-request outputs stay
bit-exact with running ``CompiledModel.run`` one request at a time
(held by tests/test_serve.py and, on every target and MLPerf-Tiny net, by
tests/conformance/test_batched.py).

:meth:`BatchedModel.run_batch` fuses the whole batched graph into ONE
AOT-compiled executable per batch shape (``jax.jit(...).lower().compile()``
with params baked as constants, cached per ``(params identity, stacked
input signature)``), so a steady-state replica pays one host dispatch per
batch of users.
"""

from __future__ import annotations

import threading
import time
from typing import TYPE_CHECKING, Sequence

import jax
import numpy as np

from repro import obs

if TYPE_CHECKING:  # repro.backend stays import-light; duck-typed at runtime
    from repro.backend.runtime import CompiledModel

__all__ = ["BatchedModel"]


def _host_row(v):
    """``v`` as a host numpy array, or None where it is not host data: a
    ``jax.Array`` or another array type keeps the per-row path.  Bare
    Python data takes float32, as ``as_input_array`` gives it."""
    if isinstance(v, (np.ndarray, np.generic)):
        return v
    if isinstance(v, jax.Array) or hasattr(v, "dtype"):
        return None
    return np.asarray(v, np.float32)


def _stack_rows(rows: list) -> tuple[jax.Array, int]:
    """One input's rows stacked along a new leading axis, and the number
    of host→device copies made.

    Host rows of one shape and dtype are stacked into a fresh host buffer
    (a batch still being copied never shares it) and put on the default
    device in one uncommitted copy, canonicalised as ``jnp.asarray`` would.
    Otherwise each host row is copied on its own and the device stacks."""
    from repro.backend.runtime import as_input_array

    host = [_host_row(v) for v in rows]
    if all(h is not None for h in host) and len({(h.shape, h.dtype) for h in host}) == 1:
        return jax.device_put(np.stack(host)), 1
    return (
        jax.numpy.stack([as_input_array(v) for v in rows]),
        sum(not isinstance(v, jax.Array) for v in rows),
    )


class BatchedModel:
    """A CompiledModel's executors vmapped over a request-slot axis."""

    def __init__(self, compiled: "CompiledModel"):
        self.compiled = compiled
        # (params id, input signature) -> (params ref, compiled executable,
        # stats row); the strong params ref keeps id() stable while the
        # entry lives
        self._entries: dict[tuple, tuple[dict, object, dict]] = {}
        self._lock = threading.Lock()

    @property
    def graph(self):
        return self.compiled.graph

    # -- stacking -------------------------------------------------------
    def stack(self, inputs_list: Sequence[dict]) -> dict:
        """Stack per-request input dicts along a new leading slot axis.

        An input whose rows are all host arrays of one shape and dtype is
        stacked on the host and copied to the device once; any other input
        copies each host row on its own and stacks on the device, so rows
        already on the device stay there.  Traced as ``batch.stack`` with
        ``rows``; ``h2d``, the number of input tensors that came as host
        arrays (a ``jax.Array`` is already on the device); and ``copies``,
        the host→device transfers the stack made."""
        if not inputs_list:
            raise ValueError("cannot stack an empty batch")
        tr = obs.get_tracer()
        if not tr.enabled:
            return self._stack(inputs_list)[0]
        with tr.span("batch.stack", rows=len(inputs_list)) as sp:
            stacked, copies = self._stack(inputs_list)
            sp.set(
                h2d=sum(
                    not isinstance(x[k], jax.Array)
                    for x in inputs_list
                    for k in self.graph.inputs
                ),
                copies=copies,
            )
        return stacked

    def _stack(self, inputs_list: Sequence[dict]) -> tuple[dict, int]:
        stacked, copies = {}, 0
        for k in self.graph.inputs:
            stacked[k], n = _stack_rows([x[k] for x in inputs_list])
            copies += n
        return stacked, copies

    @staticmethod
    def unstack(outputs: dict, n: int) -> list[dict]:
        """Split stacked graph outputs back into per-request dicts.

        Rows are numpy views over one host transfer per output tensor —
        per-row device slicing would cost ``n`` tiny dispatches per
        tensor, which at serving rates dwarfs the compute itself.
        Traced as ``batch.unstack``."""
        with obs.span("batch.unstack"):
            host = {k: np.asarray(v) for k, v in outputs.items()}
            return [{k: v[i] for k, v in host.items()} for i in range(n)]

    # -- one AOT entry per batch shape ----------------------------------
    def _signature(self, stacked: dict) -> tuple:
        return tuple(
            (k, tuple(v.shape), str(v.dtype)) for k, v in sorted(stacked.items())
        )

    def entry(self, params: dict, stacked: dict):
        """The AOT-compiled whole-batched-graph executable for this
        ``(params, batch shape)`` signature, built on first use."""
        sig = (id(params), self._signature(stacked))
        with self._lock:
            hit = self._entries.get(sig)
            if hit is not None and hit[0] is params:
                obs.counter("serve.entry_hits").inc()
                return hit[1]
        segs = self.compiled.segments
        outputs = self.graph.outputs
        input_names = tuple(self.graph.inputs.keys())

        def whole_batch(batch_inputs: dict) -> dict:
            # every segment executor vmapped over the slot axis; params
            # stay unbatched (in_axes None): all slots share one model
            env = dict(batch_inputs)
            for ls in segs:
                with jax.named_scope(f"seg{ls.index}.{ls.module}"):
                    env[ls.output_name] = jax.vmap(
                        ls.fn, in_axes=(None,) + (0,) * len(ls.input_names)
                    )(
                        ls.params_slice(params),
                        *[env[nm] for nm in ls.input_names],
                    )
            return {o: env[o] for o in outputs}

        t0 = time.perf_counter()
        lowered = jax.jit(whole_batch).lower(
            {k: stacked[k] for k in input_names}
        )
        t1 = time.perf_counter()
        executable = lowered.compile()
        t2 = time.perf_counter()
        obs.counter("serve.entry_misses").inc()
        row = {
            "batch": int(next(iter(stacked.values())).shape[0]),
            "signature": [list(map(str, s)) for s in sig[1]],
            "trace_us": (t1 - t0) * 1e6,
            "compile_us": (t2 - t1) * 1e6,
        }
        with self._lock:
            self._entries[sig] = (params, executable, row)
        return executable

    def run_batch(self, params: dict, inputs_list: Sequence[dict]) -> list[dict]:
        """Serve ``inputs_list`` as one packed batch (one host dispatch);
        returns per-request output dicts, row ``i`` bit-exact with
        ``CompiledModel.run(params, inputs_list[i])``."""
        return self.unstack(self.run_batch_async(params, inputs_list), len(inputs_list))

    def run_batch_async(self, params: dict, inputs_list: Sequence[dict]):
        """Dispatch a packed batch without blocking: returns the stacked
        output dict (jax arrays still materialising on device) — the
        server's in-flight window blocks on them in completion order.
        The entry lookup and the executable call are traced as
        ``batch.dispatch``."""
        stacked = self.stack(inputs_list)
        with obs.span("batch.dispatch"):
            return self.entry(params, stacked)(stacked)

    def entry_stats(self) -> list[dict]:
        """JSON-safe trace/compile cost per AOT batch entry."""
        with self._lock:
            return [dict(row) for (_, _, row) in self._entries.values()]
