"""Whole-graph one-jit AOT executor: kill per-segment host dispatch.

``CompiledModel.run`` walks the lowered segments in a Python loop — one
jitted dispatch per segment — so on sub-millisecond MLPerf-Tiny nets the
host round-trips dominate end-to-end latency.  This module fuses ALL
lowered segments into **one** XLA program executed without returning to
Python between segments: the moral equivalent of upstream MATCH's
generated C graph runner around a static USMP memory plan
(``static_mem_plan="hill_climb"``).

Design points:

* **Segment bodies are reused, never re-derived.**  The tracer calls the
  exact per-segment ``LoweredSegment.fn`` executors (jit-of-jit inlines
  them), so bit-exactness with ``CompiledModel.run`` — and therefore
  with the reference interpreter — is inherited by construction.
* **Weights are baked as constants.**  Params are closed over at trace
  time, exactly like MATCH's generated C links weights into ``.rodata``.
  This is also what lets the Pallas GEMM segments trace: their requant
  shift is a *static* kernel argument read from concrete params.
  Executables are cached per (params identity, input shapes/dtypes);
  passing a different params dict triggers a fresh compile.
* **AOT compile, paid once.**  ``jax.jit(...).lower(...).compile()``
  produces a held executable keyed by the input signature; ``warmup()``
  pays trace+compile explicitly, ``run()`` reuses the executable.
* **Intermediates are left to XLA.**  Every intermediate is an SSA value
  inside the one program, so XLA's buffer assignment re-derives the
  aliasing the static :class:`~repro.backend.memory.MemoryPlan` decided;
  ``stats()`` reports the plan's aliasing beside the share of its bytes
  the executable holds.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

if TYPE_CHECKING:  # avoid circular imports at module load
    from .runtime import CompiledModel

__all__ = [
    "AotCompileError",
    "AotEntry",
    "AotModel",
    "compile_aot",
]


class AotCompileError(RuntimeError):
    """The compiled model cannot be fused into one AOT executable."""


def _as_input(v):
    """Input coercion shared with ``CompiledModel.run``: preserve the
    caller's dtype (int8/quantized inputs stay integer), default bare
    Python data to float32."""
    from .runtime import as_input_array

    return as_input_array(v)


def _sig_of(inputs: dict) -> tuple:
    """Hashable (name, shape, dtype) input signature, the AOT cache key."""
    return tuple(
        sorted((k, tuple(v.shape), str(v.dtype)) for k, v in inputs.items())
    )


@dataclass
class AotEntry:
    """One compiled executable for one (params, input-signature) pair."""

    signature: tuple
    executable: object
    trace_us: float
    compile_us: float
    params: dict = field(repr=False)  # strong ref: keeps the bake valid
    calls: int = 0

    def executable_stats(self) -> dict:
        """Best-effort executable introspection (backend-dependent)."""
        out: dict = {}
        try:
            ma = self.executable.memory_analysis()
            for k in (
                "generated_code_size_in_bytes",
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
            ):
                v = getattr(ma, k, None)
                if v is not None:
                    out[k] = int(v)
        except Exception:  # pragma: no cover - backend without the API
            pass
        return out

    def to_dict(self) -> dict:
        return {
            "inputs": [list(s) for s in self.signature],
            "trace_us": self.trace_us,
            "compile_us": self.compile_us,
            "calls": self.calls,
            "executable": self.executable_stats(),
        }


class AotModel:
    """A CompiledModel fused into one jitted whole-graph program.

    Intermediate buffers stay inside the executable, where XLA's buffer
    assignment owns them; ``stats()`` reports the share of the static
    :class:`MemoryPlan`'s bytes that this covers.
    """

    def __init__(self, compiled: "CompiledModel"):
        self.compiled = compiled
        self._entries: dict[tuple, AotEntry] = {}
        self._lock = threading.Lock()
        self._dispatch_overhead: dict | None = None

    # -- introspection ---------------------------------------------------
    @property
    def graph(self):
        return self.compiled.graph

    @property
    def target(self):
        return self.compiled.target

    # -- compilation -----------------------------------------------------
    def _entry_key(self, params: dict, sig: tuple) -> tuple:
        # params are baked as constants, so the executable is only valid
        # for the exact dict it was traced with; entries hold a strong
        # ref so the id cannot be recycled while the cache lives
        return (id(params), sig)

    def warmup(self, params: dict, inputs: dict) -> AotEntry:
        """Trace + AOT-compile the whole-graph executable for these input
        shapes/dtypes (and bake ``params``).  Idempotent per signature;
        ``run`` calls it implicitly on a cache miss."""
        coerced = {k: _as_input(v) for k, v in inputs.items()}
        sig = _sig_of(coerced)
        key = self._entry_key(params, sig)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                obs.counter("aot.cache_hits").inc()
                return entry
            obs.counter("aot.cache_misses").inc()
            entry = self._compile(params, coerced, sig)
            self._entries[key] = entry
            return entry

    def _compile(self, params: dict, inputs: dict, sig: tuple) -> AotEntry:
        abstract = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in inputs.items()
        }
        jitted = jax.jit(self._build_xla_fn(params))
        with obs.span(
            "aot.compile", cat="compile", graph=self.graph.name,
            target=self.target.name,
        ) as sp:
            t0 = time.perf_counter()
            try:
                lowered = jitted.lower(abstract)
            except Exception as e:
                raise AotCompileError(
                    f"whole-graph trace failed for {self.graph.name} on "
                    f"{self.target.name}: {e}"
                ) from e
            t1 = time.perf_counter()
            executable = lowered.compile()
            t2 = time.perf_counter()
            sp.set(trace_us=(t1 - t0) * 1e6, compile_us=(t2 - t1) * 1e6)
        return AotEntry(
            signature=sig,
            executable=executable,
            trace_us=(t1 - t0) * 1e6,
            compile_us=(t2 - t1) * 1e6,
            params=params,
        )

    def _build_xla_fn(self, params: dict) -> Callable:
        """Whole program with SSA intermediates: segments inlined in
        schedule order, buffer reuse owned by XLA's assignment."""
        segments = self.compiled.segments
        outputs = self.graph.outputs

        def whole(inputs):
            env = dict(inputs)
            for ls in segments:
                xs = [env[nm] for nm in ls.input_names]
                with jax.named_scope(f"seg{ls.index}.{ls.module}"):
                    env[ls.output_name] = ls.fn(ls.params_slice(params), *xs)
            return {o: env[o] for o in outputs}

        return whole

    # -- execution -------------------------------------------------------
    def run(self, params: dict, inputs: dict) -> dict:
        """Execute the whole graph in one XLA dispatch.

        Bit-exact with ``CompiledModel.run(params, inputs)`` (same fused
        segment bodies, inlined).  First call per input signature pays
        trace + compile (see :meth:`warmup`); subsequent calls reuse the
        held executable.  With tracing on, the input coercion is traced
        as ``aot.coerce`` and the entry lookup plus the executable call
        as ``aot.dispatch``.
        """
        tr = obs.get_tracer()
        if tr.enabled:
            with tr.span("aot.coerce"):
                coerced = {k: _as_input(v) for k, v in inputs.items()}
            with tr.span("aot.dispatch", graph=self.graph.name):
                return self._dispatch(params, coerced)
        return self._dispatch(params, {k: _as_input(v) for k, v in inputs.items()})

    def _dispatch(self, params: dict, coerced: dict) -> dict:
        entry = self.warmup(params, coerced)
        entry.calls += 1
        return dict(entry.executable(coerced))

    def verify(self, params: dict, inputs: dict) -> float:
        """Max |AOT - per-segment CompiledModel.run| over graph outputs
        (0.0 = bit-exact)."""
        ref = self.compiled.run(params, inputs)
        got = self.run(params, inputs)
        err = 0.0
        for k in ref:
            err = max(err, float(jnp.max(jnp.abs(ref[k] - got[k]))))
        return err

    # -- measurement -----------------------------------------------------
    def measure_dispatch_overhead(
        self, params: dict, inputs: dict, *, repeats: int = 7
    ) -> dict:
        """Quantify the per-segment host-dispatch cost this executor
        eliminates: median wall-clock of the per-segment Python loop vs
        the one-dispatch AOT call (both warm), divided by segment count.
        The result is recorded and shipped in ``stats()`` /
        ``report_dict()["aot"]``."""
        self.warmup(params, inputs)

        def once(fn) -> float:
            t0 = time.perf_counter()
            jax.block_until_ready(list(fn(params, inputs).values()))
            return (time.perf_counter() - t0) * 1e6

        once(self.compiled.run), once(self.run)  # warm both paths
        seg_us = float(np.median([once(self.compiled.run) for _ in range(repeats)]))
        aot_us = float(np.median([once(self.run) for _ in range(repeats)]))
        n = max(1, len(self.compiled.segments))
        self._dispatch_overhead = {
            "repeats": repeats,
            "segments": n,
            "per_segment_path_us": seg_us,
            "aot_us": aot_us,
            "dispatch_overhead_us": seg_us - aot_us,
            "dispatch_overhead_per_segment_us": (seg_us - aot_us) / n,
            "speedup": seg_us / max(aot_us, 1e-9),
        }
        return dict(self._dispatch_overhead)

    def stats(self) -> dict:
        """JSON-safe AOT report: trace/compile cost, executable size, the
        plan's aliasing, measured dispatch overhead (the
        ``report_dict()["aot"]`` payload).  ``intermediate_share`` is the
        share of the plan's bytes held by intermediates, which never leave
        the executable: XLA's buffer assignment owns them."""
        plan = self.compiled.memory_plan
        io_names = set(self.graph.inputs) | set(self.graph.outputs)
        total = sum(b.nbytes for b in plan.buffers.values())
        internal = sum(
            b.nbytes for n, b in plan.buffers.items() if n not in io_names
        )
        return {
            "segments": len(self.compiled.segments),
            "intermediate_share": internal / max(total, 1),
            "plan_aliasing": plan.aliasing_summary(),
            "entries": [e.to_dict() for e in self._entries.values()],
            "dispatch_overhead": self._dispatch_overhead,
        }


def compile_aot(compiled: "CompiledModel") -> AotModel:
    """Fuse a :class:`CompiledModel` into one whole-graph AOT executable.

    The returned :class:`AotModel` traces lazily: the XLA compile happens
    on :meth:`AotModel.warmup` (or the first :meth:`AotModel.run`) for
    each (params, input shapes/dtypes) signature and is cached.
    """
    return AotModel(compiled)
