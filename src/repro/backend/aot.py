"""Whole-graph one-jit AOT executor: kill per-segment host dispatch.

``CompiledModel.run`` walks the lowered segments in a Python loop — one
jitted dispatch per segment — so on sub-millisecond MLPerf-Tiny nets the
host round-trips dominate end-to-end latency.  This module fuses ALL
lowered segments into **one** XLA program executed without returning to
Python between segments: the moral equivalent of upstream MATCH's
generated C graph runner around a static USMP memory plan
(``static_mem_plan="hill_climb"``, ``tir.InjectDoubleBuffer``,
``tir.use_async_copy``) and of HTVM's double-buffered accelerator
handoff.

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
* **The static MemoryPlan survives into the executable.**
  ``memory="arena"`` threads one flat, *donated* arena buffer through
  the program: every planned buffer is stored at its first-fit /
  hill-climb offset (:meth:`MemoryPlan.arena_view` — byte coordinates
  scaled to the host element width, disjointness preserved verbatim) and
  XLA updates the donated buffer in place, so the plan's offsets are the
  executable's offsets instead of being re-derived by XLA's own buffer
  assignment.  ``memory="xla"`` (the default, and the fastest host
  path) keeps intermediates as SSA values — XLA's buffer assignment
  then owns the aliasing, which ``stats()`` reports as plan coverage.
* **Cross-module boundaries are double-buffer staged.**  Consecutive
  segments on different execution modules mirror the pipeline
  scheduler's ``transfer_cycles`` accounting: in arena mode the
  boundary tensor lands in one of two alternating staging slots
  appended to the arena (classic double buffering — slot ``k%2`` is
  written while slot ``(k+1)%2`` is still being read), and ``stats()``
  carries the predicted transfer/compute overlap either way.  On the
  jax host runtime the copy is a dataflow op XLA is free to schedule
  concurrently (async-copy on real accelerator backends).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import TYPE_CHECKING, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

if TYPE_CHECKING:  # avoid circular imports at module load
    from .lower import LoweredSegment
    from .runtime import CompiledModel

__all__ = [
    "AotCompileError",
    "AotEntry",
    "AotModel",
    "ChainExecutor",
    "compile_aot",
    "build_chains",
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
    arena: object = field(default=None, repr=False)  # donated, arena mode
    arena_elems: int = 0
    arena_fallbacks: tuple[str, ...] = ()
    donation_honored: bool | None = None
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
            "arena_elems": self.arena_elems,
            "arena_fallbacks": list(self.arena_fallbacks),
            "donation_honored": self.donation_honored,
            "calls": self.calls,
            "executable": self.executable_stats(),
        }


class AotModel:
    """A CompiledModel fused into one jitted whole-graph program.

    ``memory="xla"`` (default) leaves intermediate buffers to XLA's own
    assignment — fastest host path; ``memory="arena"`` expresses the
    static :class:`MemoryPlan` literally (one donated flat arena, every
    buffer at its planned offset, cross-module boundaries staged through
    two alternating double-buffer slots).  ``donate_inputs=True``
    additionally donates the graph-input buffers (safe when callers pass
    numpy arrays, which are copied to device per call; a donated *jax*
    array is consumed).  Donation falls back silently — never an error —
    on backends that do not honor it; ``stats()['donation']`` records
    what was requested and what stuck.
    """

    def __init__(
        self,
        compiled: "CompiledModel",
        *,
        memory: str = "xla",
        donate_inputs: bool = False,
        staging: bool = True,
    ):
        if memory not in ("xla", "arena"):
            raise ValueError(f"memory must be 'xla' or 'arena', got {memory!r}")
        self.compiled = compiled
        self.memory = memory
        self.donate_inputs = bool(donate_inputs)
        self.staging = bool(staging)
        self._entries: dict[tuple, AotEntry] = {}
        self._lock = threading.Lock()
        self._dispatch_overhead: dict | None = None
        # static accounting: cross-module boundaries in execution order,
        # mirroring the pipeline scheduler's transfer-at-consumer-start
        # derivation — with double buffering, boundary k's input DMA can
        # overlap boundary k-1's producing compute.
        segs = compiled.mapped.segments
        self._boundaries: list[dict] = []
        for i in range(len(segs) - 1):
            a, b = segs[i], segs[i + 1]
            if a.module != b.module:
                self._boundaries.append(
                    {
                        "producer": a.anchor.name,
                        "consumer": b.anchor.name,
                        "modules": [a.module, b.module],
                        "tensor": a.output_node.name,
                        "slot": len(self._boundaries) % 2,
                        "transfer_cycles": b.transfer_cycles,
                        "overlap_cycles": min(b.transfer_cycles, a.cycles),
                    }
                )

    # -- introspection ---------------------------------------------------
    @property
    def graph(self):
        return self.compiled.graph

    @property
    def target(self):
        return self.compiled.target

    def predicted_overlap_cycles(self) -> float:
        """Transfer cycles the double-buffered staging can hide behind the
        preceding segment's compute (scheduler-consistent accounting)."""
        return sum(b["overlap_cycles"] for b in self._boundaries)

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
        if self.memory == "arena":
            fn, arena_elems, fallbacks = self._build_arena_fn(params, abstract)
            jitted = jax.jit(fn, donate_argnums=(0,))
            args = (jax.ShapeDtypeStruct((arena_elems,), jnp.float32), abstract)
        else:
            fn = self._build_xla_fn(params)
            jitted = jax.jit(fn, donate_argnums=(0,) if self.donate_inputs else ())
            arena_elems, fallbacks = 0, ()
            args = (abstract,)
        with obs.span(
            "aot.compile", cat="compile", graph=self.graph.name,
            target=self.target.name, memory=self.memory,
        ) as sp:
            t0 = time.perf_counter()
            try:
                lowered = jitted.lower(*args)
            except Exception as e:
                raise AotCompileError(
                    f"whole-graph trace failed for {self.graph.name} on "
                    f"{self.target.name}: {e}"
                ) from e
            t1 = time.perf_counter()
            executable = lowered.compile()
            t2 = time.perf_counter()
            sp.set(
                trace_us=(t1 - t0) * 1e6,
                compile_us=(t2 - t1) * 1e6,
                arena_fallbacks=list(fallbacks),
            )
        if fallbacks:
            obs.counter("aot.arena_fallbacks").inc(len(fallbacks))
        entry = AotEntry(
            signature=sig,
            executable=executable,
            trace_us=(t1 - t0) * 1e6,
            compile_us=(t2 - t1) * 1e6,
            params=params,
            arena_elems=arena_elems,
            arena_fallbacks=tuple(fallbacks),
        )
        if self.memory == "arena":
            entry.arena = jnp.zeros((arena_elems,), jnp.float32)
        return entry

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

    def _build_arena_fn(self, params: dict, abstract: dict):
        """Whole program threading the planned arena: every buffer at its
        first-fit/hill-climb offset, cross-module boundary tensors staged
        through two alternating double-buffer slots."""
        compiled = self.compiled
        graph = self.graph
        segments = compiled.segments
        view = compiled.memory_plan.arena_view()

        # abstract shape pass: segment output shapes/dtypes before any
        # arena layout decision (slot sizing needs them)
        shapes: dict[str, jax.ShapeDtypeStruct] = dict(abstract)
        for ls in segments:
            xs = [shapes[nm] for nm in ls.input_names]
            # bind the concrete params via partial: eval_shape abstracts
            # its *arguments*, and e.g. the Pallas requant shift must stay
            # a concrete (static) value during the shape pass too
            shapes[ls.output_name] = jax.eval_shape(
                partial(ls.fn, ls.params_slice(params)), *xs
            )

        def elems(name: str) -> int:
            return int(np.prod(shapes[name].shape)) if shapes[name].shape else 1

        # planned placement; a tensor larger than its planned slot (the
        # plan sized it in declared elem_bytes) falls back to SSA
        place: dict[str, int] = {}
        fallbacks: list[str] = []
        for name in shapes:
            off = view.offsets.get(name)
            if off is None:
                continue
            if elems(name) <= view.capacities_elems.get(name, 0):
                place[name] = off
            else:
                fallbacks.append(name)

        # double-buffer staging slots for cross-module boundary tensors
        # whose only consumer is the next segment (classic handoff shape)
        consumers_of: dict[str, set[int]] = {}
        for i, ls in enumerate(segments):
            for nm in ls.input_names:
                consumers_of.setdefault(nm, set()).add(i)
        staged: dict[str, int] = {}
        if self.staging:
            for b in self._boundaries:
                t = b["tensor"]
                cons = consumers_of.get(t, set())
                nxt = next(
                    i for i, ls in enumerate(segments) if ls.name == b["consumer"]
                )
                if t in place and cons == {nxt} and t not in graph.outputs:
                    staged[t] = b["slot"]
        slot_elems = [0, 0]
        for t, s in staged.items():
            slot_elems[s] = max(slot_elems[s], elems(t))
        slot_off = [
            view.length_elems,
            view.length_elems + slot_elems[0],
        ]
        arena_elems = max(1, view.length_elems + slot_elems[0] + slot_elems[1])

        def offset_of(name: str) -> int | None:
            if name in staged:
                return slot_off[staged[name]]
            return place.get(name)

        outputs = graph.outputs

        def whole(arena, inputs):
            ssa: dict[str, jnp.ndarray] = {}

            def store(arena, name, val):
                off = offset_of(name)
                if off is None:
                    ssa[name] = val
                    return arena
                flat = val.astype(jnp.float32).reshape(-1)
                scope = (
                    f"dma_stage{staged[name]}" if name in staged else "arena_store"
                )
                with jax.named_scope(scope):
                    return jax.lax.dynamic_update_slice(arena, flat, (off,))

            def load(arena, name):
                off = offset_of(name)
                if off is None:
                    return ssa[name]
                sd = shapes[name]
                flat = jax.lax.dynamic_slice(arena, (off,), (elems(name),))
                return flat.reshape(sd.shape).astype(sd.dtype)

            for name in inputs:
                arena = store(arena, name, inputs[name])
            for ls in segments:
                xs = [load(arena, nm) for nm in ls.input_names]
                with jax.named_scope(f"seg{ls.index}.{ls.module}"):
                    out = ls.fn(ls.params_slice(params), *xs)
                arena = store(arena, ls.output_name, out)
            return {o: load(arena, o) for o in outputs}, arena

        return whole, arena_elems, fallbacks

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
            with tr.span("aot.dispatch", graph=self.graph.name, memory=self.memory):
                return self._dispatch(params, coerced)
        return self._dispatch(params, {k: _as_input(v) for k, v in inputs.items()})

    def _dispatch(self, params: dict, coerced: dict) -> dict:
        entry = self.warmup(params, coerced)
        entry.calls += 1
        return self._run_entry(entry, coerced)

    def _run_entry(self, entry: "AotEntry", coerced: dict) -> dict:
        if self.memory == "arena":
            with self._lock:  # the donated arena is single-owner state
                arena = entry.arena
                out, new_arena = entry.executable(arena, coerced)
                if entry.donation_honored is None:
                    try:
                        entry.donation_honored = bool(arena.is_deleted())
                    except Exception:  # pragma: no cover
                        entry.donation_honored = None
                entry.arena = new_arena
            return dict(out)
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
        """JSON-safe AOT report: trace/compile cost, executable size,
        donation coverage, staging accounting, measured dispatch
        overhead (the ``report_dict()["aot"]`` payload)."""
        plan = self.compiled.memory_plan
        io_names = set(self.graph.inputs) | set(self.graph.outputs)
        total = sum(b.nbytes for b in plan.buffers.values())
        internal = sum(
            b.nbytes for n, b in plan.buffers.items() if n not in io_names
        )
        if self.memory == "arena":
            entries = list(self._entries.values())
            fell_back = {n for e in entries for n in e.arena_fallbacks}
            covered = sum(
                b.nbytes for n, b in plan.buffers.items() if n not in fell_back
            )
            donation = {
                "mode": "arena",
                "plan_bytes": total,
                "covered_bytes": covered,
                "coverage": covered / max(total, 1),
                "arena_donation_honored": next(
                    (e.donation_honored for e in entries if e.donation_honored is not None),
                    None,
                ),
                "fallback_buffers": sorted(fell_back),
            }
        else:
            donation = {
                "mode": "xla",
                "plan_bytes": total,
                # intermediates never leave the executable: XLA's buffer
                # assignment owns them (the aliasing the plan decided is
                # re-derived inside XLA instead of imposed)
                "covered_bytes": internal,
                "coverage": internal / max(total, 1),
                "inputs_donated": self.donate_inputs,
                "fallback_buffers": sorted(io_names & set(plan.buffers)),
            }
        return {
            "mode": self.memory,
            "segments": len(self.compiled.segments),
            "staging": {
                "enabled": self.staging,
                "slots": 2,
                "boundaries": [dict(b) for b in self._boundaries],
                "predicted_overlap_cycles": self.predicted_overlap_cycles(),
            },
            "donation": donation,
            "plan_aliasing": plan.aliasing_summary(),
            "entries": [e.to_dict() for e in self._entries.values()],
            "dispatch_overhead": self._dispatch_overhead,
        }


def compile_aot(
    compiled: "CompiledModel",
    *,
    memory: str = "xla",
    donate_inputs: bool = False,
    staging: bool = True,
) -> AotModel:
    """Fuse a :class:`CompiledModel` into one whole-graph AOT executable.

    The returned :class:`AotModel` traces lazily: the XLA compile happens
    on :meth:`AotModel.warmup` (or the first :meth:`AotModel.run`) for
    each (params, input shapes/dtypes) signature and is cached.  See the
    module docstring for the ``memory`` / donation semantics.
    """
    return AotModel(
        compiled, memory=memory, donate_inputs=donate_inputs, staging=staging
    )


# ---------------------------------------------------------------------------
# Lane chaining: the PipelinedModel AOT fast path
# ---------------------------------------------------------------------------


@dataclass
class ChainExecutor:
    """One jitted executor for a dependency-closed run of lane segments.

    ``fn(*xs)`` takes the chain's external inputs (first-use order) and
    returns one output per member segment, so the pipelined worker
    resolves every member's future from a single dispatch — fewer future
    hops and fewer host round-trips per input.
    """

    segments: tuple["LoweredSegment", ...]
    ext_inputs: tuple[str, ...]
    fn: Callable

    @property
    def output_names(self) -> tuple[str, ...]:
        return tuple(ls.output_name for ls in self.segments)


def build_chains(
    lane: Sequence["LoweredSegment"], graph_inputs: Sequence[str]
) -> list[list["LoweredSegment"]]:
    """Group a module lane into maximal dependency-closed runs.

    A segment joins the current chain when every one of its external
    inputs is either a graph input (resolved before the stream starts)
    or produced by an earlier member of the same chain — i.e. collapsing
    the run into one dispatch never has to *wait* mid-chain on another
    lane's future.  Anything else starts a new chain.
    """
    always = set(graph_inputs)
    chains: list[list["LoweredSegment"]] = []
    for ls in lane:
        if chains:
            produced = {c.output_name for c in chains[-1]}
            if all(nm in produced or nm in always for nm in ls.input_names):
                chains[-1].append(ls)
                continue
        chains.append([ls])
    return chains


def make_chain_executor(
    chain: Sequence["LoweredSegment"], params: dict
) -> ChainExecutor:
    """Compile one chain into a single jitted callable (params baked as
    constants, same contract as :class:`AotModel`).  Singleton chains
    reuse the segment's own executor unwrapped — no extra trace."""
    chain = tuple(chain)
    internal = {ls.output_name for ls in chain}
    ext: list[str] = []
    for ls in chain:
        for nm in ls.input_names:
            if nm not in internal and nm not in ext:
                ext.append(nm)
    ext_t = tuple(ext)
    if len(chain) == 1:
        ls0 = chain[0]
        sp0 = ls0.params_slice(params)

        def single(*xs):
            env = dict(zip(ext_t, xs))
            return (ls0.fn(sp0, *[env[nm] for nm in ls0.input_names]),)

        return ChainExecutor(chain, ext_t, single)

    seg_params = [ls.params_slice(params) for ls in chain]

    @jax.jit
    def fused(*xs):
        env = dict(zip(ext_t, xs))
        for ls, sp in zip(chain, seg_params):
            env[ls.output_name] = ls.fn(sp, *[env[nm] for nm in ls.input_names])
        return tuple(env[ls.output_name] for ls in chain)

    return ChainExecutor(chain, ext_t, fused)
