"""PipelinedModel: concurrent multi-module execution of a CompiledModel.

One worker thread per execution module; every inter-segment tensor is a
future keyed by its producing node's name, so a segment runs as soon as
its dependencies resolve and its module is free — the software analogue
of the per-module job queues the scheduler models.  ``run_stream``
additionally pipelines *across* inputs: while module A runs input k's
late segments, module B already runs input k+1's early ones, bounded by
``depth`` in-flight inputs (the double-buffered inter-stage queues the
pipeline-aware memory plan sizes).

Bit-exactness holds by construction: the workers call the exact same
fused ``LoweredSegment.fn`` executors on the exact same operands the
sequential ``CompiledModel.run`` loop would — only the wall-clock order
changes, never a value (checked by ``verify`` and the conformance
suite).  jax jitted calls are thread-safe and release the GIL while XLA
executes, which is where the concurrency win comes from.
"""

from __future__ import annotations

import threading
from concurrent.futures import Future
from typing import TYPE_CHECKING, Sequence

import jax
import jax.numpy as jnp

from repro import obs

from .schedule import PipelineSchedule, schedule_pipeline

if TYPE_CHECKING:  # import cycle: repro.backend never imports repro.pipeline
    from repro.backend.lower import LoweredSegment
    from repro.backend.runtime import CompiledModel

__all__ = ["PipelinedModel"]


class PipelinedModel:
    """A CompiledModel executing concurrently across execution modules.

    ``schedule`` defaults to :func:`schedule_pipeline` over the compiled
    mapping; its per-module lane order is the order each worker thread
    executes its segments in.  ``stream_depth`` bounds in-flight inputs
    for ``run_stream`` (2 = classic double buffering) and sizes the
    rotating inter-stage queue copies in the pipeline-aware memory plan.
    ``validate_memory=True`` fails fast (``MemoryPlanError``) when an
    overlap-aware plan no longer fits the declared capacities instead of
    silently running an undeployable configuration — the single-input
    plan at construction, the streaming plan on the first ``run_stream``
    call (plain ``run()`` never touches the queue copies).
    """

    def __init__(
        self,
        compiled: "CompiledModel",
        schedule: PipelineSchedule | None = None,
        *,
        stream_depth: int = 2,
        validate_memory: bool = True,
        timeout_s: float = 600.0,
    ):
        from repro.backend.memory import plan_memory

        if stream_depth < 1:
            raise ValueError(f"stream_depth must be >= 1, got {stream_depth}")
        self.compiled = compiled
        self.schedule = schedule if schedule is not None else schedule_pipeline(compiled.mapped)
        self.schedule.validate()
        # an externally supplied schedule must describe THIS mapping —
        # lanes index into compiled.segments, so a foreign schedule would
        # silently skip segments and deadlock their consumers
        segs = compiled.mapped.segments
        if (
            {e.index for e in self.schedule.entries} != set(range(len(segs)))
            or len(self.schedule.entries) != len(segs)  # no duplicate indices
            or any(
                e.name != segs[e.index].anchor.name
                or e.module != segs[e.index].module
                for e in self.schedule.entries
            )
        ):
            raise ValueError(
                "schedule does not match the compiled mapping "
                f"({self.schedule.graph_name!r} vs {compiled.graph.name!r}); "
                "pass schedule_pipeline(compiled.mapped) or None"
            )
        self.stream_depth = int(stream_depth)
        self.timeout_s = float(timeout_s)
        lowered = compiled.segments
        self._lanes: dict[str, list["LoweredSegment"]] = {}
        for module, lane in self.schedule.lanes().items():
            self._lanes[module] = [lowered[e.index] for e in lane]
        # the single-input concurrent plan gates construction; the
        # streaming plan (with its stream_depth rotating queue copies,
        # which plain run() never touches) is built and validated
        # lazily on the first run_stream call
        self._validate_memory = bool(validate_memory)
        self.memory_plan = plan_memory(compiled.mapped, schedule=self.schedule)
        if self._validate_memory:
            self.memory_plan.validate()
        self._streaming_plan = None

    # -- introspection ---------------------------------------------------
    @property
    def graph(self):
        return self.compiled.graph

    @property
    def target(self):
        return self.compiled.target

    def predicted_makespan(self) -> float:
        return self.schedule.makespan

    def predicted_speedup(self) -> float:
        return self.schedule.speedup()

    def streaming_plan(self):
        """The overlap-aware memory plan for ``run_stream`` — the
        single-input plan plus ``stream_depth`` rotating queue copies
        per buffer.  Built (and validated, when the model was
        constructed with ``validate_memory=True``) on first use."""
        if self._streaming_plan is None:
            from repro.backend.memory import plan_memory

            self._streaming_plan = plan_memory(
                self.compiled.mapped,
                schedule=self.schedule,
                stream_depth=self.stream_depth,
            )
            if self._validate_memory:
                self._streaming_plan.validate()
        return self._streaming_plan

    # -- execution -------------------------------------------------------
    def run(self, params: dict, inputs: dict) -> dict:
        """Execute one input concurrently; bit-exact with the sequential
        ``CompiledModel.run`` (independent branches overlap across
        modules, chains serialise on their dependencies)."""
        return self._execute(params, [inputs], depth=1)[0]

    def run_stream(
        self,
        params: dict,
        inputs: Sequence[dict],
        *,
        depth: int | None = None,
    ) -> list[dict]:
        """Software-pipelined streaming execution of many inputs.

        Each module worker walks inputs in order; at most ``depth``
        (default ``self.stream_depth``) inputs are in flight, so early
        pipeline stages start input k+1 while late stages finish input
        k.  ``depth`` may not exceed ``self.stream_depth`` — the memory
        plan reserved exactly that many rotating queue copies.  Outputs
        are returned in input order, each bit-exact with a sequential
        ``run`` of that input.
        """
        d = self.stream_depth if depth is None else int(depth)
        if not 1 <= d <= self.stream_depth:
            raise ValueError(
                f"depth must be in [1, stream_depth={self.stream_depth}], "
                f"got {d} — construct the model with a larger stream_depth "
                "to admit more in-flight inputs"
            )
        if d > 1:
            self.streaming_plan()  # reserve + validate the queue copies
        return self._execute(params, list(inputs), depth=d)

    def _execute(self, params: dict, inputs_list: list[dict], *, depth: int) -> list[dict]:
        from repro.backend.runtime import as_input_array

        graph = self.graph
        n_inputs = len(inputs_list)
        if n_inputs == 0:
            return []
        futs: dict[tuple[int, str], Future] = {}
        for k, inputs in enumerate(inputs_list):
            for name, v in inputs.items():
                f: Future = Future()
                f.set_result(as_input_array(v))
                futs[(k, name)] = f
            for ls in self.compiled.segments:
                futs[(k, ls.output_name)] = Future()

        steps = {
            module: [(ls, ls.params_slice(params)) for ls in lane]
            for module, lane in self._lanes.items()
        }

        # admission gate: input k may enter the pipeline only once input
        # k-depth has been fully collected (bounds live queue copies to
        # the depth the memory plan reserved)
        admit = [threading.Event() for _ in range(n_inputs)]
        for k in range(min(depth, n_inputs)):
            admit[k].set()
        timeout = self.timeout_s
        # set when the caller gives up (an output raised): workers stop
        # computing immediately instead of draining the whole stream
        stop = threading.Event()

        tracer = obs.get_tracer()

        def worker(module: str, lane_steps: list) -> None:
            tracing = tracer.enabled
            for k in range(n_inputs):
                admitted = admit[k].wait(timeout)
                for ls, seg_params in lane_steps:
                    out_fut = futs[(k, ls.output_name)]
                    if stop.is_set() or not admitted:
                        out_fut.set_exception(RuntimeError(
                            "pipeline cancelled"
                            if stop.is_set()
                            else f"input {k} was never admitted within "
                            f"{timeout}s (pipeline stalled upstream)"
                        ))
                        continue
                    try:
                        xs = [futs[(k, nm)].result(timeout) for nm in ls.input_names]
                        if tracing:
                            # block so the span covers the compute, not
                            # just the async dispatch; untraced runs keep
                            # jax's pipelined dispatch untouched
                            t0_us = tracer.now_us()
                            out = jax.block_until_ready(ls.fn(seg_params, *xs))
                            tracer.complete(
                                f"{ls.output_name}@{k}", t0_us, cat="runtime",
                                lane=f"pipeline:{module}",
                                attrs={
                                    "input": k,
                                    "thread": threading.get_ident(),
                                },
                            )
                        else:
                            out = ls.fn(seg_params, *xs)
                    except BaseException as e:  # propagate through the DAG
                        out_fut.set_exception(e)
                    else:
                        out_fut.set_result(out)

        threads = [
            threading.Thread(target=worker, args=(m, lane), daemon=True, name=f"pipeline-{m}")
            for m, lane in steps.items()
        ]
        for t in threads:
            t.start()
        results: list[dict] = []
        try:
            for k in range(n_inputs):
                out = {o: futs[(k, o)].result(timeout) for o in graph.outputs}
                results.append(out)
                nxt = k + depth
                if nxt < n_inputs:
                    admit[nxt].set()
        except BaseException:
            stop.set()  # cancel remaining work before re-raising
            raise
        finally:
            # release any still-gated inputs so workers drain and exit
            # even when an output future raised
            for ev in admit:
                ev.set()
            for t in threads:
                t.join(timeout)
        return results

    # -- verification ----------------------------------------------------
    def verify(self, params: dict, inputs: dict) -> float:
        """Max |pipelined - sequential| over graph outputs (0.0 = exact).

        On divergence, ``CompiledModel.verify(..., per_segment=True)``
        localizes the first deviating segment against the interpreter.
        """
        ref = self.compiled.run(params, inputs)
        got = self.run(params, inputs)
        err = 0.0
        for k in ref:
            err = max(err, float(jnp.max(jnp.abs(ref[k] - got[k]))))
        return err

    def report(self) -> str:
        lines = [self.schedule.gantt()]
        lines.append(self.memory_plan.report())
        return "\n".join(lines)
