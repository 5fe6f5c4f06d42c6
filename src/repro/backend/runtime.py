"""CompiledModel: the deployable artifact lowering produces.

Executes the lowered segments in topological (dispatch) order, one fused
jitted call per segment, with optional per-segment wall-clock timing.
``report()`` is the deployment summary the paper's generated runtime
prints: per-module predicted cycles, the static memory plan, and a
predicted-vs-measured table once a timed run has happened.

Bit-exactness contract: ``run(params, inputs)`` returns exactly what
``repro.cnn.execute_graph(graph, params, inputs)`` returns (checked by
``verify`` and by tests/test_backend.py on all four MLPerf-Tiny nets).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import MappedGraph
from repro.obs.log import MatchWarning
from repro.obs.log import warn as obs_warn

if TYPE_CHECKING:  # avoid a circular import with .lower
    from .lower import LoweredSegment
    from .memory import MemoryPlan

__all__ = [
    "CompiledModel",
    "DivergenceReport",
    "SegmentDivergence",
    "SegmentTiming",
    "UnsetFrequencyWarning",
    "as_input_array",
]


def as_input_array(v) -> jnp.ndarray:
    """Coerce one runtime input, *preserving* its dtype.

    Integer/quantized inputs (an int8 camera frame, a uint8 token id
    plane) must reach the segment executors as the caller typed them —
    casting everything to float32 silently widened quantized feeds.
    Only bare Python data (lists, scalars) without a dtype defaults to
    float32, matching the interpreter's historical behavior.

    Already-committed jax arrays pass through untouched — ``jnp.asarray``
    on a jax array walks the slow general-conversion path (~100us), which
    would dwarf the whole-graph AOT dispatch this layer exists to keep
    cheap.
    """
    if isinstance(v, jax.Array):
        return v
    if hasattr(v, "dtype"):
        return jnp.asarray(v)
    return jnp.asarray(v, jnp.float32)


class UnsetFrequencyWarning(MatchWarning, RuntimeWarning):
    """A SegmentTiming converted wall-clock to cycles with no clock set.

    ``frequency_hz`` defaults to 0.0, which silently turns every
    ``measured_cycles`` into 0 — a poisoned sample that would drag a
    calibration fit toward zero.  The conversion warns (and
    ``repro.calibrate.microbench`` raises) so it can never happen
    unnoticed.
    """


@dataclass(frozen=True)
class SegmentTiming:
    """Measured wall-clock for one segment of one timed run."""

    name: str
    module: str
    route: str
    predicted_cycles: float
    measured_us: float
    # the executing module's clock, so measured wall-clock converts into
    # the cycle domain the cost model predicts in (repro.calibrate)
    frequency_hz: float = 0.0

    @property
    def measured_cycles(self) -> float:
        if self.frequency_hz <= 0.0:
            obs_warn(
                f"SegmentTiming[{self.name}]: frequency_hz is unset "
                f"({self.frequency_hz}); measured_cycles is 0 and would "
                "poison a calibration fit",
                UnsetFrequencyWarning,
                stacklevel=2,
                logger="runtime",
            )
            return 0.0
        return self.measured_us * 1e-6 * self.frequency_hz

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "module": self.module,
            "route": self.route,
            "predicted_cycles": self.predicted_cycles,
            "measured_us": self.measured_us,
            "frequency_hz": self.frequency_hz,
            "measured_cycles": self.measured_cycles,
        }


@dataclass(frozen=True)
class SegmentDivergence:
    """Per-segment output deviation vs the reference interpreter."""

    name: str
    module: str
    route: str
    output_name: str
    max_abs_err: float

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "module": self.module,
            "route": self.route,
            "output_name": self.output_name,
            "max_abs_err": self.max_abs_err,
        }


@dataclass(frozen=True)
class DivergenceReport:
    """Localized bit-exactness check: every segment's output compared
    against the interpreter's value for the same node, in execution
    order — so a broken kernel names itself instead of hiding behind a
    single global max-abs number."""

    max_abs_err: float
    segments: tuple[SegmentDivergence, ...]

    @property
    def exact(self) -> bool:
        return self.max_abs_err == 0.0

    @property
    def first_divergent(self) -> SegmentDivergence | None:
        """The first segment (execution order) whose output deviates —
        downstream errors are usually just this one propagating."""
        for s in self.segments:
            if s.max_abs_err > 0.0:
                return s
        return None

    def summary(self) -> str:
        first = self.first_divergent
        if first is None:
            return f"bit-exact across {len(self.segments)} segments"
        return (
            f"max |err| {self.max_abs_err}; first divergence at segment "
            f"{first.name} ({first.module}/{first.route}): "
            f"|{first.output_name} - ref| = {first.max_abs_err}"
        )

    def to_dict(self) -> dict:
        """JSON-safe payload; also what the trace span event carries when
        ``verify(per_segment=True)`` finds a deviation."""
        first = self.first_divergent
        return {
            "max_abs_err": self.max_abs_err,
            "exact": self.exact,
            "first_divergent": first.to_dict() if first is not None else None,
            "segments": [s.to_dict() for s in self.segments],
        }


@dataclass
class CompiledModel:
    """A MappedGraph lowered to fused, memory-planned segment executors."""

    mapped: MappedGraph
    segments: list["LoweredSegment"]
    memory_plan: "MemoryPlan"
    attrs: dict = field(default_factory=dict)
    _last_timings: list[SegmentTiming] = field(default_factory=list, repr=False)
    _aot: object = field(default=None, repr=False)

    @property
    def graph(self):
        return self.mapped.graph

    @property
    def target(self):
        return self.mapped.target

    # -- execution ------------------------------------------------------
    def run(self, params: dict, inputs: dict, *, timed: bool = False) -> dict:
        """Execute all segments in order; returns {output_name: array}.

        Inputs keep the dtype the caller supplied (int8/quantized feeds
        are not widened; see :func:`as_input_array`).  ``timed=True``
        synchronizes after every segment and records a
        :class:`SegmentTiming` row (retrievable via ``last_timings``);
        each segment is executed once un-timed first, so jit
        trace/compile cost never leaks into ``measured_us`` — a cold
        first-call sample would poison the calibration fit.
        """
        env: dict[str, jnp.ndarray] = {
            k: as_input_array(v) for k, v in inputs.items()
        }
        tr = obs.get_tracer()
        tracing = tr.enabled
        timings: list[SegmentTiming] = []
        for ls in self.segments:
            xs = [env[name] for name in ls.input_names]
            seg_params = ls.params_slice(params)
            if timed:
                # warm: the first call may pay jit trace+compile; sample
                # the second (steady-state) execution only
                jax.block_until_ready(ls.fn(seg_params, *xs))
                t0 = time.perf_counter()
                out = jax.block_until_ready(ls.fn(seg_params, *xs))
                us = (time.perf_counter() - t0) * 1e6
                timings.append(
                    SegmentTiming(
                        ls.name,
                        ls.module,
                        ls.route,
                        ls.segment.cycles,
                        us,
                        frequency_hz=self.target.module(ls.module).frequency_hz,
                    )
                )
                obs.histogram(f"runtime.segment_us.{ls.module}").observe(us)
                if tracing:
                    # re-anchor the measured window onto the module lane
                    end = tr.now_us()
                    tr.complete(
                        ls.name, end - us, cat="runtime", lane=f"run:{ls.module}",
                        attrs={"route": ls.route, "predicted_cycles": ls.segment.cycles},
                    )
            elif tracing:
                t0_us = tr.now_us()
                out = ls.fn(seg_params, *xs)
                # async dispatch: the span covers host dispatch, not
                # device compute (timed=True gives the blocked window)
                tr.complete(
                    ls.name, t0_us, cat="runtime", lane=f"run:{ls.module}",
                    attrs={"route": ls.route, "async": True},
                )
            else:
                out = ls.fn(seg_params, *xs)
            env[ls.output_name] = out
        if timed:
            self._last_timings = timings
            obs.observe_timings(self.target.name, timings)
        return {o: env[o] for o in self.graph.outputs}

    @property
    def last_timings(self) -> list[SegmentTiming]:
        return list(self._last_timings)

    def verify(self, params: dict, inputs: dict, *, per_segment: bool = False):
        """Max abs deviation vs the reference interpreter (0.0 = bit-exact).

        ``per_segment=True`` returns a :class:`DivergenceReport` instead
        of the bare float: every segment output compared against the
        interpreter's value for that node, localizing the *first*
        deviating segment (the actionable one — everything after it is
        usually propagation).
        """
        if per_segment:
            return self._verify_per_segment(params, inputs)
        from repro.cnn.execute import execute_graph

        ref = execute_graph(self.graph, params, inputs)
        got = self.run(params, inputs)
        err = 0.0
        for k in ref:
            err = max(err, float(jnp.max(jnp.abs(ref[k] - got[k]))))
        return err

    def _verify_per_segment(self, params: dict, inputs: dict) -> DivergenceReport:
        from repro.cnn.execute import apply_node

        # full interpreter env: every node's reference value, not just
        # the graph outputs (segment boundaries are internal nodes)
        ref_env: dict[str, jnp.ndarray] = {
            k: jnp.asarray(v, jnp.float32) for k, v in inputs.items()
        }
        for n in self.graph.nodes:
            ref_env[n.name] = apply_node(
                n, params.get(n.name, {}), [ref_env[i] for i in n.inputs]
            )
        env: dict[str, jnp.ndarray] = {
            k: as_input_array(v) for k, v in inputs.items()
        }
        rows: list[SegmentDivergence] = []
        worst = 0.0
        for ls in self.segments:
            out = ls.fn(ls.params_slice(params), *[env[nm] for nm in ls.input_names])
            env[ls.output_name] = out
            err = float(jnp.max(jnp.abs(ref_env[ls.output_name] - out)))
            worst = max(worst, err)
            rows.append(
                SegmentDivergence(ls.name, ls.module, ls.route, ls.output_name, err)
            )
        report = DivergenceReport(max_abs_err=worst, segments=tuple(rows))
        first = report.first_divergent
        if first is not None:
            obs.counter("verify.divergences").inc()
            # localizable from the trace alone: the instant carries the
            # first deviating segment and the full per-segment table
            obs.get_tracer().instant(
                f"divergence:{first.name}", cat="verify", **report.to_dict()
            )
            # a divergence is an incident: when the flight recorder is
            # armed this writes a Perfetto dump of the lead-up (PR 9)
            obs.get_flight().trigger(
                "verify_divergence", segment=first.name, module=first.module,
                route=first.route, max_abs_err=report.max_abs_err,
            )
        return report

    # -- accounting -----------------------------------------------------
    def predicted_cycles(self) -> float:
        return self.mapped.total_cycles()

    def predicted_latency_s(self) -> float:
        return self.mapped.latency_s()

    def cycles_by_module(self) -> dict[str, float]:
        return self.mapped.cycles_by_module()

    def fused_node_count(self) -> int:
        return sum(len(ls.segment.nodes) for ls in self.segments)

    def routes(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for ls in self.segments:
            out[ls.route] = out.get(ls.route, 0) + 1
        return out

    # -- AOT ------------------------------------------------------------
    def to_aot(self):
        """The whole-graph one-jit AOT executor for this model
        (:func:`repro.backend.aot.compile_aot`): all segments fused into
        a single XLA program, bit-exact with :meth:`run` by construction.
        Built once — repeated calls return the same
        :class:`~repro.backend.aot.AotModel`, whose stats then ship in
        ``report_dict()["aot"]``."""
        from .aot import compile_aot  # no cycle: late import

        if self._aot is None:
            self._aot = compile_aot(self)
        return self._aot

    def pipeline_schedule(self):
        """The concurrent multi-module schedule of this model's mapping
        (:func:`repro.pipeline.schedule.schedule_pipeline`) — per-segment
        start/finish on each module's clock and the predicted makespan.
        Pure cost-model arithmetic, computed on demand."""
        from repro.pipeline.schedule import schedule_pipeline  # no cycle: late

        return schedule_pipeline(self.mapped)

    def predicted_makespan(self) -> float:
        """End-to-end cycles when modules run concurrently; equals
        ``predicted_cycles()`` exactly on single-module mappings and is
        never larger."""
        return self.pipeline_schedule().makespan

    def serve_dict(self, stream_requests: int = 4) -> dict:
        """Request-level serving predictions (:mod:`repro.serve`).

        Steady-state throughput is bounded by the busiest module, not by
        end-to-end latency: once the pipeline fills, a new request
        completes every *initiation interval* = max per-module busy
        cycles.  ``stream`` carries the unit-weight
        :func:`~repro.pipeline.schedule.schedule_stream` numbers for
        ``stream_requests`` concurrent requests — the quantity
        ``dispatch(..., objective="wct")`` re-ranks segmentations by.
        ``engine`` is the live :class:`~repro.serve.engine.ModelServer`
        stats when a replica has served this model (else ``None``).
        """
        from repro.pipeline.schedule import schedule_stream  # no cycle: late

        ps = self.pipeline_schedule()
        busy = ps.module_busy()
        ii = max(busy.values()) if busy else ps.makespan
        ss = schedule_stream(self.mapped, (1.0,) * max(1, stream_requests))
        f = self.target.fallback.frequency_hz
        return {
            "initiation_interval_cycles": ii,
            "bottleneck_module": max(busy, key=busy.get) if busy else None,
            "predicted_requests_per_s": (f / ii) if ii > 0 else 0.0,
            "predicted_stream_speedup": (ps.makespan / ii) if ii > 0 else 1.0,
            "stream": {
                "requests": int(max(1, stream_requests)),
                "makespan_cycles": ss.makespan,
                "weighted_completion_cycles": ss.attrs["weighted_completion"],
                "request_order": list(ss.attrs["request_order"]),
            },
            "engine": self.attrs.get("serve"),
        }

    def report_dict(self) -> dict:
        """Machine-readable companion of :meth:`report`: predicted cycles,
        memory plan, and any measured timings in one JSON-safe payload —
        what CI and the calibration fitter consume instead of parsing the
        printed tables."""
        g, t = self.graph, self.target
        measured = {tm.name: tm for tm in self._last_timings}
        segments = []
        for ls in self.segments:
            seg = ls.segment
            cost = seg.schedule.cost if seg.schedule is not None else None
            row = {
                "name": ls.name,
                "module": ls.module,
                "route": ls.route,
                "pattern": seg.pattern,
                "nodes": [n.name for n in seg.nodes],
                "predicted_cycles": seg.cycles,
                "transfer_cycles": seg.transfer_cycles,
                "l_ops": cost.l_ops if cost else 0.0,
                "l_mem": cost.l_mem if cost else 0.0,
            }
            tm = measured.get(ls.name)
            if tm is not None:
                row["measured_us"] = tm.measured_us
                row["measured_cycles"] = tm.measured_cycles
            segments.append(row)
        out = {
            "graph": g.name,
            "target": t.name,
            "calibration": t.attrs.get("calibration"),
            "segments": segments,
            "routes": self.routes(),
            "predicted_total_cycles": self.predicted_cycles(),
            "predicted_latency_s": self.predicted_latency_s(),
            "cycles_by_module": self.cycles_by_module(),
            "memory_plan": self.memory_plan.to_dict(),
            # Gantt-style concurrent schedule (repro.pipeline): per-module
            # lanes with start/finish plus the predicted makespan
            "pipeline": self.pipeline_schedule().timeline_dict(),
            # request-level serving (PR 8): steady-state initiation
            # interval + stream WCT predictions, and live replica stats
            # once a repro.serve.ModelServer has served this model
            "serve": self.serve_dict(),
            # process-wide observability snapshot (PR 7): metric registry
            # plus this target's predicted-vs-measured drift aggregates,
            # and (PR 9) the registered SLO engines' burn-rate verdicts
            "obs": {
                "metrics": obs.metrics_dict(),
                "drift": obs.drift_dict(t.name),
                "slo": obs.slo_dict(),
            },
        }
        if self._aot is not None:
            # trace/compile cost, executable size, plan aliasing and
            # measured dispatch overhead of the whole-graph AOT executor
            out["aot"] = self._aot.stats()
        if measured:
            out["measured_total_us"] = sum(tm.measured_us for tm in self._last_timings)
            out["timings"] = [tm.to_dict() for tm in self._last_timings]
        return out

    def report(self) -> str:
        """Deployment report: segments, per-module cycles, memory plan,
        and predicted-vs-measured when a ``run(..., timed=True)`` exists."""
        g, t = self.graph, self.target
        lines = [
            f"CompiledModel[{g.name} on {t.name}] — "
            f"{len(self.segments)} segments / {self.fused_node_count()} nodes, "
            f"routes {self.routes()}"
        ]
        measured = {tm.name: tm for tm in self._last_timings}
        header = f"  {'segment':<28s} {'module':<9s} {'route':<11s} {'pred cyc':>12s}"
        if measured:
            header += f" {'meas us':>10s}"
        lines.append(header)
        for ls in self.segments:
            row = (
                f"  {ls.name:<28.28s} {ls.module:<9s} {ls.route:<11s}"
                f" {ls.segment.cycles:>12.0f}"
            )
            tm = measured.get(ls.name)
            if measured:
                row += f" {tm.measured_us:>10.1f}" if tm else f" {'-':>10s}"
            lines.append(row)
        mods = ", ".join(
            f"{m}={c:.0f}" for m, c in sorted(self.cycles_by_module().items())
        )
        lines.append(
            f"  predicted total {self.predicted_cycles():.0f} cycles"
            f" ({self.predicted_latency_s()*1e3:.3f} ms @ module clock): {mods}"
        )
        if measured:
            total_us = sum(tm.measured_us for tm in self._last_timings)
            lines.append(f"  measured host wall-clock {total_us:.1f} us (jax backend)")
        lines.append(self.memory_plan.report())
        return "\n".join(lines)
