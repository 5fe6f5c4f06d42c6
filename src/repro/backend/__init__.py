"""repro.backend — lowering + runtime: MappedGraphs become executable code.

The paper's "code generation and deployment" stage (Sec. IV-C) rebuilt on
jax: where MATCH emits Mako-templated C around DORY-style memory plans,
this package walks a :class:`~repro.core.dispatcher.MappedGraph` and

* **lowers** every mapped segment into one fused, ``jax.jit``-compiled
  executor parameterized by its winning LOMA schedule
  (:mod:`repro.backend.lower`),
* **plans memory statically** — liveness over the segment execution order,
  first-fit + hill-climb offsets into flat per-level arenas, validated
  against each module's declared ``MemoryLevel`` capacities
  (:mod:`repro.backend.memory`),
* **runs** the result with per-segment timing and a predicted-vs-measured
  report, golden-checked bit-exact against the ``repro.cnn`` interpreter
  (:mod:`repro.backend.runtime`), and
* **fuses the whole graph into one jitted AOT executable** — all segments
  inlined in schedule order, zero per-segment host dispatch,
  intermediates left to XLA's buffer assignment (:mod:`repro.backend.aot`).
"""

from .aot import AotCompileError, AotEntry, AotModel, compile_aot
from .lower import LoweredSegment, LoweringError, lower
from .memory import BufferAlloc, MemoryPlan, MemoryPlanError, plan_memory
from .runtime import (
    CompiledModel,
    DivergenceReport,
    SegmentDivergence,
    SegmentTiming,
    UnsetFrequencyWarning,
    as_input_array,
)

__all__ = [
    "lower",
    "LoweredSegment",
    "LoweringError",
    "plan_memory",
    "MemoryPlan",
    "MemoryPlanError",
    "BufferAlloc",
    "CompiledModel",
    "DivergenceReport",
    "SegmentDivergence",
    "SegmentTiming",
    "UnsetFrequencyWarning",
    "as_input_array",
    "AotCompileError",
    "AotEntry",
    "AotModel",
    "compile_aot",
]
