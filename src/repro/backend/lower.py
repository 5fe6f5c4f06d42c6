"""Lowering: MappedGraph -> CompiledModel (paper Sec. IV-C "code gen").

Each :class:`~repro.core.dispatcher.MappedSegment` becomes ONE fused,
``jax.jit``-compiled executor:

* **conv / dwconv anchors** route through the tiled conv kernel in
  :mod:`repro.kernels.tiled_conv`: the winning LOMA OY tile becomes the
  band size (the L1-resident output stripe), and the bias/requant/relu
  chain is folded into the same jitted function as the segment epilogue.
* **dense anchors with a requant epilogue** route through the Pallas
  int8 GEMM :func:`repro.kernels.matmul_requant` (``rounding="even"``
  reproduces the interpreter's round-half-to-even requant bit-exactly);
  the DSE block sizes, snapped to TPU-legal dims, become its BlockSpecs.
* **everything else** (elementwise chains, pools, structural ops, CPU
  fallback segments) lowers through the reference op library shared with
  the interpreter (``repro.cnn.execute.apply_node``), fused per segment.

Schedules reach the kernels via
:func:`repro.core.schedule.schedule_from_result` — lowering never re-runs
the DSE; it consumes the winners the dispatcher already stored.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import (
    KernelSchedule,
    MappedGraph,
    MappedSegment,
    MatchTarget,
    Node,
    schedule_from_result,
)
from repro.cnn.execute import apply_node
from repro.kernels.matmul_requant import matmul_requant
from repro.kernels.tiled_conv import tiled_conv2d
from repro.kernels.tpu import LANE, SUBLANE, tpu_block

from .memory import plan_memory
from .runtime import CompiledModel

__all__ = ["lower", "LoweredSegment", "LoweringError"]


class LoweringError(RuntimeError):
    """The mapped graph cannot be lowered to segment executors."""


@dataclass
class LoweredSegment:
    """One fused executor for one mapped segment."""

    index: int
    segment: MappedSegment
    route: str  # "tiled_conv" | "pallas_gemm" | "reference" | "structural"
    input_names: tuple[str, ...]
    output_name: str
    fn: Callable  # fn(seg_params: dict, *inputs) -> output array
    kernel_schedule: KernelSchedule | None = None
    meta: dict = field(default_factory=dict)

    @property
    def name(self) -> str:
        return self.segment.anchor.name

    @property
    def module(self) -> str:
        return self.segment.module

    def params_slice(self, params: dict) -> dict:
        return {n.name: params.get(n.name, {}) for n in self.segment.nodes}


# ---------------------------------------------------------------------------
# Fused executors
# ---------------------------------------------------------------------------


def _fused_reference_fn(
    nodes: Sequence[Node],
    input_names: tuple[str, ...],
    output_name: str,
    anchor_impl: Callable | None = None,
):
    """One jitted function evaluating the whole segment chain through the
    shared op library (bit-exact with the interpreter by construction).
    ``anchor_impl(params, *xs)`` overrides the first node's evaluation —
    that is how the tiled conv kernel slots in under the same epilogue."""

    @jax.jit
    def fn(seg_params: dict, *xs):
        env = dict(zip(input_names, xs))
        for i, nd in enumerate(nodes):
            args = [env[k] for k in nd.inputs]
            p = seg_params.get(nd.name, {})
            if i == 0 and anchor_impl is not None:
                env[nd.name] = anchor_impl(p, *args)
            else:
                env[nd.name] = apply_node(nd, p, args)
        return env[output_name]

    return fn


def _tiled_conv_impl(anchor: Node, ksched: KernelSchedule | None, band_tiling: bool):
    """Anchor override running the banded conv kernel with the winning
    schedule's OY tile as the band size (one whole-array band when the
    caller disables band tiling for host-throughput runs)."""
    stride = int(anchor.attr("stride", 1) or 1)
    depthwise = anchor.op == "dwconv2d"
    oy = int(anchor.attr("OY", 1) or 1)
    block_oy = oy
    if band_tiling and ksched is not None:
        block_oy = max(1, min(int(ksched.block_of("OY", oy)), oy))

    def impl(p: dict, x):
        w = jnp.asarray(p["w"])
        groups = x.shape[-1] if depthwise else 1
        return tiled_conv2d(x, w, stride=stride, block_oy=block_oy, feature_groups=groups)

    return impl, block_oy


def _pallas_dense_fn(
    seg: MappedSegment,
    ksched: KernelSchedule | None,
    ref_fn: Callable,
):
    """dense(+bias)+requant(+relu) through the Pallas int8 GEMM.

    The requant shift is read from the concrete params at call time (it is
    a static kernel argument); activations/weights are integer-valued by
    the integerized-graph contract, so the int8 casts are lossless.  If
    the params supply a requant scale/addend at runtime (which the GEMM
    epilogue does not model), the call falls back to ``ref_fn`` — the
    segment's fused reference executor — instead of silently diverging.

    Returns ``(fn, (bm, bk, bn))``, the BlockSpec dims at the anchor's
    (B, C, K) geometry: the DSE tile snapped to TPU-legal dims.
    """
    anchor = seg.anchor
    chain_ops = [n.op for n in seg.epilogue]
    has_relu = "relu" in chain_ops
    bias_node = next((n for n in seg.nodes if n.op == "bias_add"), None)
    requant_node = next(n for n in seg.nodes if n.op == "requant")

    def blocks_for(m: int, kd: int, n_out: int) -> tuple[int, int, int]:
        if ksched is None:
            return m, kd, n_out
        return (
            tpu_block(int(ksched.block_of("B", m)), m, SUBLANE),
            tpu_block(int(ksched.block_of("C", kd)), kd, LANE),
            tpu_block(int(ksched.block_of("K", n_out)), n_out, LANE),
        )

    def fn(seg_params: dict, x):
        rp = seg_params.get(requant_node.name, {})
        if "scale" in rp or "addend" in rp:
            return ref_fn(seg_params, x)
        x2 = jnp.asarray(x, jnp.float32).reshape(x.shape[0], -1)
        w = jnp.asarray(seg_params[anchor.name]["w"])  # (K, C)
        n_out = w.shape[0]
        bm, bk, bn = blocks_for(*x2.shape, n_out)
        a8 = x2.astype(jnp.int8)
        w8 = w.astype(jnp.int8).T  # (C, K)
        if bias_node is not None:
            bias = jnp.asarray(seg_params[bias_node.name]["b"]).astype(jnp.int32)
        else:
            bias = jnp.zeros((n_out,), jnp.int32)
        mult = jnp.ones((n_out,), jnp.int32)
        attr_shift = requant_node.attr("shift", None)
        default_shift = 5.0 if attr_shift is None else float(attr_shift)
        shift = int(np.asarray(seg_params[requant_node.name].get("shift", default_shift)))
        y8 = matmul_requant(
            a8,
            w8,
            mult,
            bias,
            shift=shift,
            relu=has_relu,
            rounding="even",
            block_m=bm,
            block_n=bn,
            block_k=bk,
        )
        return y8.astype(jnp.float32)

    geometry = (int(anchor.attr(a, 1) or 1) for a in ("B", "C", "K"))
    return fn, blocks_for(*geometry)


# ---------------------------------------------------------------------------
# Route selection + entry point
# ---------------------------------------------------------------------------


def _kernel_schedule(seg: MappedSegment, target: MatchTarget) -> KernelSchedule | None:
    if seg.schedule is None or seg.workload is None:
        return None
    module = target.module(seg.module)
    return schedule_from_result(seg.schedule, seg.workload, module)


def _route_of(seg: MappedSegment, use_pallas: bool) -> str:
    anchor = seg.anchor
    if anchor.op in ("conv2d", "dwconv2d"):
        return "tiled_conv"
    # only graphs explicitly integerized to 1-byte elems may take the int8
    # kernel (a missing attr means unknown dtype: fail safe to reference)
    eb = anchor.attr("elem_bytes", None)
    int8 = eb is not None and int(eb) == 1
    requant = next((n for n in seg.nodes if n.op == "requant"), None)
    # a folded requant carrying scale/addend attrs needs the general
    # affine epilogue — only the plain shift form maps onto the GEMM kernel
    plain_requant = requant is not None and not (
        "scale" in requant.attrs or "addend" in requant.attrs
    )
    if use_pallas and anchor.op == "dense" and plain_requant and int8:
        return "pallas_gemm"
    if seg.workload is None:
        return "structural"
    return "reference"


def lower(
    mapped: MappedGraph,
    target: MatchTarget | str | None = None,
    *,
    use_pallas: bool = True,
    band_tiling: bool = True,
    allow_spill: bool = True,
    hill_climb_iters: int = 200,
    aot: bool = False,
) -> CompiledModel:
    """Compile a MappedGraph into fused, memory-planned segment executors.

    ``target`` defaults to ``mapped.target``; a string is resolved as a
    registered target name (:mod:`repro.targets.registry`) and must match
    the target the graph was dispatched on.  ``use_pallas=False`` forces
    dense segments onto the reference route and ``band_tiling=False``
    collapses convs to one whole-array band: together they select the
    "fused" fidelity — same fused segments and memory plan, but the
    fastest host execution (the default is the HW-faithful execution
    shape: L1-stripe conv bands + the Pallas int8 GEMM).  The Pallas
    kernels run interpreted on the CPU backend and compile through Mosaic
    on a TPU (:func:`repro.kernels.tpu.interpret_mode`).  ``aot=True``
    additionally attaches the whole-graph one-jit AOT executor
    (``CompiledModel.to_aot()``; XLA compile stays lazy until its first
    ``warmup``/``run``), so ``report_dict()`` carries the AOT payload.
    """
    if target is None:
        target = mapped.target
    elif isinstance(target, str):
        # a name adds no information beyond a consistency check: resolve
        # it canonically (aliases included) without building a fresh
        # target, then lower against the dispatch target itself
        from repro.targets.registry import get_target, target_info

        resolved = target_info(target)["name"]
        if resolved != mapped.target.name:
            # registry names need not equal MatchTarget.name (a factory
            # may decorate it): only the instantiated name is decisive
            actual = get_target(target).name
            if actual != mapped.target.name:
                raise LoweringError(
                    f"target {actual!r} does not match the dispatch target "
                    f"{mapped.target.name!r}"
                )
        target = mapped.target
    elif target is not mapped.target and target.name != mapped.target.name:
        raise LoweringError(
            f"target {target.name!r} does not match the dispatch target "
            f"{mapped.target.name!r}"
        )
    graph = mapped.graph

    # every graph output must be a segment boundary — fused chain internals
    # never materialize, so nothing else is addressable at runtime
    boundary = {s.output_node.name for s in mapped.segments}
    for o in graph.outputs:
        if graph.has(o) and o not in boundary:
            raise LoweringError(f"graph output {o} is fused inside a segment")
    covered = {n.name for s in mapped.segments for n in s.nodes}
    missing = {n.name for n in graph.nodes} - covered
    if missing:
        raise LoweringError(f"mapped graph does not cover nodes: {sorted(missing)}")

    lower_span = obs.span(
        "lower", cat="compile", graph=graph.name, target=target.name,
        segments=len(mapped.segments),
    )
    lower_span.__enter__()
    lowered: list[LoweredSegment] = []
    for i, seg in enumerate(mapped.segments):
        # chain internals must be single-consumer (the pattern matcher
        # guarantees it; re-checked here because lowering depends on it)
        for nd in seg.nodes[:-1]:
            ext = [c.name for c in graph.consumers(nd.name) if c.name not in {m.name for m in seg.nodes}]
            if ext:
                raise LoweringError(
                    f"segment {seg.anchor.name}: internal node {nd.name} "
                    f"is consumed outside the segment by {ext}"
                )
        inputs = seg.external_inputs(graph)
        out_name = seg.output_node.name
        with obs.span("lower.segment", cat="compile") as sp:
            ksched = _kernel_schedule(seg, target)
            route = _route_of(seg, use_pallas)
            sp.set(segment=seg.anchor.name, module=seg.module, route=route)
        obs.counter(f"lower.route.{route}").inc()
        meta: dict = {"pattern": seg.pattern}
        if route == "tiled_conv":
            impl, block_oy = _tiled_conv_impl(seg.anchor, ksched, band_tiling)
            fn = _fused_reference_fn(seg.nodes, inputs, out_name, anchor_impl=impl)
            meta["block_oy"] = block_oy
        elif route == "pallas_gemm":
            ref_fn = _fused_reference_fn(seg.nodes, inputs, out_name)
            fn, meta["blocks"] = _pallas_dense_fn(seg, ksched, ref_fn)
        else:
            fn = _fused_reference_fn(seg.nodes, inputs, out_name)
        lowered.append(
            LoweredSegment(
                index=i,
                segment=seg,
                route=route,
                input_names=inputs,
                output_name=out_name,
                fn=fn,
                kernel_schedule=ksched,
                meta=meta,
            )
        )

    plan = plan_memory(
        mapped, allow_spill=allow_spill, hill_climb_iters=hill_climb_iters
    )
    routes: dict[str, int] = {}
    for ls in lowered:
        routes[ls.route] = routes.get(ls.route, 0) + 1
    lower_span.set(routes=routes).__exit__(None, None, None)
    model = CompiledModel(mapped=mapped, segments=lowered, memory_plan=plan)
    if aot:
        model.to_aot()
    return model
