"""Pallas TPU kernel: RG-LRU linear recurrence with chunked state carry.

h_t = a_t * h_{t-1} + b_t over the time axis, vectorised across channel
lanes.  The grid walks (batch, channel-block, time-chunk) with the time
chunk innermost; the running state h lives in VMEM scratch and persists
across chunk steps — the recurrent analogue of the flash-attention
accumulator pattern.  Inside a chunk the recurrence is an in-register
``fori_loop`` over rows (VPU elementwise work, no MXU).

Chunk (bt) and channel-block (bw) sizes come from the LOMA DSE on the
``scan`` workload against the TPU VPU module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tpu import interpret_mode

__all__ = ["rglru_scan"]


def _kernel(a_ref, b_ref, o_ref, h_ref, *, bt: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)  # (bt, bw)
    b = b_ref[0].astype(jnp.float32)

    def step(t, carry):
        h, out = carry
        h = a[t] * h + b[t]
        out = jax.lax.dynamic_update_index_in_dim(out, h, t, 0)
        return h, out

    h0 = h_ref[0]  # (bw,)
    out0 = jnp.zeros_like(a)
    h, out = jax.lax.fori_loop(0, bt, step, (h0, out0))
    h_ref[0] = h
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_w", "block_t"))
def rglru_scan(
    a: jax.Array,  # (B, T, W) decay in (0,1]
    b: jax.Array,  # (B, T, W) input term
    *,
    block_w: int = 128,
    block_t: int = 128,
) -> jax.Array:
    B, T, W = a.shape
    bw, bt = min(block_w, W), min(block_t, T)
    assert W % bw == 0 and T % bt == 0

    return pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(B, W // bw, T // bt),  # time innermost: h carries across chunks
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda bb, wi, ti: (bb, ti, wi)),
            pl.BlockSpec((1, bt, bw), lambda bb, wi, ti: (bb, ti, wi)),
        ],
        out_specs=pl.BlockSpec((1, bt, bw), lambda bb, wi, ti: (bb, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((B, T, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret_mode(),
    )(a, b)
