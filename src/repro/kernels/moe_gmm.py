"""Pallas TPU kernel: grouped expert matmul (MoE FFN inner GEMMs).

Computes y[e] = x[e] @ w[e] for every expert e over capacity-dispatched
activations (E, C, D) x (E, D, F) -> (E, C, F).  The expert dimension is
the outermost grid axis so an expert's weight tile streams HBM→VMEM once
per (C, F) sweep — the weight-stationary schedule the LOMA DSE picks for
this workload.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tpu import interpret_mode

__all__ = ["moe_gmm"]


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d")
)
def moe_gmm(
    x: jax.Array,  # (E, C, D)
    w: jax.Array,  # (E, D, F)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 512,
) -> jax.Array:
    E, C, D = x.shape
    _, _, F = w.shape
    bc, bf, bd = min(block_c, C), min(block_f, F), min(block_d, D)
    assert C % bc == 0 and F % bf == 0 and D % bd == 0

    return pl.pallas_call(
        _kernel,
        grid=(E, C // bc, F // bf, D // bd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret_mode(),
    )(x, w)
