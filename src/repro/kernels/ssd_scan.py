"""Pallas TPU kernel: Mamba-2 SSD chunk scan with carried state.

Per (batch, head) the grid walks time chunks; the (P, N) SSM state lives
in VMEM scratch and persists across chunks.  Each chunk does the SSD
dual form entirely on-chip:

  y_diag = ((C B^T) .* L) xb          (intra-chunk, MXU matmuls)
  y_off  = C h^T .* exp(a_cs)         (state contribution)
  h     <- exp(a_cs[-1]) h + (decay .* xb)^T B   (state update)

Inputs are pre-scaled by the wrapper: xb = x*dt, a = dt*A (so the kernel
is the pure dual-form recurrence).  Chunk length bt is the DSE knob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tpu import interpret_mode

__all__ = ["ssd_scan"]


def _kernel(xb_ref, a_ref, b_ref, c_ref, o_ref, h_ref, *, bt: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = xb_ref[0, 0].astype(jnp.float32)  # (bt, P)
    a = a_ref[0, 0].astype(jnp.float32)  # (bt,)
    Bm = b_ref[0].astype(jnp.float32)  # (bt, N)
    Cm = c_ref[0].astype(jnp.float32)  # (bt, N)

    a_cs = jnp.cumsum(a)  # (bt,)
    # segsum: seg[i, j] = sum_{j<k<=i} a_k, masked lower-tri
    seg = a_cs[:, None] - a_cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bt, bt)
    y_diag = jax.lax.dot_general(
        scores * L, xb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bt, P)

    h = h_ref[...]  # (P, N)
    y_off = jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bt, P)
    y_off = y_off * jnp.exp(a_cs)[:, None]

    decay_states = jnp.exp(a_cs[-1] - a_cs)  # (bt,)
    h_new = jnp.exp(a_cs[-1]) * h + jax.lax.dot_general(
        xb * decay_states[:, None], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P, N)
    h_ref[...] = h_new
    o_ref[0, 0] = (y_diag + y_off).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t",))
def ssd_scan(
    xb: jax.Array,  # (B, H, T, P)  x pre-scaled by dt
    a: jax.Array,  # (B, H, T)     dt * A  (<= 0)
    Bm: jax.Array,  # (B, T, N)
    Cm: jax.Array,  # (B, T, N)
    *,
    block_t: int = 128,
) -> jax.Array:
    B, H, T, P = xb.shape
    N = Bm.shape[-1]
    bt = min(block_t, T)
    assert T % bt == 0

    return pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(B, H, T // bt),
        in_specs=[
            pl.BlockSpec((1, 1, bt, P), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bt), lambda b, h, t: (b, h, t)),
            pl.BlockSpec((1, bt, N), lambda b, h, t: (b, t, 0)),
            pl.BlockSpec((1, bt, N), lambda b, h, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bt, P), lambda b, h, t: (b, h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret_mode(),
    )(xb, a, Bm, Cm)
