"""Pallas TPU kernel: int8 GEMM with fused requantization epilogue.

The paper-faithful kernel: DIANA/NE16 execute conv/GEMM with
re-quantization, ReLU and clipping "directly at the output" (Sec. V-A),
after MATCH's HW-aware pass rewrites mul-add-div chains into
f(x) = (x*M + B) >> S (Table II).  This kernel is the TPU adaptation:

* int8 A (M,K) x int8 W (K,N) accumulated in int32 on the MXU,
* fused epilogue: per-output-channel multiplier M and bias B, arithmetic
  right shift S, optional ReLU, clip to int8 —
  all while the accumulator tile is still resident in VMEM.

BlockSpec tiling (bm, bn, bk) comes from the LOMA DSE over the TPU
MatchTarget (repro.kernels.ops), exactly as the MCU targets get their
L1 tiling from the same engine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tpu import interpret_mode

__all__ = ["matmul_requant"]


def _round_shift_even(t: jax.Array, shift: int) -> jax.Array:
    """round-half-to-even(t / 2^shift) in pure int32 arithmetic.

    Matches ``jnp.round(x / 2**S)`` on integer-valued inputs, so a kernel
    using this epilogue is bit-exact against the float requant oracle.
    """
    if shift <= 0:
        return t
    q = jax.lax.shift_right_arithmetic(t, shift)  # floor(t / 2^S)
    r = t - (q << shift)  # remainder in [0, 2^S)
    half = 1 << (shift - 1)
    inc = jnp.where(r > half, 1, jnp.where(r == half, q & 1, 0))
    return q + inc


def _kernel(a_ref, w_ref, mult_ref, bias_ref, o_ref, acc_ref, *, shift: int, relu: bool, rounding: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands go to the MXU as they are; Mosaic refuses an int32 dot
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...]
        y = acc * mult_ref[...] + bias_ref[...]
        if rounding == "even":
            y = _round_shift_even(y, shift)
        else:
            y = jax.lax.shift_right_arithmetic(y, shift)
        if relu:
            y = jnp.maximum(y, 0)
        o_ref[...] = jnp.clip(y, -128, 127).astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "shift", "relu", "rounding", "interpret"),
)
def matmul_requant(
    a: jax.Array,  # (M, K) int8
    w: jax.Array,  # (K, N) int8
    mult: jax.Array,  # (N,) int32 per-channel multiplier
    bias: jax.Array,  # (N,) int32
    *,
    shift: int = 8,
    relu: bool = False,
    rounding: str = "floor",  # "floor" (HW shift) | "even" (interpreter round)
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    M, K = a.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)

    mult2 = jnp.broadcast_to(mult[None, :], (1, N)).astype(jnp.int32)
    bias2 = jnp.broadcast_to(bias[None, :], (1, N)).astype(jnp.int32)

    return pl.pallas_call(
        functools.partial(_kernel, shift=shift, relu=relu, rounding=rounding),
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret_mode() if interpret is None else interpret,
    )(a, w, mult2, bias2)
