"""What the Pallas TPU lowering (Mosaic) accepts, derived in one place.

* :func:`interpret_mode` — kernels run under the Pallas interpreter only
  on the CPU backend; on a TPU they compile through Mosaic.  It is
  derived from the backend, never passed down the compile path.
* :func:`tpu_block` — snaps a DSE tile to a legal BlockSpec dim: the last
  dim of a block must be a multiple of 128 (a lane) or the whole array
  dim, the second-to-last a multiple of 8 (a sublane) or the whole dim.
"""

from __future__ import annotations

import jax

__all__ = ["LANE", "SUBLANE", "interpret_mode", "tpu_block"]

LANE = 128
SUBLANE = 8


def interpret_mode() -> bool:
    """True when Pallas kernels must run interpreted (CPU backend)."""
    return jax.default_backend() == "cpu"


def tpu_block(block: int, dim: int, quantum: int) -> int:
    """Largest multiple of ``quantum`` that divides ``dim`` and is no larger
    than ``block`` (raised to one quantum), else the whole ``dim``.

    The result always tiles ``dim`` exactly, as the kernels require; the
    DSE's ceil-padded tiles are snapped down.
    """
    if block >= dim:
        return dim
    b = max(block, quantum) // quantum * quantum
    while b >= quantum:
        if dim % b == 0:
            return b
        b -= quantum
    return dim
