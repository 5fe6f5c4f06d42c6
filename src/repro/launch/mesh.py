"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Shapes per the brief:

* single pod:  (16, 16)    axes ("data", "model")   = 256 chips
* multi pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips

The "pod" axis is pure data parallelism across pods (gradient all-reduce
crosses the inter-pod links); "model" carries TP/EP within a pod row.

Mesh axes are ``Auto``: the sharding rules in :mod:`repro.distributed`
are GSPMD constraints (``with_sharding_constraint``), which jax refuses on
``Explicit`` axes — the default of ``jax.make_mesh``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (CPU) devices exist — smoke tests."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
