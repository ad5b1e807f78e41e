"""Mesh construction — the one place a ``jax.sharding.Mesh`` is built.

Functions, not module-level constants: importing this module never
touches jax device state (required for the dry-run's forced-512-device
initialization order).

Every axis is ``AxisType.Auto``: sharding is propagated by the compiler
from the ``NamedSharding``/``with_sharding_constraint`` annotations the
models carry. (``jax.make_mesh`` defaults to Explicit axes, under which a
sharded contraction dimension is a type error rather than a collective.)

Single pod: (data=16, model=16) — 256 v5e chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the "pod" axis is an
extra DP dimension by default (DESIGN.md §5), with PP over "pod" available
via repro.parallel.pipeline.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (CPU smoke / small-host runs)."""
    n = len(jax.devices())
    if n % model_axis:
        model_axis = 1
    return make_mesh((n // model_axis, model_axis), ("data", "model"))
