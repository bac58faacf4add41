"""Production meshes.

Functions, not module-level constants — importing this module never
touches jax device state. The dry-run forces 512 host devices via
XLA_FLAGS before any jax import (see dryrun.py).
"""
from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_local_mesh(model: int = 1):
    """Smoke/test mesh over whatever devices exist (usually 1 CPU)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(_AUTO,) * 2)
