"""Production mesh construction.

A *function*, not a module-level constant — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
device query).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods × 256 chips as (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh():
    """Whatever devices exist locally, as a 1×N (data, model) mesh — used by
    tests and the CPU examples."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"), axis_types=_auto(2))


def _auto(n: int):
    """GSPMD-propagated axes: the sharding rules place activations with
    ``with_sharding_constraint``, which ``Explicit`` axes (the
    ``make_mesh`` default) reject."""
    return (jax.sharding.AxisType.Auto,) * n
