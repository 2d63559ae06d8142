"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (jax locks the device count on first backend init, and the
dry-run must set XLA_FLAGS before that happens).
"""
from __future__ import annotations

import jax

from repro.distribution.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Mesh over whatever devices exist (tests / CPU runs)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))
