"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model") — the 'pod' axis
crosses DCN and carries either extra DP (default) or the compressed gradient
reduction (parallel/compression.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model stack places arrays with
    ``in_shardings``/``with_sharding_constraint`` and lets XLA propagate
    the rest, which explicit axes (``make_mesh``'s default) refuse for
    gathers and einsums whose output sharding is ambiguous."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return _auto_mesh((n // model, model), ("data", "model"))
