"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
initialization, and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding hints
    (``core/hints.py``, ``train/step.py``) place arrays with
    ``with_sharding_constraint``, which JAX refuses on ``Explicit`` axes,
    the default ``make_mesh`` builds."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return _auto_mesh(shape, axes)


def make_host_mesh(model_par: int = 1):
    """Single-host mesh for smoke tests / examples (1 device)."""
    n = len(jax.devices())
    return _auto_mesh((n // model_par, model_par), ("data", "model"))
