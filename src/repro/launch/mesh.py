"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # The sharding rules (sharding/rules.py) place arrays through
    # PartitionSpec constraints and let GSPMD propagate the rest: Auto axes.
    # jax.make_mesh defaults to Explicit axes, whose sharding-in-types
    # refuses e.g. the embedding gather without an out_sharding.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (host) devices exist — for tests."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
