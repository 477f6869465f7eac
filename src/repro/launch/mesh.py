"""Production meshes (assignment spec).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import.

Mesh axes are ``Auto``: the sharding rules (``repro.parallel.sharding``)
place params and let GSPMD propagate, which ``jax.make_mesh``'s default
``Explicit`` axes refuse (e.g. a gather from a vocab-sharded embedding).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — examples/tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         (AxisType.Auto,) * 2)
