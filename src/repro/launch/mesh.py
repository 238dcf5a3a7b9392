"""Production mesh definitions.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (device count locks on first jax init).

  single pod : (16, 16)    axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips
  cell mesh  : (D,)        axis ("cells",)   — scenario-grid sharding
  client mesh: (D,)        axis ("clients",) — within-cell client sharding
  grid mesh  : (Dc, Dn)    axes ("cells", "clients") — both, composed

The dry-run launcher sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import so 512 placeholder CPU devices exist.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# Grid sharding wants a flat 1-D mesh over the (scenario × seed) cell
# axis regardless of how training meshes are shaped; within-cell client
# sharding (DESIGN.md §8) adds the composable "clients" axis. The
# factories live with the placement layer (DESIGN.md §5) and are
# re-exported here so drivers import every mesh from one module.
from repro.experiments.placement import (  # noqa: F401
    CELL_AXIS,
    CLIENT_AXIS,
    make_cell_mesh,
    make_client_mesh,
    make_grid_mesh,
)


def _auto_mesh(shape, axes):
    # Model code constrains activations with ``with_sharding_constraint``
    # under ``with mesh:`` (models/common.maybe_shard), which only accepts
    # Auto axes; ``jax.make_mesh`` defaults to Explicit ones.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke paths (tests, examples)."""
    return _auto_mesh((1, 1), ("data", "model"))
