"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.

Baseline production meshes (the assignment's):
  single-pod: (data=16, model=16)           = 256 chips (one v5e pod)
  multi-pod : (pod=2, data=16, model=16)    = 512 chips

MRA-factored meshes (paper C1; same devices, model axis split K-ways) live
in core/replication.make_mra_mesh.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist right now, as a 1D (data,) mesh — for local
    examples and tests that want a real (non-dry-run) mesh."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
