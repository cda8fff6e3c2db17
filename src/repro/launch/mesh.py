"""Device meshes.

``make_mesh`` is a FUNCTION (never a module-level mesh) so importing this
module never touches jax device state: a process that fakes its device
count via XLA_FLAGS must be able to import it before the backend starts.
"""

from __future__ import annotations

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over the first devices, every axis ``Auto``
    (e.g. (4, 2) on 8 host devices, or (2, 2) on a four-chip host)."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
