"""Production mesh construction. A FUNCTION (not module-level) so importing
never touches jax device state."""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """Mesh whose axes are all Auto: the models place activations with
    ``with_sharding_constraint``, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh for smoke/bench paths (axis names match production)."""
    return _auto_mesh((1, 1), ("data", "model"))
