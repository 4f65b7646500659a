"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Shapes per the assignment:
  single pod : (16, 16)      axes ("data", "model")   — 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") — 512 chips
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the repo's sharding code
    (``with_sharding_constraint``, ``NamedSharding`` placement, the
    fan-out's ``shard_map``) is written for compiler-propagated
    shardings, while ``make_mesh`` defaults to ``Explicit`` axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_parallel: int = 1):
    """Elastic helper: best (data, model) mesh for whatever is alive.

    Used by the elastic-restart path (repro.train.elastic) when a pod
    comes back with fewer healthy hosts.
    """
    model_parallel = max(1, min(model_parallel, n_devices))
    while n_devices % model_parallel:
        model_parallel -= 1
    return _auto_mesh(
        (n_devices // model_parallel, model_parallel), ("data", "model")
    )
