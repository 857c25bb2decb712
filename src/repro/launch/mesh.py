"""Production meshes.  Functions only — importing this module never touches
jax device state; ``dryrun.py`` sets XLA_FLAGS for 512 placeholder devices
before any jax import."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules place
    arrays through ``with_sharding_constraint``, which only Auto axes take."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist locally, as a (data, model) mesh — used by
    examples and tests on CPU."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
