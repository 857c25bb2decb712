"""Mesh-aware activation sharding constraints, usable from model code.

Model code stays mesh-agnostic: ``constrain`` looks up the ambient abstract
mesh (set by ``with mesh:`` in the launcher) and becomes a no-op when there
is none (CPU unit tests) or when a dim does not divide.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _mesh():
    # abstract mesh (jax.set_mesh / use_mesh context)
    m = jax.sharding.get_abstract_mesh()
    if m.axis_names:
        return m
    # `with mesh:` (the launcher/dry-run convention) sets the physical
    # mesh on thread_resources, NOT the abstract mesh — check it too,
    # else every activation constraint in model code silently no-ops
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    if not m.empty and m.axis_names:
        return m
    return None


def _axis_size(mesh, ax):
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
        return n
    return dict(zip(mesh.axis_names, mesh.axis_sizes))[ax]


def batch_axes(mesh=None):
    mesh = mesh or _mesh()
    if mesh is None:
        return None
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None


def constrain(x, *spec_dims):
    """with_sharding_constraint with symbolic dims:

    'B' -> (pod, data) when divisible; 'S' -> model when divisible;
    'M' -> model when divisible; None -> unsharded.
    """
    mesh = _mesh()
    if mesh is None:
        return x
    names = mesh.axis_names
    dims = []
    for d, size in zip(spec_dims, x.shape):
        if d == "B":
            ax = batch_axes(mesh)
            dims.append(ax if ax and size % _axis_size(mesh, ax) == 0
                        else None)
        elif d in ("S", "M"):
            ok = "model" in names and size % _axis_size(mesh, "model") == 0
            dims.append("model" if ok else None)
        else:
            dims.append(None)
    if all(d is None for d in dims):
        return x
    return jax.lax.with_sharding_constraint(x, P(*dims))
