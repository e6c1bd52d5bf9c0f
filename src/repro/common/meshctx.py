"""Mesh context: one place that asks JAX "which mesh is active?" and "make
this mesh active", written against the installed JAX (0.9):
``jax.sharding.get_abstract_mesh``, ``jax.set_mesh``,
``jax.make_mesh(..., axis_types=...)``, ``jax.shard_map`` and the dict
returned by ``Compiled.cost_analysis()``.

Model/serving code goes through these names only (the `mesh-api` analyzer
rule enforces it):

  * ``current_mesh()`` returns the active mesh (concrete or abstract) or
    ``None``; never raises, never returns an *empty* mesh.
  * ``use_mesh(mesh)`` is a context manager activating ``mesh`` so that
    (a) ``current_mesh()`` sees it from any thread-locally nested code,
    (b) bare-``PartitionSpec`` sharding constraints resolve inside ``jit``,
    (c) ``shard_map`` collectives can bind its axis names.
  * ``make_mesh(shape, names)`` builds a mesh with Auto (or Explicit) axes.
  * ``axis_sizes_dict(mesh)`` maps axis name -> size for concrete *and*
    abstract meshes.
  * ``shard_map`` is ``jax.shard_map``.

``current_mesh()`` asks ``jax.sharding.get_abstract_mesh()`` first, then a
thread-local registry that ``use_mesh`` maintains itself, so a mesh
activated here is visible even where JAX reports none.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Optional, Sequence

import jax
from jax.sharding import Mesh

__all__ = [
    "current_mesh",
    "use_mesh",
    "make_mesh",
    "axis_sizes_dict",
    "shard_map",
    "cost_analysis_dict",
]

shard_map = jax.shard_map

# ---------------------------------------------------------------- resolution

_LOCAL = threading.local()  # .stack: list of meshes activated by use_mesh


def _registry_stack() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def _nonempty(mesh) -> Optional[Mesh]:
    """Normalize: an empty / axis-less mesh counts as 'no mesh'."""
    if mesh.empty or not mesh.axis_names:
        return None
    return mesh


def current_mesh() -> Optional[Mesh]:
    """The active (concrete or abstract) mesh, or None outside any context."""
    mesh = _nonempty(jax.sharding.get_abstract_mesh())
    if mesh is not None:
        return mesh
    stack = _registry_stack()
    return _nonempty(stack[-1]) if stack else None


# ---------------------------------------------------------------- activation


@contextlib.contextmanager
def use_mesh(mesh: Mesh) -> Iterator[Mesh]:
    """Activate `mesh` for the calling thread (``jax.set_mesh``), mirrored
    into the thread-local registry that ``current_mesh()`` falls back to."""
    stack = _registry_stack()
    stack.append(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        stack.pop()


# -------------------------------------------------------------- construction


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    explicit: bool = False,
) -> Mesh:
    """``jax.make_mesh`` with Auto axes, or sharding-in-types Explicit axes
    when `explicit=True`."""
    kind = jax.sharding.AxisType.Explicit if explicit else jax.sharding.AxisType.Auto
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names, axis_types=(kind,) * len(names))


# ------------------------------------------------------------------- queries


def axis_sizes_dict(mesh) -> dict:
    """{axis name: size} for concrete Mesh and AbstractMesh alike."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def cost_analysis_dict(compiled) -> dict:
    """`Compiled.cost_analysis()` as a dict ({} when the backend has none)."""
    return compiled.cost_analysis() or {}
