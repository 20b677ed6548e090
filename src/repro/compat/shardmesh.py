"""Mesh/sharding constructors, spelled for JAX 0.9.

Every mesh, sharding-context, or shard_map construction in this repo goes
through here instead of calling ``jax.*`` directly, so a later JAX that
renames one of them changes this file only.
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

P = PartitionSpec


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              axis_types: Optional[tuple] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with all-Auto axis types unless told otherwise."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axes)
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=tuple(axis_types),
        **({"devices": devices} if devices is not None else {}))


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """``with jax.set_mesh(mesh)``, yielding the mesh."""
    with jax.set_mesh(mesh):
        yield mesh


def shard_map(f, *, mesh: Mesh, in_specs: Any, out_specs: Any,
              check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()``, with ``{}`` where XLA reports none."""
    return compiled.cost_analysis() or {}
