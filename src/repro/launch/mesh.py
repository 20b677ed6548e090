"""Production mesh builders + the elastic shrink/grow helpers.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests keep their single device.
Construction goes through repro.compat (see src/repro/compat/).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.compat import Mesh
from repro.compat import make_mesh as _compat_make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _compat_make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh with Auto axis types (tests / examples)."""
    return _compat_make_mesh(shape, axes)


def slice_for_process(mesh: Optional[Mesh], process_index: int,
                      axis: str = "data") -> Optional[int]:
    """Map a process (host) index to the ``axis`` slice wholly owned by its
    devices — the attribution step between "process P is slow" (per-host
    heartbeats, runtime/monitor.py) and "drop slice i" (``shrink_mesh``).

    Returns ``None`` when no single slice is wholly owned by that process
    (no mesh, the axis is absent, or the host's devices straddle slices —
    e.g. a host owning a whole *model* column): the caller falls back to
    its by-convention choice rather than evicting healthy devices.
    """
    if mesh is None or axis not in mesh.axis_names:
        return None
    ax = mesh.axis_names.index(axis)
    devices = np.asarray(mesh.devices)
    moved = np.moveaxis(devices, ax, 0)
    for i in range(moved.shape[0]):
        procs = {getattr(d, "process_index", 0) for d in moved[i].flat}
        if procs == {process_index}:
            return i
    return None


def shrink_mesh(mesh: Optional[Mesh], drop_axis_index: Optional[int] = None,
                axis: str = "data", min_axis_size: int = 1,
                drop_process_index: Optional[int] = None) -> Optional[Mesh]:
    """Rebuild ``mesh`` without one slice along ``axis`` — the elastic
    straggler-eviction path: dropping index ``drop_axis_index`` along the
    data axis evicts that slice's devices (the suspected-slow host) and the
    remaining device grid becomes a mesh with the same axis names.

    Returns ``None`` when the mesh cannot shrink: no mesh, the axis is
    absent, or shrinking would take it below ``min_axis_size`` (the
    trainer's ``min_data_parallel`` floor). Raises on an out-of-range index
    — the caller named a slice that does not exist.

    The surviving devices keep their grid positions (no re-layout), so
    every other slice's placement is stable across the shrink — only the
    evicted slice's shards move, through the elastic state reshard.

    ``drop_process_index`` names the slow *host* instead of a grid index
    (the attribution path): it resolves through ``slice_for_process`` and
    returns ``None`` when that host does not own a whole slice — the
    caller keeps its by-convention fallback rather than guessing.
    """
    if mesh is None or axis not in mesh.axis_names:
        return None
    if drop_process_index is not None:
        if drop_axis_index is not None:
            raise ValueError(
                "pass drop_axis_index or drop_process_index, not both")
        drop_axis_index = slice_for_process(mesh, drop_process_index, axis)
        if drop_axis_index is None:
            return None
    elif drop_axis_index is None:
        raise ValueError("need drop_axis_index or drop_process_index")
    ax = mesh.axis_names.index(axis)
    devices = np.asarray(mesh.devices)
    size = devices.shape[ax]
    if not 0 <= drop_axis_index < size:
        raise ValueError(
            f"drop_axis_index {drop_axis_index} out of range for "
            f"{axis}={size}")
    if size <= 1 or size - 1 < min_axis_size:
        return None
    kept = np.delete(devices, drop_axis_index, axis=ax)
    # the Mesh constructor (via repro.compat) takes the device grid as-is —
    # no re-layout, unlike the make_mesh convenience path
    return Mesh(kept, mesh.axis_names, axis_types=mesh.axis_types)


def grow_mesh(mesh: Optional[Mesh], slice_devices,
              insert_axis_index: Optional[int] = None,
              axis: str = "data") -> Optional[Mesh]:
    """Rebuild ``mesh`` with one extra slice along ``axis`` — the elastic
    re-admission path: an evicted host that returned contributes its
    devices back as a slice, re-inserted at ``insert_axis_index`` (its old
    grid position, so a shrink→grow round trip restores the original
    device grid exactly; default: appended after the last slice).

    ``slice_devices`` must match the shape of one existing slice (the
    grid with ``axis`` removed; a flat sequence of the right length is
    reshaped) and be disjoint from the surviving devices. Returns ``None``
    when there is no mesh or the axis is absent; raises ``ValueError`` on
    a shape mismatch, device overlap, or out-of-range insert index — the
    caller offered a slice that cannot rejoin this grid.

    Surviving devices keep their grid positions, mirroring ``shrink_mesh``:
    only the returning slice's shards materialize fresh (from the restored
    checkpoint / the live-state reshard in ``Trainer.readmit``).
    """
    if mesh is None or axis not in mesh.axis_names:
        return None
    ax = mesh.axis_names.index(axis)
    devices = np.asarray(mesh.devices)
    size = devices.shape[ax]
    if insert_axis_index is None:
        insert_axis_index = size
    if not 0 <= insert_axis_index <= size:
        raise ValueError(
            f"insert_axis_index {insert_axis_index} out of range for "
            f"{axis}={size} (0..{size} valid)")
    slice_shape = devices.shape[:ax] + devices.shape[ax + 1:]
    new = np.asarray(slice_devices, dtype=object)
    if new.shape != slice_shape:
        if new.size != int(np.prod(slice_shape)):
            raise ValueError(
                f"slice of {new.size} devices cannot fill a "
                f"{slice_shape} grid slice")
        new = new.reshape(slice_shape)
    overlap = set(d.id for d in devices.flat) & set(d.id for d in new.flat)
    if overlap:
        raise ValueError(
            f"returning slice overlaps the live mesh: device ids "
            f"{sorted(overlap)}")
    grown = np.insert(devices, insert_axis_index, new, axis=ax)
    return Mesh(grown, mesh.axis_names, axis_types=mesh.axis_types)
