"""Mesh helpers. The production mesh itself lives in repro.launch.mesh."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AbstractMesh, AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None) -> Mesh:
    """The one concrete-mesh constructor: every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to Explicit axes, under which the models'
    ``with_sharding_constraint`` calls and the device tier's padded slicing of
    uneven leaves are refused; every mesh in the program and its tests comes
    from here. ``devices`` defaults to ``jax.devices()``; pass described
    topology devices to compile for a chip that is not attached.
    """
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def abstract_mesh(*axes: tuple[str, int]) -> AbstractMesh:
    """``AbstractMesh`` with Auto axes from (name, size) pairs, e.g.
    ``abstract_mesh(("data", 16), ("model", 16))``."""
    names = tuple(n for n, _ in axes)
    sizes = tuple(s for _, s in axes)
    return AbstractMesh(sizes, names, axis_types=(AxisType.Auto,) * len(names))


def mesh_axis_size(mesh: Mesh, axes: tuple[str, ...] | str | None) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """The axes that constitute the data-parallel/failure dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def flat_device_index(mesh: Mesh) -> np.ndarray:
    """device_id -> flat index in the mesh's row-major device ordering."""
    return np.array([d.id for d in mesh.devices.flat])


def hosts_of_mesh(mesh: Mesh, host_chips: int = 8) -> dict[int, list[int]]:
    """host index -> device ids, assuming device ids dense & hosts contiguous."""
    out: dict[int, list[int]] = {}
    for d in mesh.devices.flat:
        out.setdefault(d.id // host_chips, []).append(d.id)
    return out


def topology_of_mesh(
    mesh: Mesh,
    n_ranks: int | None = None,
    host_chips: int = 8,
    hosts_per_rack: int = 4,
    racks_per_pod: int = 4,
    placement_level: str = "rack",
):
    """Derive a :class:`repro.core.topology.ClusterTopology` for the engine's
    rank space from the physical mesh. One engine rank = one data-axis
    coordinate; its host is read off the mesh's device ordering (first device
    of each data slice, ``hosts_of_mesh`` convention), and the rack/pod
    levels follow the ``regular()`` contiguous packing above that. The
    result is what ``EngineConfig.topology`` / ``VirtualCluster(topology=)``
    expect for domain-aware parity placement (DESIGN.md §16)."""
    from repro.core.topology import ClusterTopology

    if n_ranks is None:
        n_ranks = mesh_axis_size(mesh, data_axes(mesh)) or 1
    devs = [d.id for d in mesh.devices.flat]
    # Devices per engine rank under row-major ordering with the data axes
    # leading (launch.mesh convention): a contiguous block per rank.
    per_rank = max(len(devs) // max(n_ranks, 1), 1)
    labels = []
    for r in range(n_ranks):
        lead = devs[min(r * per_rank, len(devs) - 1)]
        host = lead // host_chips
        rack = host // hosts_per_rack
        pod = rack // racks_per_pod
        labels.append((host, rack, pod))
    return ClusterTopology(
        labels=tuple(labels),
        placement_level=placement_level,
        name=f"mesh[{','.join(f'{k}={v}' for k, v in mesh.shape.items())}]",
    )
