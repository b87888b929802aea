"""Device mesh construction + host→shard placement.

The placement rule replaces shyama's ``assign_partha_madhava``
(``server/gy_shconnhdlr.cc:5876``): instead of a capacity/affinity-aware
central assignment with DB-backed stickiness, hosts map to mesh shards by a
stable modulus of host id — deterministic, stateless, and uniform. Region/
zone affinity returns at the multi-slice level (DCN axis) where it matters
for TPUs; within a slice every shard is equidistant over ICI.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HOST_AXIS = "hosts"
SLICE_AXIS = "slices"    # DCN axis of a multi-slice mesh (outer)

def make_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (HOST_AXIS,))


def make_mesh2d(n_slices: int, per_slice: int) -> Mesh:
    """Multi-slice mesh: (slices × hosts) — the DCN tier (SURVEY §2.6
    multi-slice; the madhava-per-DC / shyama-across-DCs hierarchy).

    The outer ``slices`` axis rides DCN between slices; the inner
    ``hosts`` axis rides ICI within a slice. Collectives written against
    ``axes_of(mesh)`` reduce over both; the pairing dispatch routes in
    two stages so each flow crosses DCN at most once.
    """
    devs = jax.devices()
    need = n_slices * per_slice
    if len(devs) < need:
        raise RuntimeError(f"need {need} devices, have {len(devs)}")
    grid = np.asarray(devs[:need]).reshape(n_slices, per_slice)
    return Mesh(grid, (SLICE_AXIS, HOST_AXIS))


def axes_of(mesh: Mesh) -> tuple:
    """The mesh's shard axes, outermost first (collectives reduce over
    all of them; the stacked state's leading dim shards over the tuple)."""
    return tuple(mesh.axis_names)


def gather_all(x, axes):
    """all_gather over every mesh axis, innermost first (tiled) — the
    multi-axis gather used by every rollup path."""
    from jax import lax

    for ax in reversed(axes):
        x = lax.all_gather(x, ax, tiled=True)
    return x


def shard_of_host(host_id, n_shards: int):
    """Stable host→shard placement (works on np or jnp arrays)."""
    return host_id % n_shards


def leading_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding that splits leaves on their leading (shard) axis
    over every mesh axis (1-D and multi-slice meshes alike)."""
    return NamedSharding(mesh, P(axes_of(mesh)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
