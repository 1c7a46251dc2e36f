"""Production mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — dryrun.py must set XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules here
    are constraints for the partitioner (``with_sharding_constraint``,
    ``shard_map``), which ``Explicit`` axes — JAX's default — reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_for(n_data: int, n_model: int):
    """("data", "model") mesh sized for this process's devices.

    Requested extents are clamped to what ``jax.device_count()`` can
    actually tile: ``n_model`` first (model parallelism degrades to
    replication more gracefully than data parallelism degrades to
    serialization), then ``n_data`` to the largest count that divides the
    remaining pool.  ``make_mesh_for(8, 1)`` on a 4-device host is a 4x1
    mesh, on a single device 1x1 — callers write one mesh line that runs
    anywhere from laptops to pods."""
    if n_data < 1 or n_model < 1:
        raise ValueError(f"mesh extents must be >= 1, got "
                         f"({n_data}, {n_model})")
    avail = jax.device_count()
    n_model = min(n_model, avail)
    while avail % n_model:
        n_model -= 1
    n_data = min(n_data, avail // n_model)
    while (avail // n_model) % n_data:
        n_data -= 1
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh on whatever single device exists — smoke tests / examples."""
    return make_mesh_for(1, 1)


def data_axes(mesh) -> tuple:
    """All data-parallel axes of a mesh ('pod' is an outer DP axis)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def data_devices(mesh) -> tuple:
    """The device ring of one model-parallel slice: the devices a
    data-partitioned ``shard_map`` ring (repro.shard) runs across, in
    data-axis order."""
    n_model = 1
    for a in mesh.axis_names:
        if a not in ("pod", "data"):
            n_model *= mesh.shape[a]
    flat = mesh.devices.reshape(-1, n_model)
    return tuple(flat[:, 0])
