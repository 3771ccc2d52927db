"""Production mesh definitions.

TPU v5e target: one pod = 256 chips as a (data=16, model=16) mesh;
multi-pod = 2 pods = 512 chips with a leading "pod" axis used for outer
data parallelism (the data-center network axis).

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before any jax initialization and only then builds the mesh.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["make_production_mesh", "make_sweep_mesh", "data_axes",
           "MESH_SHAPES", "as_shardings"]

MESH_SHAPES = {
    "pod": ((16, 16), ("data", "model")),
    "multipod": ((2, 16, 16), ("pod", "data", "model")),
}


def make_production_mesh(*, multi_pod: bool = False):
    shape, axes = MESH_SHAPES["multipod" if multi_pod else "pod"]
    return jax.make_mesh(shape, axes)


def make_sweep_mesh(n_data: int = 1, *, devices=None) -> Mesh:
    """``("sweep", "data")`` mesh over the visible devices.

    The simulation engine's sharded sweeps (``repro.federated.engine.
    run_sweep_sharded``) partition the flat (seeds x budgets) configuration
    axis over ``sweep`` and — when ``n_data > 1`` — the per-round client
    window over ``data`` (the same client/data axis `repro.federated.
    sharded` psums over).  ``n_data`` must divide the device count; the
    remaining devices form the sweep axis.  Like ``make_production_mesh``
    this is a function, not a module constant, so importing never touches
    jax device state.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n_dev = len(devices)
    if n_data < 1 or n_dev % n_data:
        raise ValueError(f"n_data={n_data} does not divide the "
                         f"{n_dev} visible devices")
    return Mesh(np.array(devices).reshape(n_dev // n_data, n_data),
                ("sweep", "data"))


def as_shardings(mesh, specs):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``, for
    ``jit`` in/out_shardings that must not depend on a global mesh."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, PartitionSpec) else s,
        specs, is_leaf=lambda s: isinstance(s, PartitionSpec))


def data_axes(mesh) -> tuple:
    """The composite batch-parallel axis spec for this mesh."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)
