"""Sweep-axis sharding: partition a ``run_sweep`` grid over a device mesh.

``run_sweep`` turns the paper's (seeds x budgets) experiment grids into
one vmapped ``lax.scan`` — on *one* device.  This module supplies the
pieces the engine composes into ``run_sweep_sharded``, the first
multi-device execution path:

* the flat configuration axis (every (seed, budget) pair, row-major with
  budgets outermost so it un-flattens back into the grid layout) is
  partitioned over the mesh's ``"sweep"`` axis with ``shard_map``;
* each device vmaps the *same* per-config scan over its local shard, so
  every configuration's trajectory is computed by exactly the program
  the single-device path runs — which is why the 1-D sweep mesh is
  bit-equal to the vmap path (pinned by tests/test_sweep_sharding.py);
* sweeps whose size does not divide the mesh are statically padded with
  copies of the last configuration (``pad_configs``) and the padding is
  sliced off after the gather — shapes stay static, no ragged shards;
* an optional ``"data"`` mesh axis distributes the per-round client
  window *inside* every scan (``repro.federated.sharded.
  sharded_window_eval``'s psum), giving the 2-D ``(sweep, data)`` mesh.

The mesh comes from ``repro.launch.mesh.make_sweep_mesh`` and the
partition specs from ``repro.launch.sharding.sweep_specs`` — the same
launch-layer helpers the production LM stack uses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import make_sweep_mesh
from repro.launch.sharding import sweep_specs

from .sharded import shard_map

__all__ = ["SWEEP_AXIS", "DATA_AXIS", "mesh_axes", "pad_configs",
           "pad_lane_tree", "sharded_sweep_fn", "default_sweep_mesh"]

SWEEP_AXIS = "sweep"
DATA_AXIS = "data"


def default_sweep_mesh(n_data: int = 1) -> Mesh:
    """All visible devices as a ``(sweep, data)`` mesh (data axis trivial
    by default: pure configuration parallelism)."""
    return make_sweep_mesh(n_data)


def mesh_axes(mesh: Mesh) -> tuple:
    """``(n_sweep, n_data)`` sizes of a sweep mesh (absent data axis = 1)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if SWEEP_AXIS not in sizes:
        raise ValueError(f"mesh {mesh.axis_names} has no {SWEEP_AXIS!r} "
                         "axis — build it with launch.mesh.make_sweep_mesh")
    return sizes[SWEEP_AXIS], sizes.get(DATA_AXIS, 1)


def pad_configs(keys: jnp.ndarray, budgets: jnp.ndarray, n_shards: int):
    """Pad the flat config axis up to a multiple of ``n_shards``.

    ``keys`` (n, 2) PRNG keys and ``budgets`` (n,) are padded with copies
    of the *last* configuration — a valid config, so the padded lanes
    trace/execute identically and their outputs are simply sliced off by
    the caller.  Returns ``(keys_padded, budgets_padded)`` with leading
    dim ``ceil(n / n_shards) * n_shards``.
    """
    n = keys.shape[0]
    n_pad = -(-n // n_shards) * n_shards
    if n_pad != n:
        reps = n_pad - n
        keys = jnp.concatenate(
            [keys, jnp.broadcast_to(keys[-1:], (reps,) + keys.shape[1:])])
        budgets = jnp.concatenate(
            [budgets, jnp.broadcast_to(budgets[-1:], (reps,))])
    return keys, budgets


def pad_lane_tree(tree, n_shards: int):
    """Pad every leaf's leading (lane) axis up to a multiple of
    ``n_shards`` with broadcast copies of the last lane — the pytree
    counterpart of ``pad_configs``, used for the per-lane schedule
    stack (``repro.scenarios.ScheduleArrays`` with a leading lane axis)
    that rides the sharded flat sweep alongside keys/budgets."""
    def pad(a):
        n = a.shape[0]
        n_pad = -(-n // n_shards) * n_shards
        if n_pad == n:
            return a
        return jnp.concatenate(
            [a, jnp.broadcast_to(a[-1:], (n_pad - n,) + a.shape[1:])])
    return jax.tree.map(pad, tree)


def sharded_sweep_fn(scan_config_fn, mesh: Mesh, scheduled: bool = False):
    """shard_map + jit a per-config scan into a mesh-sharded flat sweep.

    ``scan_config_fn(preds, y, costs, key, budget) -> out pytree`` runs
    ONE configuration (each leaf (T, ...)).  The returned callable takes
    the same stream arrays plus flat ``keys`` (n, 2) / ``budgets`` (n,)
    config arrays whose leading dim must divide the mesh's sweep axis
    (validated on every call — pad first with ``pad_configs``), and
    returns the out pytree with a leading (n,) config axis, assembled in
    config order.  Stream arrays are replicated on every device; only the
    config axis is partitioned.

    ``scheduled=True`` adds a trailing *per-lane* schedule-stack argument
    (``repro.scenarios.ScheduleArrays`` with a leading lane axis — one
    schedule row set per flat config, any mix of scenarios) partitioned
    over the sweep axis exactly like keys/budgets; expects
    ``scan_config_fn(..., sched)`` taking one lane's ``(T, ...)`` rows.
    Pad the stack alongside the configs with ``pad_lane_tree``.
    """
    in_specs, out_spec = sweep_specs(mesh, axis=SWEEP_AXIS)

    if scheduled:
        # schedule stack: lane-partitioned like keys/budgets (a pytree
        # prefix — every ScheduleArrays leaf shards its leading lane axis)
        in_specs = in_specs + (P(SWEEP_AXIS),)

        def per_shard(preds, y, costs, keys, budgets, sched):
            run = lambda k, b, s: scan_config_fn(preds, y, costs, k, b, s)
            return jax.vmap(run)(keys, budgets, sched)
    else:
        def per_shard(preds, y, costs, keys, budgets):
            run = lambda k, b: scan_config_fn(preds, y, costs, k, b)
            return jax.vmap(run)(keys, budgets)

    # out_spec leaves the data axis unmentioned: with a non-trivial data
    # axis every output is gather-replicated over it (sharded_window_eval),
    # so one copy per sweep shard is the whole answer.  Replication
    # checking is disabled because jax cannot track replication through
    # this scan-of-vmap.
    mapped = shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False)
    fn = jax.jit(mapped)

    def call(preds, y, costs, keys, budgets, sched=None):
        sweep_specs(mesh, n_configs=keys.shape[0], axis=SWEEP_AXIS)
        if scheduled:
            lanes = {a.shape[0] for a in jax.tree.leaves(sched)}
            if lanes != {keys.shape[0]}:
                raise ValueError(
                    f"sharded_sweep_fn: schedule stack lanes {lanes} do "
                    f"not match the {keys.shape[0]} flat configs — pad "
                    "with pad_lane_tree alongside pad_configs")
            return fn(preds, y, costs, keys, budgets, sched)
        return fn(preds, y, costs, keys, budgets)

    return call
