"""Production mesh construction (deliverable e).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (only the dryrun CLI sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the round code leaves resharding (the cohort gather/scatter
    # around shard_map) to the partitioner. jax.make_mesh defaults to
    # Explicit axes, under which that scatter is a type error.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 16×16 = 256 chips single-pod; 2×16×16 = 512 two-pod.

    FL mapping: clients live on ("pod","data"); tensor parallelism on
    "model". The pod axis is the slowest (DCI links between pods).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Client mesh over every device of this host: ("data"=n, "model"=1).

    One chip (or a CPU test process) gives a 1×1 mesh on which the sharded
    runtime's psums are identities; a 4-chip v5e host partitions the clients
    four ways. Pod-scale jobs (≥ 256 devices) use make_production_mesh."""
    return _mesh((jax.device_count(), 1), ("data", "model"))
