"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state -- required because the dry-run forces 512 host
devices via XLA_FLAGS before first jax init, while tests/benches must see
a single device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis Auto (sharding by annotation)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) single-pod or (2, 16, 16) two-pod production mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_shape_for(devices: int, *, model_parallel: int = 16,
                   pods: int = 1):
    """Pure factorization behind :func:`make_mesh_for` -- returns
    ``(shape, axis_names)`` without touching jax device state, so the
    awkward-count behavior is unit-testable on any box.

    Hardened for awkward counts: `model` is the largest divisor of
    `devices` not exceeding `model_parallel` (odd / non-power-of-two
    counts land on a real factorization instead of halving past valid
    divisors or dividing by zero), the pod axis only materializes when
    it divides the remainder, and impossible inputs raise instead of
    deriving a degenerate mesh.
    """
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if model_parallel < 1:
        raise ValueError(
            f"model_parallel must be >= 1, got {model_parallel}")
    if pods < 1:
        raise ValueError(f"pods must be >= 1, got {pods}")
    model = max(m for m in range(1, min(model_parallel, devices) + 1)
                if devices % m == 0)
    rest = devices // model
    pod = pods if pods > 1 and rest % pods == 0 else 1
    data = rest // pod
    if pod > 1:
        return (pod, data, model), ("pod", "data", "model")
    return (data, model), ("data", "model")


def make_mesh_for(devices: int, *, model_parallel: int = 16,
                  pods: int = 1):
    """Elastic variant: build the best (pod, data, model) mesh for an
    arbitrary device count (restart-on-fewer-hosts path).  See
    :func:`mesh_shape_for` for the factorization rules."""
    shape, axes = mesh_shape_for(devices, model_parallel=model_parallel,
                                 pods=pods)
    return make_mesh(shape, axes)


def make_solver_mesh(shards: int):
    """1-D mesh for the distributed-conquer eigensolver: `shards` devices
    on a single axis named `dist.sharding.SOLVER_AXIS`.

    The D&C tree pairs nodes, so the shard count must be a power of two;
    and the devices must already be visible -- forcing host devices after
    first jax init silently does nothing, so a shortfall here raises
    with the fix spelled out rather than falling back to one device.
    """
    from repro.dist.sharding import SOLVER_AXIS

    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if shards & (shards - 1):
        raise ValueError(
            f"shards must be a power of two (the D&C tree pairs "
            f"nodes), got {shards}")
    avail = jax.device_count()
    if shards > avail:
        raise ValueError(
            f"solver mesh needs {shards} devices but only {avail} "
            f"visible; force host devices before first jax init "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count={shards}, "
            f"or run.py --mesh {shards})")
    return make_mesh((shards,), (SOLVER_AXIS,))


def describe(mesh) -> str:
    return " x ".join(f"{k}={v}" for k, v in mesh.shape.items())
