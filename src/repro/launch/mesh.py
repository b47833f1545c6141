"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the pod
axis is a pure data-parallel (DCN-connected) replica dimension.

A FUNCTION, not a module constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh for CPU tests (requires forced host device count)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(_AUTO, _AUTO))


def make_engine_mesh(tp: int, offset: int = 0):
    """1×tp ("data","model") mesh for one FLOWSERVE TE: the TE's NPUs form a
    pure tensor-parallel SPMD group; data parallelism happens across TEs
    (the JE schedules requests over engines), never inside one (DESIGN.md §5).

    ``offset`` places the TE on devices [offset, offset+tp) so co-resident
    TEs (a PD pair, a fork source+target) occupy DISJOINT device windows and
    DistFlow's cross-mesh reshards move between genuinely different device
    sets (DESIGN.md §7).
    """
    n = jax.device_count()
    if offset + tp > n:
        raise RuntimeError(
            f"EngineConfig tp={tp} at device_offset={offset} exceeds the "
            f"visible device count {n}; for simulated-host runs set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{max(offset + tp, 8)} before jax initializes")
    if offset == 0:
        return make_host_mesh(data=1, model=tp)
    import numpy as np
    devices = np.asarray(jax.devices()[offset:offset + tp]).reshape(1, tp)
    return jax.sharding.Mesh(devices, ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
