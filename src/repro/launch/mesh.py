"""Production mesh construction + topology-aware device ordering.

``make_production_mesh`` is the deliverable entry point: 16×16
("data","model") per pod, (2,16,16) ("pod","data","model") across two
pods. With ``topology_aware=True`` the physical device order is permuted
by the paper's priority walk (core/placement.py) before the mesh is
built, so the high-traffic "model" axis lands on minimal-hop ICI rings
and the coordinator (logical position 0) sits at the topology centroid —
the thread→core binding of §IV, chip-granular.

Importing this module never touches jax device state; everything is
behind functions.
"""

from __future__ import annotations

import numpy as np

from repro.core import placement
from repro.core import topology as topo_mod
from repro.core.routing import expert_steal_table

__all__ = ["make_production_mesh", "production_topology",
           "mesh_steal_table", "line_steal_table", "coordinator_device",
           "POD_SHAPE"]

POD_SHAPE = (16, 16)          # 256 chips per v5e pod (2-D ICI torus)


def production_topology(multi_pod: bool = False) -> topo_mod.Topology:
    """Modeled hop-distance topology matching the production mesh.

    Device id d in jax.devices() order corresponds to topology core d
    (pods enumerate consecutively, row-major within a pod).
    """
    if multi_pod:
        return topo_mod.multi_pod(2, *POD_SHAPE)
    return topo_mod.tpu_pod_2d(*POD_SHAPE)


def make_production_mesh(*, multi_pod: bool = False,
                         topology_aware: bool = False,
                         devices=None):
    """Build the production mesh (deliverable (e) entry point)."""
    import jax
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if not topology_aware:
        return jax.make_mesh(
            shape, axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
            devices=devices)
    devices = np.asarray(devices if devices is not None else jax.devices())
    if devices.size != int(np.prod(shape)):
        raise ValueError(f"need {int(np.prod(shape))} devices, "
                         f"got {devices.size}")
    topo = production_topology(multi_pod)
    perm = placement.device_order_priority(topo, shape)
    grid = devices[perm].reshape(shape)
    return Mesh(grid, axes,
                axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def coordinator_device(mesh):
    """The 'master thread' analogue: checkpoint leader / RNG seeder.

    Logical position (0, ..., 0) — under topology-aware ordering this is
    the highest-priority (most central) chip, so broadcast-style traffic
    (init, restore fan-out) starts from the centroid (first-touch
    analogue).
    """
    return np.asarray(mesh.devices).reshape(-1)[0]


def mesh_steal_table(mesh, num_experts: int, policy: str = "dfwspt",
                     seed: int = 0) -> np.ndarray:
    """Expert steal order for a mesh with experts on the 'model' axis.

    Expert e lives on model-axis block e·M/E (M = model axis size); its
    owning physical chip (representative: pod 0, data row 0) indexes the
    modeled topology. Returns the (E, E-1) table for core/routing.route.
    """
    devs = np.asarray(mesh.devices)
    axes = mesh.axis_names
    model_ax = axes.index("model")
    M = devs.shape[model_ax]
    # representative device per model index: first along all other axes
    index = [0] * devs.ndim
    reps = []
    for m in range(M):
        index[model_ax] = m
        reps.append(devs[tuple(index)].id)
    reps = np.asarray(reps)
    if num_experts >= M:
        if num_experts % M:
            raise ValueError(f"experts {num_experts} % model axis {M} != 0")
        expert_device = reps[(np.arange(num_experts) * M) // num_experts]
    else:
        if M % num_experts:
            raise ValueError(f"model axis {M} % experts {num_experts} != 0")
        expert_device = reps[np.arange(num_experts) * (M // num_experts)]
    multi_pod = "pod" in axes
    topo = production_topology(multi_pod)
    return expert_steal_table(topo, expert_device, policy=policy, seed=seed)


def line_steal_table(num_experts: int,
                     policy: str = "dfwspt") -> np.ndarray:
    """Expert steal order for a step jitted for one device.

    Expert e sits on chip e of a modeled 1 × n ring, n = max(devices, E),
    so under DFWSPT row e is the others by hop distance min(|i-j|, n-|i-j|),
    ties by the lower id. Returns the (E, E-1) table for
    core/routing.route.
    """
    import jax

    topo = topo_mod.tpu_pod_2d(1, max(len(jax.devices()), num_experts))
    return expert_steal_table(topo, np.arange(num_experts), policy=policy)
