"""Device-mesh helpers.

The reference scales with Lightning Fabric DDP (one process per device, NCCL
all-reduce — see SURVEY §2.4).  The TPU-native design is single-controller:
one process drives all local chips through a `jax.sharding.Mesh`; gradient
reduction is whatever XLA inserts for a batch-sharded / param-replicated jit —
a `psum` riding ICI.  Multi-host extends the same mesh over DCN via
`jax.distributed.initialize` without changing any algorithm code.

Axis conventions used across the framework:
- ``data``: data-parallel axis (batch sharded, params replicated)
- ``model``: FSDP axis (params/opt-state sharded — parallel/fsdp.py owns the
  partition rule; batch sharded over *both* axes so FSDP is still DP + ZeRO-3)
- ``trainer``/player sub-meshes: decoupled topology (algos/ppo/ppo_decoupled.py)
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"


def make_mesh(
    devices: Optional[Sequence[Any]] = None,
    axis_names: Sequence[str] = ("data",),
    axis_sizes: Optional[Sequence[int]] = None,
    n_devices: Optional[int] = None,
) -> Mesh:
    """Build the device mesh over ``devices`` (the `Runtime` passes the ones
    its accelerator setting selected); without them, over the first
    ``n_devices`` of JAX's default backend.

    1-D (the default): all devices on one axis.  2-D (``("data", "model")``):
    ``axis_sizes`` gives the extent of every axis — the trailing (``model``)
    axis rides ICI-adjacent devices so FSDP's all-gather/reduce-scatter stays
    on the fastest links, exactly the GSPMD mesh-major convention.
    """
    if devices is None:
        devices = jax.devices()[:n_devices]
    arr = np.asarray(devices)
    if len(axis_names) == 1:
        return Mesh(arr.reshape(-1), axis_names)
    if axis_sizes is None or len(axis_sizes) != len(axis_names):
        raise ValueError(
            f"a {len(axis_names)}-D mesh needs axis_sizes of the same length, got {axis_sizes!r}"
        )
    want = int(np.prod(axis_sizes))
    if want != arr.size:
        raise ValueError(
            f"axis_sizes {tuple(axis_sizes)} needs {want} devices but the mesh has {arr.size}"
        )
    return Mesh(arr.reshape(tuple(axis_sizes)), tuple(axis_names))


def model_axis_size(mesh: Optional[Mesh]) -> int:
    """Extent of the ``model`` (FSDP) axis; 1 when the mesh is 1-D/absent."""
    if mesh is None or MODEL_AXIS not in mesh.axis_names:
        return 1
    return int(mesh.shape[MODEL_AXIS])


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Fully replicate a pytree over the mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def shard_along(tree: Any, mesh: Mesh, axis_name: str = "data", axis: int = 0) -> Any:
    """Shard every leaf's ``axis`` dimension over ``axis_name``."""

    def put(x):
        spec = [None] * np.ndim(x)
        spec[axis] = axis_name
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    return jax.tree_util.tree_map(put, tree)


def batch_sharding(mesh: Mesh, axis_name: str = "data") -> NamedSharding:
    return NamedSharding(mesh, P(axis_name))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
