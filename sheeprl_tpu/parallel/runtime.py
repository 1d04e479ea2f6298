"""The `Runtime`: TPU-native replacement for Lightning Fabric.

The reference instantiates `lightning.fabric.Fabric` from config and calls
`fabric.launch(entrypoint, cfg)` — the single process-spawn point
(/root/reference/sheeprl/cli.py:101-199).  On TPU there is nothing to spawn:
JAX is single-controller per host, every local chip is already visible, and
multi-host synchronization comes from `jax.distributed`.  `Runtime` therefore
carries:

- the device mesh (1-D ``data`` axis) and precision policy;
- PRNG seeding;
- host-side "collectives" that mirror Fabric's API surface
  (`all_gather`/`broadcast`/object broadcast) — trivial in-process when
  world_size==1 per host, `multihost_utils` when distributed;
- the callback hook mechanism (`runtime.call("on_checkpoint_coupled", ...)`)
  used by the checkpoint callback (reference utils/callback.py:14-148).

A second, strategy-free runtime for "player" models
(`get_single_device_runtime`, reference utils/fabric.py:8-35) is a
device-pinning helper here: players run on ``mesh.devices[0]`` and never touch
collectives.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.parallel.mesh import make_mesh
from sheeprl_tpu.parallel.precision import PRECISION_DTYPES as _PRECISION_TO_DTYPES
from sheeprl_tpu.parallel.precision import cast_floating


class Runtime:
    # Run-health facade (sheeprl_tpu/diagnostics): attached by the CLI before
    # launch, or lazily by utils.get_diagnostics for direct entrypoint callers.
    diagnostics = None

    def __init__(
        self,
        devices: int | str = 1,
        num_nodes: int = 1,
        strategy: str = "auto",
        accelerator: str = "auto",
        precision: str = "32-true",
        callbacks: Optional[Sequence[Any]] = None,
        fsdp: int = 1,
        fsdp_min_shard_bytes: Optional[int] = None,
    ):
        self.num_nodes = num_nodes
        self.strategy = strategy
        self.accelerator = accelerator
        self.precision = precision
        if precision not in _PRECISION_TO_DTYPES:
            raise ValueError(f"Unknown precision '{precision}'; valid: {list(_PRECISION_TO_DTYPES)}")
        self.param_dtype, self.compute_dtype = _PRECISION_TO_DTYPES[precision]
        self.callbacks = list(callbacks or [])

        # Multi-host: initialize jax.distributed only when a coordinator is set
        # (TPU pods set these in the environment). Single host: no-op.
        if num_nodes > 1 and not jax.process_count() > 1 and os.environ.get("JAX_COORDINATOR_ADDRESS"):
            jax.distributed.initialize()  # pragma: no cover - needs a pod

        # `auto` is JAX's default backend: the TPU where libtpu initialises,
        # otherwise the CPU (howto/tpu.md).  A named platform must exist —
        # `jax.devices("tpu")` raises on a machine without one, so
        # fabric.accelerator=tpu can never train on the CPU by accident.
        available = jax.devices() if accelerator in ("auto", None) else jax.devices(accelerator)
        n = len(available) if devices in ("auto", -1, "-1") else int(devices)
        if n > len(available):
            raise ValueError(f"Requested {n} devices but only {len(available)} are available")
        self.fsdp = int(fsdp or 1)
        self.fsdp_min_shard_bytes = None if fsdp_min_shard_bytes is None else int(fsdp_min_shard_bytes)
        if self.fsdp > 1:
            if n % self.fsdp != 0:
                raise ValueError(
                    f"fsdp axis size ({self.fsdp}) must divide the device count ({n})"
                )
            # 2-D ("data", "model") mesh: batch shards over both axes, params
            # and optimizer state shard over "model" (parallel/fsdp.py rule).
            self.mesh = make_mesh(
                available[:n], axis_names=("data", "model"), axis_sizes=(n // self.fsdp, self.fsdp)
            )
        else:
            self.mesh = make_mesh(available[:n])
        # printed at launch (and journaled with run_start) so a CPU run is
        # never mistaken for a chip run
        info = self.device_info
        print(
            f"Runtime: {info['device_count']} x {info['platform']} ({info['device_kind']}), "
            f"fabric.accelerator={accelerator}, precision={precision}",
            flush=True,
        )
        self._launched = False

    # -- topology ---------------------------------------------------------
    @property
    def devices(self) -> List[Any]:
        return list(self.mesh.devices.reshape(-1))

    @property
    def device(self) -> Any:
        """The 'player' device (first in the mesh)."""
        return self.devices[0]

    @property
    def device_info(self) -> Dict[str, Any]:
        """What the mesh actually resolved to, as JAX reports it."""
        return {
            "platform": self.device.platform,
            "device_kind": self.device.device_kind,
            "device_count": self.world_size,
        }

    @property
    def world_size(self) -> int:
        return len(self.devices)

    @property
    def global_rank(self) -> int:
        # single-controller: the process rank; per-device rank only matters
        # inside jitted collectives which use mesh axes instead.
        return jax.process_index()

    @property
    def is_global_zero(self) -> bool:
        return jax.process_index() == 0

    # -- launch -----------------------------------------------------------
    def launch(self, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        """Run the entrypoint. No process spawn: the mesh already spans all
        local devices (ICI) and, when `jax.distributed` is initialized, all
        hosts (DCN)."""
        self._launched = True
        return fn(self, *args, **kwargs)

    # -- precision --------------------------------------------------------
    def cast(self, tree: Any) -> Any:
        """Cast floating leaves to the compute dtype."""
        return cast_floating(tree, self.compute_dtype)

    # -- host collectives (Fabric API surface; executed by
    # tests/test_parallel/test_multihost.py on a 2-process CPU mesh) --------
    def all_gather(self, tree: Any) -> Any:
        """Gather across *processes* (multi-host). In-process device-sharded
        values are already globally addressable, so this is the identity on a
        single host."""
        if jax.process_count() == 1:
            return tree
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(tree)

    def broadcast(self, obj: Any, src: int = 0) -> Any:
        """Object broadcast (the reference's Gloo ``broadcast_object_list``,
        e.g. the log-dir broadcast of utils/logger.py:78-114): arbitrary
        picklable objects ride the array collective as length-prefixed bytes —
        ``broadcast_one_to_all`` itself only ships numeric array pytrees."""
        if jax.process_count() == 1:
            return obj
        import pickle

        from jax.experimental import multihost_utils

        is_src = jax.process_index() == src
        payload = pickle.dumps(obj) if is_src else b""
        n = int(
            multihost_utils.broadcast_one_to_all(np.int32(len(payload)), is_source=is_src)
        )
        buf = np.frombuffer(payload, np.uint8) if is_src else np.zeros(n, np.uint8)
        buf = np.asarray(multihost_utils.broadcast_one_to_all(buf, is_source=is_src), np.uint8)
        return pickle.loads(buf.tobytes())

    def barrier(self) -> None:
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("sheeprl_tpu_barrier")

    # -- callbacks ---------------------------------------------------------
    def call(self, hook_name: str, **kwargs: Any) -> None:
        for cb in self.callbacks:
            hook = getattr(cb, hook_name, None)
            if hook is not None:
                hook(runtime=self, **kwargs)

    # -- checkpoint io ------------------------------------------------------
    def save(self, path: str, state: Dict[str, Any]) -> None:
        """Checkpoint write, routed through the resilience layer when the
        diagnostics facade carries one (async off-critical-path writer +
        manifest sidecar + ckpt_begin/ckpt_end journaling); otherwise a plain
        synchronous save that still writes the manifest, so resume-time
        verification works for every producer (eval helpers, tests).

        Multi-process (``jax.distributed``) saves are *coordinated* group
        snapshots (resilience/coordination.py): barrier → broadcast-agreed
        step → one ``ckpt_<step>_<rank>.ckpt`` shard per rank with a group
        manifest, so resume selection can reject torn snapshots.  The
        single-process path below is bit-identical to the pre-coordination
        behavior.

        FSDP (``fsdp > 1``, single process): the save is *truly sharded* —
        one ``ckpt_<step>_<k>.ckpt`` partial per model-axis shard, each
        holding only the leaf slices that shard owns, with the layout
        recorded in the manifest group (resilience/sharded.py).  Bytes per
        shard scale down with the axis; the write is synchronous (partials
        must land as one verified group)."""
        if jax.process_count() > 1:
            from sheeprl_tpu.resilience.coordination import coordinated_save

            coordinated_save(self, path, state)
            return
        if self.fsdp > 1:
            from sheeprl_tpu.resilience.sharded import save_sharded_checkpoint

            save_sharded_checkpoint(
                path, state, axis_size=self.fsdp, min_shard_bytes=self.fsdp_min_shard_bytes
            )
            self.barrier()
            return
        if self.is_global_zero:
            diagnostics = self.diagnostics
            routed = diagnostics is not None and diagnostics.save_checkpoint(path, state)
            if not routed:
                from sheeprl_tpu.resilience.manifest import save_verified_checkpoint

                save_verified_checkpoint(path, state)
        self.barrier()

    def load(self, path: str) -> Dict[str, Any]:
        """Checkpoint read; a non-zero rank of a multi-process run loads its
        own shard of a coordinated group when one exists next to the
        (canonical, rank-0) resolved path, falling back to the rank-0 file —
        today's state is replicated, so the fallback is always valid.

        FSDP partial-shard groups are detected from the shard-0 manifest and
        reassembled into the full host tree (resilience/sharded.py) — the
        loaded tree is axis-size-agnostic, so resuming under a *different*
        ``fsdp_axis_size`` (or pure DP) just re-places it under the new
        rule."""
        from sheeprl_tpu.utils.checkpoint import load_state

        if jax.process_count() == 1:
            from sheeprl_tpu.resilience.sharded import is_partial_checkpoint, load_sharded_checkpoint

            if is_partial_checkpoint(path):
                return load_sharded_checkpoint(path)
        if jax.process_count() > 1 and jax.process_index() > 0:
            from sheeprl_tpu.resilience.coordination import rank_shard_path

            mine = rank_shard_path(path, jax.process_index())
            if os.path.isfile(mine):
                path = mine
        return load_state(path)

    def seed_everything(self, seed: int) -> jax.Array:
        np.random.seed(seed)
        import random

        random.seed(seed)
        return jax.random.PRNGKey(seed)


def get_single_device_runtime(runtime: Runtime) -> Runtime:
    """Strategy-free runtime sharing device/precision with `runtime`
    (reference utils/fabric.py:8-35): used to wrap player models so env
    interaction never crosses collectives."""
    single = Runtime.__new__(Runtime)
    single.num_nodes = 1
    single.strategy = "single"
    single.accelerator = runtime.accelerator
    single.precision = runtime.precision
    single.param_dtype = runtime.param_dtype
    single.compute_dtype = runtime.compute_dtype
    single.callbacks = runtime.callbacks
    single.diagnostics = runtime.diagnostics
    single.fsdp = 1
    single.fsdp_min_shard_bytes = None
    single.mesh = make_mesh([runtime.device])
    single._launched = True
    return single
