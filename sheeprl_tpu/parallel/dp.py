"""Coupled data-parallelism helpers (reference: Lightning DDP, SURVEY §2.4).

The reference's coupled mode is: every rank computes its own batch, gradients
are all-reduced (torch DDP on ``fabric.backward``), and the DreamerV3
``Moments`` quantile is computed over the all-gathered return values
(reference ``algos/dreamer_v3/utils.py:56-64``).

The TPU-native equivalent used across this package is ``jax.shard_map`` over a
1-D ``"data"`` mesh axis: the batch enters sharded (``P(..., "data", ...)``),
params/opt-states enter replicated (``P()``), the body computes local
gradients and explicitly ``lax.pmean``-reduces them before the optimizer
update — the collective is *in the compiled HLO*, riding ICI, not implied.
``tests/test_parallel/test_dp_sharding.py`` asserts both the input shardings
and the presence of the all-reduce in the compiled module.

Off-policy loops use these helpers so a single code path serves 1..N devices:

- :func:`dp_axis` — the axis name iff genuinely distributed, else ``None``
- :func:`fold_key` — per-device independent RNG (reference: per-rank seeds)
- :func:`pmean_tree` — gradient/metric all-reduce
- :func:`dp_jit` — shard_map + jit wrapper
- :func:`stage` — host batch → sharded device arrays (``device_put`` with a
  ``NamedSharding``; raw dtype travels over PCIe, normalization runs sharded)

FSDP (2-D ``("data", "model")`` mesh — parallel/fsdp.py owns the partition
rule): the step compiles through a *global-view* jit instead of shard_map.
``dp_axis`` returns ``None`` on a model-axis mesh, so ``fold_key`` /
``pmean_tree`` / ``all_gather_cat`` become identities and ``jax.grad`` yields
global gradients; layout flows from the committed input shardings (params
sharded by :func:`fsdp.shard_tree`, batch sharded over both axes by
:func:`stage`) plus the output constraints :func:`dp_jit` applies — the
all-gather/reduce-scatter pattern is inserted by XLA, not hand-written.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.parallel.mesh import MODEL_AXIS, model_axis_size

AXIS = "data"


def fsdp_axis(mesh: Optional[Mesh]) -> Optional[str]:
    """The ``model`` (FSDP) axis name when the mesh has one of extent > 1."""
    if model_axis_size(mesh) > 1:
        return MODEL_AXIS
    return None


def dp_axis(mesh: Optional[Mesh]) -> Optional[str]:
    """The data-parallel axis name if ``mesh`` spans >1 device, else None.

    Deliberately ``None`` on an FSDP (model-axis) mesh: that path runs
    global-view jit, so the explicit per-device collectives keyed off this
    axis must become no-ops.
    """
    if fsdp_axis(mesh) is not None:
        return None
    if mesh is not None and mesh.devices.size > 1:
        return AXIS
    return None


def fold_key(key: jax.Array, axis: Optional[str]) -> jax.Array:
    """Per-device independent RNG stream (like per-rank seeding in DDP)."""
    if axis is None:
        return key
    return jax.random.fold_in(key, jax.lax.axis_index(axis))


def pmean_tree(tree: Any, axis: Optional[str]) -> Any:
    """Mean-reduce a pytree across the data axis (no-op when single device)."""
    if axis is None:
        return tree
    return jax.lax.pmean(tree, axis)


def all_gather_cat(x: jax.Array, axis: Optional[str]) -> jax.Array:
    """Gather shards from every device and stack on a new leading axis, so a
    subsequent global reduction (quantile, mean) sees the full batch — the
    reference's ``fabric.all_gather`` semantics."""
    if axis is None:
        return x
    return jax.lax.all_gather(x, axis)


def dp_jit(
    fn,
    mesh: Optional[Mesh],
    in_specs: Sequence[Any],
    out_specs: Any,
    donate_argnums: Tuple[int, ...] = (),
    min_shard_bytes: Optional[int] = None,
):
    """shard_map ``fn`` over the 1-D data mesh and jit it.

    ``fn`` must already be written for the local view (fold its RNG keys with
    :func:`fold_key`, pmean its grads with :func:`pmean_tree`).  When ``mesh``
    is None/size-1, this is a plain ``jax.jit`` — one code path for both.

    FSDP mesh: global-view jit.  The per-device collectives inside ``fn`` are
    already no-ops (``dp_axis`` returned None to the caller), inputs carry
    committed shardings, and every *output* leaf is constrained to its
    partition-rule spec (``min_shard_bytes`` tunes the rule) — params-out gets
    the identical spec as params-in, keeping donation an in-place shard-to-
    shard alias and the steady-state layout stable across iterations.
    """
    if fsdp_axis(mesh) is not None:
        from sheeprl_tpu.parallel.fsdp import constrain_tree

        def constrained(*args):
            out = fn(*args)
            return constrain_tree(out, mesh, min_shard_bytes)

        return jax.jit(constrained, donate_argnums=donate_argnums)
    if dp_axis(mesh) is None:
        return jax.jit(fn, donate_argnums=donate_argnums)
    from jax import shard_map

    mapped = shard_map(fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs, check_vma=False)
    return jax.jit(mapped, donate_argnums=donate_argnums)


def fsdp_min_shard_bytes(cfg) -> Optional[int]:
    """The configured FSDP replication floor, or None for the rule default.

    ``fabric.fsdp_min_shard_bytes`` interpolates ``distribution.
    fsdp_min_shard_bytes`` in the shipped configs; checking fabric first keeps
    a direct fabric override and the train step consistent."""
    for section in ("fabric", "distribution"):
        try:
            block = cfg.get(section) or {}
            value = block.get("fsdp_min_shard_bytes")
        except AttributeError:
            continue
        if value is not None:
            return int(value)
    return None


def local_sample_size(global_batch: int, device_resident: bool = False) -> int:
    """Rows THIS PROCESS must draw from its replay buffer so the trained
    global batch is ``global_batch``.

    Host replay: single-process (any number of local devices) draws the full
    amount — ``stage`` shards it over the mesh; multi-process (DCN) draws
    ``global_batch / process_count`` because each host contributes its block
    to ``make_array_from_process_local_data`` (drawing the full global batch
    per process would silently train at ``process_count``x the configured
    batch — code-review finding, round 4).

    Device-resident replay (``device_resident=True``): the HBM ring's
    ``sample`` always takes the GLOBAL batch — its sharded gather divides
    over the whole mesh internally — so the full amount is returned
    regardless of process count."""
    if device_resident:
        return global_batch
    n = jax.process_count()
    if global_batch % n != 0:
        raise ValueError(
            f"global batch ({global_batch}) must be divisible by the process count ({n})"
        )
    return global_batch // n


def batch_spec(batch_axis: int = 0, mesh: Optional[Mesh] = None) -> P:
    """PartitionSpec sharding ``batch_axis`` over the data axis (prefix-spec
    for a whole batch pytree).  On an FSDP mesh the batch shards over *both*
    axes — FSDP is still data parallelism (ZeRO-3: every device trains its
    own rows, only the params/opt-state are sharded)."""
    entry = (AXIS, MODEL_AXIS) if fsdp_axis(mesh) is not None else AXIS
    return P(*([None] * batch_axis), entry)


def stage(tree: Any, mesh: Optional[Mesh], batch_axis: int = 0) -> Any:
    """Move a host batch pytree onto the mesh, sharded along ``batch_axis``.

    Single-device: plain ``jnp.asarray``.  Multi-device: ``jax.device_put``
    with a ``NamedSharding`` — each device receives only its shard (this is
    what makes DP *real*: the compiled step's batch argument sharding is
    ``P(..., "data")``, not replicated).  FSDP meshes shard the batch over
    both axes (see :func:`batch_spec`).
    """
    if mesh is None or mesh.devices.size <= 1:
        return jax.tree_util.tree_map(jnp.asarray, tree)
    batch_entry = (AXIS, MODEL_AXIS) if fsdp_axis(mesh) is not None else AXIS
    sharding_cache = {}
    multiprocess = len(getattr(mesh, "devices", np.empty(0)).ravel()) > len(jax.local_devices())

    def put(x):
        x = np.asarray(x)
        spec = [None] * x.ndim
        spec[batch_axis] = batch_entry
        key = x.ndim
        if key not in sharding_cache:
            sharding_cache[key] = NamedSharding(mesh, P(*spec))
        if multiprocess:
            # DCN path: the mesh spans processes, so each host holds only ITS
            # batch rows (the reference's per-rank DDP batches); assemble the
            # global array from the process-local block — only local shards
            # are transferred, the global view is logical.
            return jax.make_array_from_process_local_data(sharding_cache[key], x)
        return jax.device_put(x, sharding_cache[key])

    return jax.tree_util.tree_map(put, tree)


def normalize_staged(staged: Any, cnn_keys) -> Any:
    """Shared device-side batch preprocessing for the Dreamer loops: float32
    upcast + pixel scaling to [-0.5, 0.5] for CNN keys (data crosses the wire
    in its raw dtype; this runs on device arrays)."""
    batch = {}
    for k, arr in staged.items():
        arr = arr.astype(jnp.float32)
        if k in cnn_keys:
            arr = arr / 255.0 - 0.5
        batch[k] = arr
    return batch


def train_batches(local_data: Any, n: int, mesh: Optional[Mesh], cnn_keys, device_resident: bool):
    """The Dreamer loops' per-gradient-step batch iterator.

    Device-resident replay: ``local_data`` is already a list of HBM batches —
    just normalize.  Host replay: double-buffer the host->HBM staging via
    ``prefetch_staged``.
    """
    from functools import partial

    _normalize = partial(normalize_staged, cnn_keys=cnn_keys)
    if device_resident:
        return (_normalize(b) for b in local_data)
    return prefetch_staged(local_data, n, mesh, batch_axis=1, transform=_normalize)


def prefetch_staged(samples: Any, n: int, mesh: Optional[Mesh], batch_axis: int = 0, transform=None):
    """Double-buffered host→HBM staging over the ``n`` gradient-step slices of
    a sampled super-batch (SURVEY §2.2 TPU note; VERDICT r1 item 10).

    ``samples`` leaves are ``[n, ...]`` host arrays; slice ``i+1`` is staged
    (``device_put`` is asynchronous) immediately after slice ``i`` is yielded,
    so its host-gather + PCIe/ICI transfer overlaps the device executing step
    ``i`` instead of sitting on the critical path.  ``transform`` runs on the
    *device* arrays (normalization etc. — keep the wire format raw uint8).
    """

    def _stage(i: int):
        staged = stage(jax.tree_util.tree_map(lambda v: np.asarray(v[i]), samples), mesh, batch_axis)
        return transform(staged) if transform is not None else staged

    if n <= 0:
        return
    current = _stage(0)
    for i in range(1, n):
        upcoming = _stage(i)  # async H2D while the consumer's step i-1 runs
        yield current
        current = upcoming
    yield current
