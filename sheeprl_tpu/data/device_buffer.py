"""Device-resident sequential replay buffer.

The reference streams every sampled batch host->GPU each gradient step
(reference sheeprl/data/buffers.py:291-326 converts to torch tensors per
sample).  On TPU that transfer is the end-to-end bottleneck: a DV3-S batch
(16 x 64 x 64x64x3 uint8) is ~50 MB per gradient step, while the *collected*
data is only ~12 KB per policy step.  This buffer therefore keeps the whole
replay ring in HBM:

- ``add`` writes one policy step into the ring in place (jitted, donated)
  — the only host->device traffic is the newest frame;
- per-env write heads: envs advance independently (episode-end rows are
  appended only to done envs), replacing the host path's one-sub-buffer-per-
  env ``EnvIndependentReplayBuffer`` + ``SequentialReplayBuffer`` pair;
- ``sample`` draws sequence windows with the host ``SequentialReplayBuffer``'s
  age-space semantics (windows never span an env's write head; starts uniform
  over each env's valid range) but the read runs on device and the returned
  ``[T, B, ...]`` batch never touches the host.  Env choice is uniform on a
  single device; in multi-device mode it is *block-stratified* — each device's
  batch block draws only from its own env shard (see ``_draw_env_idx``).

Storage form.  Every key is held as ``[cap, n_envs, width]``, ``width`` the
product of its trailing dims (``rgb`` ``[cap, n, 3, 64, 64]`` is held as
``[cap, n, 12288]``); the logical trailing shape is put back on the
``[T, B, ...]`` batch inside the sampling executable, and ``state_dict`` /
``load_state_dict`` / ``footprint`` speak logical shapes and the same bytes.
Why: the TPU runtime picks an array's device layout from its shape, and for
``u8[cap, n, 3, 64, 64]`` it puts the *ring* axis in the lanes (64x64 in the
lanes would pad twofold).  XLA's gather wants the indexed axis major, so it
first re-laid the whole ring (PERF.md section 6, PR 28: 38.85 of a 62.5 ms
iteration at 250,000 rows, a 6.1 GB temporary, and no ring over ~400,000
rows).  The read now follows what the layout allows, by the key's width:

- a width that is a multiple of the 128-lane tile (frames 3x64x64, the RSSM
  slabs) gets a row-major layout: row ``(t, e)`` is contiguous, and a gather
  of the ``T x B`` rows reads exactly them;
- any other width (scalars, actions, small vectors, 84x84 frames) gets the
  ring axis in the lanes: a sample is read as ``B`` windows of ``T``
  consecutive ring rows (``dynamic_slice`` along the axis the layout has
  minor), the wrap at the ring's end mended inside the executable.

The write is one ``dynamic_update_slice`` per written env in either layout.
The invariant, held from the compiler by ``tests/test_data/
test_device_buffer.py``: neither executable holds an operand, a result or a
temporary that grows with ``buffer_size`` other than the ring itself — the
cost of a sample is a function of ``batch x sequence_length x row bytes``.

Capacity: the ring is what it stores and nothing more (a 1,000,000-row
64x64x3 uint8 ring is 12.3 GB of a v5e's 16 GB; DV3 Atari-100K is 1.2 GB).
For a ring that does not fit beside the model keep the host path
(``buffer.device=False``).

Head bookkeeping (per-env ``pos``/``full``) stays on the host: it's a few
ints per policy step, and host-side index math keeps sampling logic in cheap
numpy while every array byte stays in HBM.
"""

from __future__ import annotations

import math
import warnings
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the minor tile of a TPU layout: a key whose width is a multiple of it is
# laid out row-major, any other with the ring axis in the lanes
_LANES = 128
# windows read by one trip of their loop.  Up to here a batch (DV3 16, DV1/DV2
# 50, per device) is spelled out whole, which is the form the compiler leaves
# every layout alone for; a larger one becomes a ``while`` whose ring operand
# XLA may re-lay (seen for widths of 64 and over)
_UNROLL = 64

Shapes = Tuple[Tuple[str, Tuple[int, ...]], ...]


def _write_rows(ring: jax.Array, new: jax.Array, rows: jax.Array, envs: jax.Array, mine: Optional[jax.Array] = None) -> jax.Array:
    """``new[i]`` into ``ring[rows[i], envs[i]]``, one ``dynamic_update_slice``
    each: in place under donation whatever the ring's layout (a scatter
    re-lays a narrow key whole at ``n_envs`` > 1).  Where ``mine[i]`` is false
    the row is written back as it was found; such rows come first in the
    order, so that none undoes a write that counts."""
    n = new.shape[0]
    new = list(new.reshape(n, 1, 1, *ring.shape[2:]).astype(ring.dtype))
    at = [(rows[i], envs[i], *(0,) * (ring.ndim - 2)) for i in range(n)]
    if mine is not None:
        # all read before the first write: read and written in turn, XLA
        # fuses each pair and re-lays the ring for the fusion
        found = [lax.dynamic_slice(ring, at[i], new[i].shape) for i in range(n)]
        new = [jnp.where(mine[i], new[i], found[i]) for i in range(n)]
    for i in range(n):
        ring = lax.dynamic_update_slice(ring, new[i], at[i])
    return ring


def _read_rows(ring: jax.Array, starts: jax.Array, env_idx: jax.Array, seq_len: int) -> jax.Array:
    """``[T, B, ...]`` by a gather of rows ``(starts[b] + t) % cap``: for a
    ring whose rows are contiguous."""
    rows = (starts[None, :] + jnp.arange(seq_len)[:, None]) % ring.shape[0]
    return ring[rows, env_idx[None, :]]


def _read_windows(ring: jax.Array, starts: jax.Array, env_idx: jax.Array, seq_len: int) -> jax.Array:
    """``[T, B, ...]`` as ``B`` slices of ``T`` consecutive ring rows: for a
    ring whose layout has the ring axis minor.  A window that runs past the
    ring's end is cut from its last ``T`` rows followed by its first ``T``.
    The slices are taken one by one (a loop, unrolled up to ``_UNROLL``): a
    batched ``dynamic_slice`` is a gather to XLA, which re-lays the ring for
    it at ``n_envs`` > 1."""
    cap, trailing = ring.shape[0], ring.shape[2:]
    size, zeros = (seq_len, 1, *trailing), (0,) * len(trailing)
    first = jnp.minimum(starts, cap - seq_len)  # of the last T rows at most

    def window(_, at):
        row, shift, env = at
        tail = lax.dynamic_slice(ring, (row, env, *zeros), size)
        head = lax.dynamic_slice(ring, (0, env, *zeros), size)
        return None, lax.dynamic_slice(jnp.concatenate([tail, head]), (shift, 0, *zeros), size)[:, 0]

    _, windows = lax.scan(window, None, (first, starts - first, env_idx), unroll=_UNROLL)
    return jnp.swapaxes(windows, 0, 1)


# The two jitted functions' names are their executables' names in a profile
# (``jit_replay_add`` / ``jit_replay_gather`` on the ``XLA Modules`` line):
# a reduction finds them by name, so a rename is a change to what is measured.
@partial(jax.jit, donate_argnums=(0,))
def replay_add(buf: Dict[str, jax.Array], step: Dict[str, jax.Array], rows: jax.Array, envs: jax.Array) -> Dict[str, jax.Array]:
    """Whole-dict ring write in ONE dispatched program: ``step[k]`` is
    ``[n_sel, ...]`` written at ``(rows[i], envs[i])`` of ``buf[k]``.  One
    device call per policy step instead of one per key — each dispatch is
    host work on the hot thread (``loop.replay_add_host_ms``, PERF.md)."""
    return {k: _write_rows(buf[k], step[k], rows, envs) for k in buf}


@partial(jax.jit, static_argnums=(3, 4))
def replay_gather(
    buf: Dict[str, jax.Array], starts: jax.Array, env_idx: jax.Array, seq_len: int, shapes: Optional[Shapes] = None
) -> Dict[str, jax.Array]:
    """Whole-dict sequence read in ONE dispatched program:
    ``[cap, n_envs, ...] -> [seq_len, B, ...]`` per key; window ``b`` is rows
    ``(starts[b] + t) % cap`` of env ``env_idx[b]``.  ``shapes`` names the
    logical trailing shape of each key held flat (none: as stored)."""
    out = {}
    for k, ring in buf.items():
        read = _read_rows if math.prod(ring.shape[2:]) % _LANES == 0 else _read_windows
        out[k] = read(ring, starts, env_idx, seq_len)
    for k, trailing in shapes or ():
        out[k] = out[k].reshape(*out[k].shape[:2], *trailing)
    return out


def _make_sharded_gather(mesh, seq_len: int, shapes: Shapes):
    """Per-device local read over an env-sharded ring (multi-device mode).

    Inside ``shard_map`` every device sees only its env block; ``env_idx`` is
    drawn block-stratified on the host so each device's indices are local.
    The output batch leaves sharded ``P(None, "data")`` on the batch axis —
    exactly the in_spec of the shard_map'd Dreamer train steps — with ZERO
    cross-device traffic.
    """
    from jax.sharding import PartitionSpec as P

    from sheeprl_tpu.parallel.dp import dp_jit

    def local_gather(storage, starts, env_local):
        return replay_gather(storage, starts, env_local, seq_len, shapes)

    return dp_jit(
        local_gather,
        mesh,
        in_specs=(P(None, "data"), P("data"), P("data")),
        out_specs=P(None, "data"),
    )


def _make_sharded_add(mesh):
    """The ring write over an env-sharded ring: every device gets the whole
    (KB-sized) step and writes the rows of its own env block, so each local
    program is the single-device one (left to the SPMD partitioner, a narrow
    key is re-laid whole on the way in and out)."""
    from jax.sharding import PartitionSpec as P

    from sheeprl_tpu.parallel.dp import dp_axis, dp_jit

    axis = dp_axis(mesh)
    if axis is None:  # a mesh with a model axis runs global-view programs
        return replay_add

    def local_add(storage, step, rows, envs):
        n_local = next(iter(storage.values())).shape[1]
        local = envs - lax.axis_index(axis) * n_local
        mine = (local >= 0) & (local < n_local)
        order = jnp.argsort(mine)  # another block's envs first: _write_rows
        rows, local, mine = rows[order], jnp.clip(local, 0, n_local - 1)[order], mine[order]
        return {k: _write_rows(storage[k], step[k][order], rows, local, mine) for k in storage}

    return dp_jit(
        local_add,
        mesh,
        in_specs=(P(None, "data"), P(), P(), P()),
        out_specs=P(None, "data"),
        donate_argnums=(0,),
    )


class DeviceSequentialReplayBuffer:
    """Sequence replay living in HBM (single-host; per-env write heads).

    API mirrors what the Dreamer loop needs from the host
    ``EnvIndependentReplayBuffer(SequentialReplayBuffer)``: ``add(step_data[,
    indices])``, ``sample(batch, sequence_length, n_samples)`` (a list of
    device batches, one per gradient step), ``state_dict``/``load_state_dict``,
    ``mark_last_truncated``.
    """

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        obs_keys: Sequence[str] = (),
        mesh: Optional[Any] = None,
        **_: Any,
    ):
        if buffer_size <= 0:
            raise ValueError(f"The buffer size must be greater than zero, got: {buffer_size}")
        if n_envs <= 0:
            raise ValueError(f"The number of environments must be greater than zero, got: {n_envs}")
        self._buffer_size = int(buffer_size)
        self._n_envs = int(n_envs)
        self._obs_keys = tuple(obs_keys)
        self._buf: Dict[str, jax.Array] = {}  # [cap, n_envs, width] per key
        self._shapes: Shapes = ()  # each key's logical trailing shape, as replay_gather takes it
        self._pos = np.zeros(self._n_envs, dtype=np.int64)
        self._filled = np.zeros(self._n_envs, dtype=np.int64)  # rows ever written, capped at size
        self._added = np.zeros(self._n_envs, dtype=np.int64)  # monotone (dataset-export cursor)
        self.dataset_disk_bytes = 0
        self._rng = np.random.default_rng()
        # multi-device: the ring is sharded over the mesh's data axis along
        # the env dimension; each device stores and samples only its env block
        self._mesh = mesh if (mesh is not None and mesh.devices.size > 1) else None
        self._world = int(self._mesh.devices.size) if self._mesh else 1
        if self._mesh and self._n_envs % self._world != 0:
            raise ValueError(
                f"n_envs ({self._n_envs}) must be divisible by the mesh size ({self._world}) "
                "for the env-sharded device buffer"
            )
        self._add = _make_sharded_add(self._mesh) if self._mesh else replay_add
        self._gather_cache: Dict[int, Any] = {}

    # -- properties mirrored from the host buffer ---------------------------
    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def full(self):
        return tuple(bool(f >= self._buffer_size) for f in self._filled)

    @property
    def empty(self) -> bool:
        return not self._buf

    @property
    def is_memmap(self) -> bool:
        return False

    @property
    def added_steps(self) -> np.ndarray:
        """Per-env monotone count of steps ever added (envs advance
        independently here — episode-end rows go only to done envs)."""
        return self._added.copy()

    def __len__(self) -> int:
        return self._buffer_size

    def seed(self, seed: Optional[int]) -> None:
        self._rng = np.random.default_rng(seed)

    # -- write path ----------------------------------------------------------
    def add(self, data: Dict[str, np.ndarray], indices: Any = None, validate_args: bool = False) -> None:
        """Insert ONE policy step.  ``data`` leaves are ``[1, n_sel, ...]``
        where ``n_sel = len(indices)`` (all envs when ``indices`` is None)."""
        del validate_args
        # Coerce non-array leaves (lists/scalars) so .shape/.dtype are defined
        # everywhere below; array leaves (numpy or jax) pass through without a
        # host round-trip. Build a local dict rather than writing back into
        # the caller's (callers reuse step_data across iterations).
        data = {
            k: v if isinstance(v, (np.ndarray, jax.Array)) else np.asarray(v)
            for k, v in data.items()
        }
        steps = next(iter(data.values())).shape[0]
        if steps != 1:
            raise ValueError(
                f"DeviceSequentialReplayBuffer.add expects one step at a time, got {steps}"
            )
        envs = np.arange(self._n_envs) if indices is None else np.asarray(list(indices))
        was_empty = self.empty
        # the whole-dict single-dispatch scatter requires every add() to carry
        # the full key set (partial writes would need per-key dispatches back)
        if not was_empty and data.keys() != self._buf.keys():
            raise KeyError(
                f"add() must provide exactly the buffer's key set {sorted(self._buf)}; "
                f"got {sorted(data)}"
            )
        for k, v in data.items():
            if k not in self._buf:
                # only reachable on the very first add (the key-set equality
                # check above rejects any mismatch once initialized)
                # Dtype policy: device storage is at most 32-bit.  JAX's x64
                # mode is off framework-wide, so 64-bit leaves would silently
                # narrow inside jnp.zeros; make the narrowing explicit and loud
                # (checkpoint round trips toggling buffer.device would
                # otherwise change dtypes without a trace — ADVICE r2).
                dtype = np.dtype(v.dtype)
                if dtype.itemsize == 8 and dtype.kind in "fiu":
                    narrowed = np.dtype(f"{dtype.kind}4")
                    warnings.warn(
                        f"DeviceSequentialReplayBuffer: key '{k}' arrives as {dtype} but device "
                        f"storage is 32-bit; storing as {narrowed}",
                        UserWarning,
                        stacklevel=2,
                    )
                    dtype = narrowed
                self._buf[k] = self._to_storage(
                    jnp.zeros((self._buffer_size, self._n_envs, math.prod(v.shape[2:])), dtype=dtype)
                )
                self._shapes += ((k, tuple(v.shape[2:])),)
        rows = jnp.asarray(self._pos[envs] % self._buffer_size, jnp.int32)
        envs_dev = jnp.asarray(envs, jnp.int32)
        # device leaves (e.g. the player's actions) stay on device: the slice
        # is a dispatched op, never a blocking fetch — this is what lets the
        # hot loop add the current step *before* fetching the action values
        # (see dreamer_v3.py's pipelined iteration).  Host leaves ride along
        # as KB-sized transfer operands of the same single dispatch.
        step = {k: v[0] for k, v in data.items()}
        self._buf = self._add(self._buf, step, rows, envs_dev)
        self._pos[envs] = (self._pos[envs] + 1) % self._buffer_size
        self._filled[envs] = np.minimum(self._filled[envs] + 1, self._buffer_size)
        self._added[envs] += 1

    def mark_last_truncated(self, env_idx: int) -> None:
        """Flag the most recent stored step of one env as truncated (the
        RestartOnException surgery, reference dreamer_v3.py:656-664)."""
        last = int((self._pos[env_idx] - 1) % self._buffer_size)
        self._buf["terminated"] = self._buf["terminated"].at[last, env_idx].set(0.0)
        self._buf["truncated"] = self._buf["truncated"].at[last, env_idx].set(1.0)
        if "is_first" in self._buf:
            self._buf["is_first"] = self._buf["is_first"].at[last, env_idx].set(0.0)

    # -- read path -----------------------------------------------------------
    def _draw_env_idx(self, n: int, seq_len: int) -> np.ndarray:
        valid_envs = np.nonzero(self._filled >= seq_len)[0]
        if self._mesh is None:
            if valid_envs.size == 0:
                raise ValueError(
                    f"Cannot sample a sequence of length {seq_len}. Data added so far: {self._filled.tolist()}"
                )
            return valid_envs[self._rng.integers(0, valid_envs.size, size=(n,))]
        # env-sharded: each device's batch block draws only from its own env
        # block (block-stratified rather than iid-uniform over all envs), so
        # the shard_map gather stays fully local
        if n % self._world != 0:
            raise ValueError(f"batch_size ({n}) must be divisible by the mesh size ({self._world})")
        n_local = self._n_envs // self._world
        b_local = n // self._world
        blocks = []
        for d in range(self._world):
            local_valid = valid_envs[(valid_envs >= d * n_local) & (valid_envs < (d + 1) * n_local)]
            if local_valid.size == 0:
                raise ValueError(
                    f"Cannot sample a sequence of length {seq_len} from device {d}'s env block. "
                    f"Data added so far: {self._filled.tolist()}"
                )
            blocks.append(local_valid[self._rng.integers(0, local_valid.size, size=(b_local,))])
        return np.concatenate(blocks)

    def _draw(self, n: int, seq_len: int):
        """(starts, env_idx) numpy arrays for ``n`` valid sequence windows."""
        if self.empty or self._filled.max(initial=0) == 0:
            raise ValueError("No sample has been added to the buffer. Call 'add' first")
        if seq_len > self._buffer_size:
            raise ValueError(
                f"The sequence length ({seq_len}) is greater than the buffer size ({self._buffer_size})"
            )
        env_idx = self._draw_env_idx(n, seq_len)
        filled = self._filled[env_idx]
        pos = self._pos[env_idx]
        # age of the window start, uniform over each env's valid range
        start_ages = seq_len - 1 + (
            self._rng.random(n) * (filled - seq_len + 1)
        ).astype(np.int64)
        starts = np.where(
            filled >= self._buffer_size,
            (pos - 1 - start_ages) % self._buffer_size,
            filled - 1 - start_ages,
        )
        return starts, env_idx

    def sample(self, batch_size: int, sequence_length: int = 1, n_samples: int = 1, **_: Any):
        """A LIST of ``n_samples`` device batches, each a dict of
        ``[T, batch_size, ...]`` arrays already resident in HBM."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        gather = None
        if self._mesh is not None:
            if sequence_length not in self._gather_cache:
                self._gather_cache[sequence_length] = _make_sharded_gather(self._mesh, sequence_length, self._shapes)
            gather = self._gather_cache[sequence_length]
        out = []
        for _ in range(n_samples):
            starts, env_idx = self._draw(batch_size, sequence_length)
            if self._mesh is not None:
                # local env index within each device's block + sharded inputs
                n_local = self._n_envs // self._world
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                idx_sharding = NamedSharding(self._mesh, P("data"))
                starts_dev = jax.device_put(jnp.asarray(starts, jnp.int32), idx_sharding)
                env_local = jax.device_put(jnp.asarray(env_idx % n_local, jnp.int32), idx_sharding)
                out.append(gather(self._buf, starts_dev, env_local))
            else:
                out.append(
                    replay_gather(
                        self._buf,
                        jnp.asarray(starts, jnp.int32),
                        jnp.asarray(env_idx, jnp.int32),
                        sequence_length,
                        self._shapes,
                    )
                )
        return out

    # -- footprint (diagnostics memory telemetry) ------------------------------
    def footprint(self) -> Dict[str, int]:
        """HBM-resident storage bytes (``device_bytes`` is the GLOBAL total;
        env-sharded storage splits it evenly across the mesh's devices)."""
        total = sum(int(v.nbytes) for v in self._buf.values())
        out = {"device_bytes": total}
        if self.dataset_disk_bytes:
            out["dataset_disk"] = int(self.dataset_disk_bytes)
        return out

    # -- checkpointing ---------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        # np.asarray over a jax.Array is a read-only view; copy so checkpoint
        # surgery (truncated-flag patching) can write into the snapshot
        return {
            "buffer": {k: np.array(self._buf[k]).reshape(*self._buf[k].shape[:2], *shape) for k, shape in self._shapes},
            "pos": self._pos.copy(),
            "filled": self._filled.copy(),
            "added": self._added.copy(),
        }

    def _to_storage(self, arr) -> jax.Array:
        storage = jnp.asarray(arr)
        if self._mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            storage = jax.device_put(storage, NamedSharding(self._mesh, P(None, "data")))
        return storage

    def _load_rings(self, rings: Dict[str, Any]) -> None:
        """Logical ``[cap, n_envs, ...]`` arrays (a checkpoint's) into the
        flat storage; the reshape is the host's, a view."""
        self._shapes = tuple((k, tuple(np.shape(v)[2:])) for k, v in rings.items())
        self._buf = {k: self._to_storage(np.asarray(v).reshape(*np.shape(v)[:2], -1)) for k, v in rings.items()}
        self._gather_cache.clear()

    def load_state_dict(self, state: Dict[str, Any]) -> "DeviceSequentialReplayBuffer":
        if "buffers" in state:
            # host EnvIndependentReplayBuffer format (one sub-state per env):
            # stack the per-env [cap, 1, ...] storages along the env axis so
            # checkpoints survive toggling buffer.device between runs
            subs = state["buffers"]
            keys = subs[0]["buffer"].keys()
            self._load_rings({k: np.concatenate([np.asarray(s["buffer"][k]) for s in subs], axis=1) for k in keys})
            self._pos = np.asarray([s["pos"] for s in subs], dtype=np.int64)
            self._filled = np.asarray(
                [self._buffer_size if s["full"] else s["pos"] for s in subs], dtype=np.int64
            )
            self._added = np.asarray(
                [s.get("added", self._buffer_size if s["full"] else s["pos"]) for s in subs],
                dtype=np.int64,
            )
            return self
        self._load_rings(state["buffer"])
        self._pos = np.asarray(state["pos"], dtype=np.int64).copy()
        self._filled = np.asarray(state["filled"], dtype=np.int64).copy()
        # checkpoints predating the export subsystem: the stored window is
        # the best lower bound (mirrors ReplayBuffer.load_state_dict)
        self._added = np.asarray(state.get("added", self._filled), dtype=np.int64).copy()
        return self
