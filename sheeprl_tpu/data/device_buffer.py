"""Device-resident sequential replay buffer.

The reference streams every sampled batch host->GPU each gradient step
(reference sheeprl/data/buffers.py:291-326 converts to torch tensors per
sample).  On TPU that transfer is the end-to-end bottleneck: a DV3-S batch
(16 x 64 x 64x64x3 uint8) is ~50 MB per gradient step, while the *collected*
data is only ~12 KB per policy step.  This buffer therefore keeps the whole
replay ring in HBM:

- ``add`` scatters one policy step into the ring in place (jitted, donated)
  — the only host->device traffic is the newest frame;
- per-env write heads: envs advance independently (episode-end rows are
  appended only to done envs), replacing the host path's one-sub-buffer-per-
  env ``EnvIndependentReplayBuffer`` + ``SequentialReplayBuffer`` pair;
- ``sample`` draws sequence windows with the host ``SequentialReplayBuffer``'s
  age-space semantics (windows never span an env's write head; starts uniform
  over each env's valid range) but the gather runs on device and the returned
  ``[T, B, ...]`` batch never touches the host.  Env choice is uniform on a
  single device; in multi-device mode it is *block-stratified* — each device's
  batch block draws only from its own env shard (see ``_draw_env_idx``);
- capacity math: DV3 Atari-100K (1e5 steps x 64x64x3 uint8) is ~1.2 GB — it
  fits v5e HBM next to the S model.  For bigger buffers keep the host path
  (``buffer.device=False``).

Head bookkeeping (per-env ``pos``/``full``) stays on the host: it's a few
ints per policy step, and host-side index math keeps sampling logic in cheap
numpy while every array byte stays in HBM.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# The two jitted functions' names are their executables' names in a profile
# (``jit_replay_add`` / ``jit_replay_gather`` on the ``XLA Modules`` line):
# a reduction finds them by name, so a rename is a change to what is measured.
@partial(jax.jit, donate_argnums=(0,))
def replay_add(buf: Dict[str, jax.Array], step: Dict[str, jax.Array], rows: jax.Array, envs: jax.Array) -> Dict[str, jax.Array]:
    """Whole-dict ring write in ONE dispatched program: ``step[k]`` is
    ``[n_sel, ...]`` written at ``(rows[i], envs[i])`` of ``buf[k]``.  One
    device call per policy step instead of one per key — each dispatch is
    host work on the hot thread, and at 7 buffer keys that is 7 of them per
    step (per-dispatch cost on an attached host: not measured; the chip
    benchmark should re-decide whether the fusion still pays).  Works for
    sharded storage too: the updates are tiny and the SPMD partitioner
    applies each to the owning shard."""
    return {k: buf[k].at[rows, envs].set(step[k]) for k in buf}


@partial(jax.jit, static_argnums=(3,))
def replay_gather(buf: Dict[str, jax.Array], starts: jax.Array, env_idx: jax.Array, seq_len: int) -> Dict[str, jax.Array]:
    """Whole-dict sequence gather in ONE dispatched program:
    ``[cap, n_envs, ...] -> [seq_len, B, ...]`` per key; window ``b`` is rows
    ``(starts[b] + t) % cap`` of env ``env_idx[b]``."""
    cap = next(iter(buf.values())).shape[0]
    rows = (starts[None, :] + jnp.arange(seq_len)[:, None]) % cap  # [T, B]
    return {k: v[rows, env_idx[None, :]] for k, v in buf.items()}


def _make_sharded_gather(mesh, seq_len: int):
    """Per-device local gather over an env-sharded ring (multi-device mode).

    Inside ``shard_map`` every device sees only its env block; ``env_idx`` is
    drawn block-stratified on the host so each device's indices are local.
    The output batch leaves sharded ``P(None, "data")`` on the batch axis —
    exactly the in_spec of the shard_map'd Dreamer train steps — with ZERO
    cross-device traffic.
    """
    from jax.sharding import PartitionSpec as P

    from sheeprl_tpu.parallel.dp import dp_jit

    def local_gather(storage, starts, env_local):
        return replay_gather(storage, starts, env_local, seq_len)

    return dp_jit(
        local_gather,
        mesh,
        in_specs=(P(None, "data"), P("data"), P("data")),
        out_specs=P(None, "data"),
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
        self._buf: Dict[str, jax.Array] = {}
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
                    jnp.zeros((self._buffer_size, self._n_envs, *v.shape[2:]), dtype=dtype)
                )
        rows = jnp.asarray(self._pos[envs] % self._buffer_size, jnp.int32)
        envs_dev = jnp.asarray(envs, jnp.int32)
        # device leaves (e.g. the player's actions) stay on device: the slice
        # is a dispatched op, never a blocking fetch — this is what lets the
        # hot loop add the current step *before* fetching the action values
        # (see dreamer_v3.py's pipelined iteration).  Host leaves ride along
        # as KB-sized transfer operands of the same single dispatch.
        step = {k: v[0] for k, v in data.items()}
        self._buf = replay_add(self._buf, step, rows, envs_dev)
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
                self._gather_cache[sequence_length] = _make_sharded_gather(self._mesh, sequence_length)
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
            "buffer": {k: np.array(v) for k, v in self._buf.items()},
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

    def load_state_dict(self, state: Dict[str, Any]) -> "DeviceSequentialReplayBuffer":
        if "buffers" in state:
            # host EnvIndependentReplayBuffer format (one sub-state per env):
            # stack the per-env [cap, 1, ...] storages along the env axis so
            # checkpoints survive toggling buffer.device between runs
            subs = state["buffers"]
            keys = subs[0]["buffer"].keys()
            self._buf = {
                k: self._to_storage(np.concatenate([np.asarray(s["buffer"][k]) for s in subs], axis=1))
                for k in keys
            }
            self._pos = np.asarray([s["pos"] for s in subs], dtype=np.int64)
            self._filled = np.asarray(
                [self._buffer_size if s["full"] else s["pos"] for s in subs], dtype=np.int64
            )
            self._added = np.asarray(
                [s.get("added", self._buffer_size if s["full"] else s["pos"]) for s in subs],
                dtype=np.int64,
            )
            return self
        self._buf = {k: self._to_storage(v) for k, v in state["buffer"].items()}
        self._pos = np.asarray(state["pos"], dtype=np.int64).copy()
        self._filled = np.asarray(state["filled"], dtype=np.int64).copy()
        # checkpoints predating the export subsystem: the stored window is
        # the best lower bound (mirrors ReplayBuffer.load_state_dict)
        self._added = np.asarray(state.get("added", self._filled), dtype=np.int64).copy()
        return self
