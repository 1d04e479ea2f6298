"""EnvPool-style persistent shared-memory vector-env executor.

Gymnasium's ``AsyncVectorEnv`` round-trips every observation through a pickled
pipe message (or, with ``shared_memory=True``, still pays a per-step pickle of
the step results).  This executor keeps persistent worker processes (spawned
once, reused for the whole run — the EnvPool model, Weng et al. 2022) and
moves the per-step payload entirely through pre-allocated shared buffers:

* actions are written in place by the parent, read in place by workers;
* observations (and the terminal observation on autoreset boundaries) are
  written in place by workers into per-key shared buffers and copied out
  **once**, batched, in :meth:`step_wait`;
* rewards / terminated / truncated live in shared scalar buffers (rewards as
  float32 end-to-end — the training loops cast to float32 anyway, so a
  float64 slab would only buy a bigger buffer and one extra downcast copy);
* the per-step pipe traffic is a single command byte down and a single ack
  byte back **per worker** — the only pickling left happens on the rare steps
  whose ``info`` dict is non-empty (episode ends, env restarts).

Worker sharding (``envs_per_worker``): each worker owns a contiguous slab of
envs and steps it sequentially, writing results straight into its slice of
the shared buffers.  The host's per-step Python work is therefore
O(num_workers) — one command write and one ack drain per worker — plus one
vectorized copy per observation key, instead of the one-process-per-env
model's O(num_envs) pipe round-trips and per-env read loop.  That is what
keeps 64-512 concurrent envs throughput-bound instead of Python-bound
(PERF.md §6); ``envs_per_worker=1`` recovers the one-env-per-process layout
for expensive simulators that need a whole core each.

Autoreset follows ``gym.vector.AutoresetMode.SAME_STEP`` bit-for-bit with
``SyncVectorEnv``: on done the returned obs is the new episode's reset obs,
the terminal obs rides in ``infos["final_obs"]`` and the final step's info in
``infos["final_info"]`` (aggregated through the inherited ``_add_info``, so
the ``_key`` mask layout is byte-identical to gymnasium's own vector envs).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import gymnasium as gym
import numpy as np
from gymnasium.vector.utils import CloudpickleWrapper, batch_space

_CMD_STEP = b"S"  # step every env of the worker's slab
_CMD_CLOSE = b"C"
_CMD_RESET = b"R"  # followed by pickled (per-slab seed list, options)
_ACK_EMPTY = b"n"  # slab stepped: every info was {} and no autoreset happened


def _obs_layout(space: gym.Space) -> List[Tuple[Optional[str], tuple, np.dtype]]:
    """Flatten a Dict-of-Box (or plain Box) observation space into
    ``(key, shape, dtype)`` buffer specs; ``key is None`` for a bare Box."""
    if isinstance(space, gym.spaces.Dict):
        return [(k, tuple(s.shape), np.dtype(s.dtype)) for k, s in space.spaces.items()]
    if isinstance(space, gym.spaces.Box):
        return [(None, tuple(space.shape), np.dtype(space.dtype))]
    raise TypeError(
        f"SharedMemoryVectorEnv supports Box or Dict[str, Box] observation spaces, got: {space}"
    )


def _alloc(ctx, num_envs: int, layout) -> Dict[Optional[str], Any]:
    """One shared byte buffer per obs key, sized ``[num_envs, *shape]``."""
    return {
        key: ctx.RawArray("b", int(num_envs * np.prod(shape, dtype=np.int64) * dtype.itemsize) or 1)
        for key, shape, dtype in layout
    }


def _views(bufs, num_envs: int, layout) -> Dict[Optional[str], np.ndarray]:
    return {
        key: np.frombuffer(bufs[key], dtype=dtype).reshape(num_envs, *shape)
        for key, shape, dtype in layout
    }


def _write_obs(views: Dict[Optional[str], np.ndarray], index: int, obs: Any) -> None:
    for key, view in views.items():
        view[index] = obs if key is None else np.asarray(obs[key])


def _read_obs(views: Dict[Optional[str], np.ndarray], index: int) -> Any:
    if list(views.keys()) == [None]:
        return np.array(views[None][index], copy=True)
    return {k: np.array(v[index], copy=True) for k, v in views.items()}


def auto_envs_per_worker(num_envs: int) -> int:
    """Default slab size: enough workers to use every host core (one env per
    worker up to ``cpu_count`` workers), then grow the slabs instead of the
    process count — 256 envs on a 64-core TPU-VM host become 64 workers of 4
    envs, not 256 processes fighting the scheduler."""
    workers = max(1, min(int(num_envs), os.cpu_count() or 1))
    return -(-int(num_envs) // workers)  # ceil division


def _worker(
    start: int,
    env_fns_wrapper: CloudpickleWrapper,
    pipe,
    obs_bufs,
    final_bufs,
    act_buf,
    rew_buf,
    term_buf,
    trunc_buf,
    obs_specs,
    act_shape,
    act_dtype,
    num_envs: int,
) -> None:
    """Persistent slab worker: owns envs ``[start, start + len(fns))`` and
    steps/resets them in place over the shared buffers, one command/ack round
    trip per *vector* step.

    Env-level fault tolerance stays INSIDE the worker — wrap the env fns in
    ``RestartOnException`` before building the executor and a transient env
    crash is absorbed here (the restart info flag still reaches the parent),
    instead of killing the worker process and its whole slab.
    """
    envs = [fn() for fn in env_fns_wrapper.fn]
    obs_views = _views(obs_bufs, num_envs, obs_specs)
    final_views = _views(final_bufs, num_envs, obs_specs)
    act_view = np.frombuffer(act_buf, dtype=act_dtype).reshape(num_envs, *act_shape[1:])
    rew_view = np.frombuffer(rew_buf, dtype=np.float32)
    term_view = np.frombuffer(term_buf, dtype=np.uint8)
    trunc_view = np.frombuffer(trunc_buf, dtype=np.uint8)
    try:
        while True:
            cmd = pipe.recv_bytes()
            try:
                if cmd == _CMD_STEP:
                    # (env index, info, has_final, final_info) for the rare
                    # envs with something to pickle; an all-quiet slab acks
                    # with one byte
                    payloads: List[Tuple[int, dict, bool, Optional[dict]]] = []
                    for offset, env in enumerate(envs):
                        index = start + offset
                        action = act_view[index]
                        if action.ndim > 0:
                            action = np.array(action, copy=True)  # detach from the shared page
                        obs, reward, terminated, truncated, info = env.step(action)
                        has_final = False
                        final_info: Optional[dict] = None
                        if terminated or truncated:  # SAME_STEP autoreset
                            _write_obs(final_views, index, obs)
                            final_info = info
                            has_final = True
                            obs, info = env.reset()
                        _write_obs(obs_views, index, obs)
                        rew_view[index] = np.float32(reward)
                        term_view[index] = np.uint8(terminated)
                        trunc_view[index] = np.uint8(truncated)
                        if info or has_final:
                            payloads.append((index, info, has_final, final_info))
                    if payloads:
                        pipe.send_bytes(pickle.dumps(("ok", payloads)))
                    else:
                        pipe.send_bytes(_ACK_EMPTY)
                elif cmd == _CMD_CLOSE:
                    break
                else:  # reset: _CMD_RESET + pickled (slab seed list, options)
                    seeds, options = pickle.loads(cmd[1:])
                    infos: List[dict] = []
                    for offset, env in enumerate(envs):
                        obs, info = env.reset(seed=seeds[offset], options=options)
                        _write_obs(obs_views, start + offset, obs)
                        infos.append(info)
                    pipe.send_bytes(pickle.dumps(("ok", infos)))
            except Exception as err:  # noqa: BLE001 — surfaced in the parent
                import traceback

                pipe.send_bytes(pickle.dumps(("error", f"{err!r}\n{traceback.format_exc()}")))
    finally:
        for env in envs:
            try:
                env.close()
            except Exception:  # pragma: no cover - best-effort teardown
                pass
        pipe.close()


class SharedMemoryVectorEnv(gym.vector.VectorEnv):
    """Persistent slab-worker vector env with in-place shared-memory transport.

    Drop-in for ``Sync``/``AsyncVectorEnv`` under SAME_STEP autoreset, with
    native ``step_async``/``step_wait`` so the training loops can overlap env
    stepping with device dispatch.  Selected via ``cfg.env.executor=shared_memory``;
    ``cfg.env.envs_per_worker`` sets the slab size (null = auto heuristic).
    """

    def __init__(
        self,
        env_fns: Sequence[Callable[[], gym.Env]],
        context: str = "spawn",
        step_timeout: Optional[float] = None,
        envs_per_worker: Optional[int] = None,
    ):
        self.env_fns = list(env_fns)
        self.num_envs = len(self.env_fns)
        if self.num_envs == 0:
            raise ValueError("SharedMemoryVectorEnv needs at least one env fn")
        self._step_timeout = step_timeout
        if envs_per_worker in (None, "auto"):
            envs_per_worker = auto_envs_per_worker(self.num_envs)
        self.envs_per_worker = int(envs_per_worker)
        if self.envs_per_worker < 1:
            raise ValueError(f"envs_per_worker must be >= 1, got: {envs_per_worker}")
        # contiguous slabs: worker w owns envs [w*epw, min((w+1)*epw, N))
        self._slabs: List[Tuple[int, int]] = [
            (lo, min(lo + self.envs_per_worker, self.num_envs))
            for lo in range(0, self.num_envs, self.envs_per_worker)
        ]
        self.num_workers = len(self._slabs)

        # probe spaces/metadata exactly like gymnasium's AsyncVectorEnv does
        probe = self.env_fns[0]()
        try:
            self.metadata = dict(getattr(probe, "metadata", {}) or {})
            self.single_observation_space = probe.observation_space
            self.single_action_space = probe.action_space
            self.render_mode = getattr(probe, "render_mode", None)
        finally:
            probe.close()
        self.metadata["autoreset_mode"] = gym.vector.AutoresetMode.SAME_STEP
        self.observation_space = batch_space(self.single_observation_space, self.num_envs)
        # fail at construction like the obs path does — an unsupported action
        # space would otherwise surface as a confusing dtype/reshape error on
        # the first step (batch_space(Dict/Tuple).dtype is None)
        if not isinstance(
            self.single_action_space, (gym.spaces.Box, gym.spaces.Discrete, gym.spaces.MultiDiscrete)
        ):
            raise TypeError(
                "SharedMemoryVectorEnv supports Box, Discrete or MultiDiscrete action "
                f"spaces, got: {self.single_action_space}"
            )
        self.action_space = batch_space(self.single_action_space, self.num_envs)

        ctx = mp.get_context(context)
        self._obs_specs = _obs_layout(self.single_observation_space)
        self._obs_bufs = _alloc(ctx, self.num_envs, self._obs_specs)
        self._final_bufs = _alloc(ctx, self.num_envs, self._obs_specs)
        act_dtype = np.dtype(self.action_space.dtype)
        act_shape = tuple(self.action_space.shape)
        self._act_buf = ctx.RawArray("b", int(np.prod(act_shape, dtype=np.int64) * act_dtype.itemsize) or 1)
        self._rew_buf = ctx.RawArray("b", self.num_envs * 4)  # float32 end-to-end
        self._term_buf = ctx.RawArray("b", self.num_envs)
        self._trunc_buf = ctx.RawArray("b", self.num_envs)

        self._obs_views = _views(self._obs_bufs, self.num_envs, self._obs_specs)
        self._final_views = _views(self._final_bufs, self.num_envs, self._obs_specs)
        self._act_view = np.frombuffer(self._act_buf, dtype=act_dtype).reshape(act_shape)
        self._rew_view = np.frombuffer(self._rew_buf, dtype=np.float32)
        self._term_view = np.frombuffer(self._term_buf, dtype=np.uint8)
        self._trunc_view = np.frombuffer(self._trunc_buf, dtype=np.uint8)

        self._pipes = []
        self._processes = []
        self._pending = False
        self._closed = False
        for w, (lo, hi) in enumerate(self._slabs):
            parent_pipe, child_pipe = ctx.Pipe()
            proc = ctx.Process(
                target=_worker,
                name=f"shm-env-{lo}-{hi - 1}",
                args=(
                    lo,
                    CloudpickleWrapper(tuple(self.env_fns[lo:hi])),
                    child_pipe,
                    self._obs_bufs,
                    self._final_bufs,
                    self._act_buf,
                    self._rew_buf,
                    self._term_buf,
                    self._trunc_buf,
                    self._obs_specs,
                    act_shape,
                    act_dtype,
                    self.num_envs,
                ),
                daemon=True,
            )
            proc.start()
            child_pipe.close()
            self._pipes.append(parent_pipe)
            self._processes.append(proc)

    # -- helpers -----------------------------------------------------------
    def _recv(self, worker: int):
        """One ack from one worker: ``("ok", payload)`` or a raised worker
        error.  ``payload`` is the step payload list or the reset info list."""
        pipe = self._pipes[worker]
        lo, hi = self._slabs[worker]
        if self._step_timeout is not None and not pipe.poll(self._step_timeout):
            raise TimeoutError(
                f"env worker {worker} (envs {lo}..{hi - 1}) did not answer within {self._step_timeout}s"
            )
        try:
            msg = pipe.recv_bytes()
        except (EOFError, ConnectionResetError) as err:
            raise RuntimeError(
                f"env worker {worker} (envs {lo}..{hi - 1}) died (crashed outside RestartOnException?)"
            ) from err
        if msg == _ACK_EMPTY:
            return []
        payload = pickle.loads(msg)
        if payload[0] == "error":
            raise RuntimeError(f"env worker {worker} (envs {lo}..{hi - 1}) raised:\n{payload[1]}")
        return payload[1]

    def _batched_obs(self):
        # ONE vectorized memcpy per key out of the shared slabs.  The copy —
        # not a zero-copy view — is deliberate: the training loops retain the
        # returned obs across the next step_async window, during which the
        # workers are already overwriting the shared pages in place.
        if list(self._obs_views.keys()) == [None]:
            return np.array(self._obs_views[None], copy=True)
        return {k: np.array(v, copy=True) for k, v in self._obs_views.items()}

    # -- gym.vector API ----------------------------------------------------
    def reset(self, *, seed=None, options=None):
        if self._pending:
            raise RuntimeError("reset() called while a step_async is in flight")
        if seed is None:
            seeds: List[Optional[int]] = [None] * self.num_envs
        elif isinstance(seed, int):
            seeds = [seed + i for i in range(self.num_envs)]
        else:
            seeds = list(seed)
            if len(seeds) != self.num_envs:
                raise ValueError(f"expected {self.num_envs} seeds, got {len(seeds)}")
        for pipe, (lo, hi) in zip(self._pipes, self._slabs):
            pipe.send_bytes(_CMD_RESET + pickle.dumps((seeds[lo:hi], options)))
        infos: Dict[str, Any] = {}
        for w, (lo, _) in enumerate(self._slabs):
            for offset, info in enumerate(self._recv(w)):
                infos = self._add_info(infos, info, lo + offset)
        return self._batched_obs(), infos

    def step_async(self, actions) -> None:
        if self._pending:
            raise RuntimeError("step_async() called while a previous step is still in flight")
        np.copyto(self._act_view, np.asarray(actions, dtype=self._act_view.dtype).reshape(self._act_view.shape))
        for pipe in self._pipes:
            pipe.send_bytes(_CMD_STEP)
        self._pending = True

    def step_wait(self):
        if not self._pending:
            raise RuntimeError("step_wait() called with no step_async in flight")
        self._pending = False
        # one ack drain per WORKER; per-env Python happens only for the rare
        # envs that shipped a payload (episode end, restart, non-empty info)
        infos: Dict[str, Any] = {}
        for w in range(self.num_workers):
            for index, info, has_final, final_info in self._recv(w):
                if has_final:
                    infos = self._add_info(
                        infos,
                        {"final_obs": _read_obs(self._final_views, index), "final_info": final_info or {}},
                        index,
                    )
                infos = self._add_info(infos, info, index)
        return (
            self._batched_obs(),
            self._rew_view.copy(),
            self._term_view.astype(np.bool_),
            self._trunc_view.astype(np.bool_),
            infos,
        )

    def step(self, actions):
        self.step_async(actions)
        return self.step_wait()

    def close(self, **kwargs) -> None:
        if self._closed:
            return
        self._closed = True
        if self._pending:  # drain so workers are at the top of their loop
            try:
                self.step_wait()
            except Exception:  # pragma: no cover - already tearing down
                pass
        for pipe in self._pipes:
            try:
                pipe.send_bytes(_CMD_CLOSE)
            except (BrokenPipeError, OSError):
                pass
        deadline = time.monotonic() + 5.0
        for proc in self._processes:
            proc.join(max(0.1, deadline - time.monotonic()))
            if proc.is_alive():  # pragma: no cover - stuck worker
                proc.terminate()
        for pipe in self._pipes:
            pipe.close()

    def __del__(self):  # pragma: no cover - GC teardown
        try:
            self.close()
        except Exception:
            pass
