"""Split-phase (``step_async`` / ``step_wait``) facade over any vector env.

The training loops' critical path used to be ``fetch actions -> envs.step ->
train dispatch`` — a fully serialized sum (PERF.md §5).  This wrapper gives
every executor one uniform async surface so the hot loops can issue the env
step the moment the action values land, keep dispatching device work (train
step, replay writes) while the env workers are stepping, and only block in
``step_wait`` right before the observations are needed — making the
per-iteration critical path ``max(host dispatch + fetch, env_step)``.

Executors (``cfg.env.executor``):

* ``sync`` — gymnasium ``SyncVectorEnv``; ``step_async`` runs the serial step
  on a dedicated background thread.  Real simulators release the GIL in their
  native step (and plain sleeps do too), so the overlap is real; for pure
  in-process Python toy envs it degrades gracefully to the serialized cost.
* ``async`` — gymnasium ``AsyncVectorEnv`` (one spawned OS process per env);
  its native ``step_async``/``step_wait`` is used directly.
* ``shared_memory`` — :class:`~sheeprl_tpu.envs.executor.SharedMemoryVectorEnv`,
  persistent slab workers with in-place shared obs/action buffers
  (EnvPool-style: no per-step pickling, one batched copy out, one
  command/ack per worker — ``env.envs_per_worker`` sets the slab size).

All three keep ``SAME_STEP`` autoreset semantics bit-for-bit (golden-tested
in ``tests/test_envs/test_async_pipeline.py``), and ``step()`` still works
(``step_async`` + ``step_wait``) so non-pipelined call sites are unaffected.
"""

from __future__ import annotations

from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

import gymnasium as gym

EXECUTORS = ("sync", "async", "shared_memory")


class PipelinedVectorEnv:
    """Uniform ``step_async``/``step_wait`` over Sync/Async/shared-memory
    vector envs; everything else (spaces, ``reset``, ``step``, ``num_envs``)
    delegates to the wrapped env."""

    def __init__(self, envs: gym.vector.VectorEnv):
        self.envs = envs
        self._native = callable(getattr(envs, "step_async", None)) and callable(
            getattr(envs, "step_wait", None)
        )
        self._pool: Optional[ThreadPoolExecutor] = (
            None if self._native else ThreadPoolExecutor(1, thread_name_prefix="env-step")
        )
        self._future: Optional[Future] = None
        self._pending = False

    # -- split-phase stepping ---------------------------------------------
    def step_async(self, actions: Any) -> None:
        """Start stepping the envs; returns immediately."""
        if self._pending:
            raise RuntimeError("step_async() called while a previous step is still in flight")
        if self._native:
            self.envs.step_async(actions)
        else:
            self._future = self._pool.submit(self.envs.step, actions)
        # only after a successful dispatch: a raising dispatch (bad actions
        # shape etc.) must leave the wrapper usable, not wedged in-flight
        self._pending = True

    def step_wait(self):
        """Block until the in-flight step finishes; returns the usual
        ``(obs, rewards, terminated, truncated, infos)`` 5-tuple."""
        if not self._pending:
            raise RuntimeError("step_wait() called with no step_async in flight")
        self._pending = False
        if self._native:
            return self.envs.step_wait()
        future, self._future = self._future, None
        return future.result()

    def step(self, actions: Any):
        """Serialized convenience path (identical results to async+wait).  A
        sync executor steps on the caller's thread: with nothing to overlap,
        the hand-over to the ``env-step`` thread and back is two wake-ups a
        vector step that a busy host stretches (PERF.md section 6, PR 31)."""
        if self._native:
            self.step_async(actions)
            return self.step_wait()
        if self._pending:
            raise RuntimeError("step() called while a step_async is in flight")
        return self.envs.step(actions)

    # -- passthrough -------------------------------------------------------
    def reset(self, *, seed=None, options=None):
        if self._pending:
            raise RuntimeError("reset() called while a step_async is in flight")
        return self.envs.reset(seed=seed, options=options)

    def close(self, **kwargs) -> None:
        if self._pending:  # drain so the executor shuts down at a step boundary
            try:
                self.step_wait()
            except Exception:  # pragma: no cover - already tearing down
                pass
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        self.envs.close(**kwargs)

    def __getattr__(self, name: str):
        if name.startswith("_") or name == "envs":  # avoid recursion pre-__init__
            raise AttributeError(name)
        return getattr(self.envs, name)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"PipelinedVectorEnv({self.envs!r}, native={self._native})"
