"""Async off-critical-path checkpointing.

The train loop's cost is one :func:`host_snapshot` — a batched
``jax.device_get`` for device arrays plus a private copy of host-numpy leaves
(replay-buffer slabs are mutated in place by the very next vector step, and
the truncated-flag surgery in ``CheckpointCallback`` is *undone* right after
submit, so the snapshot must not alias caller memory) — and an enqueue.  A
single background thread serializes/fsyncs through the atomic tmp+rename in
``utils/checkpoint.py::save_state``, writes the manifest sidecar, and
journals ``ckpt_begin`` / ``ckpt_end`` (write duration, bytes, queued time)
so the goodput train spans no longer absorb checkpoint cost.

Double-buffering with backpressure: at most ``max_pending`` snapshots wait in
the queue; a loop that checkpoints faster than the disk can absorb blocks in
``submit`` instead of accumulating unbounded host copies.  A failed write
journals ``ckpt_end`` with ``status="failed"`` and warns — it never raises
into the training loop (the next periodic checkpoint is the retry).
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, Mapping, Optional

import numpy as np

#: Queue-entry sentinel marking a submitted callable (``submit_task``) rather
#: than a checkpoint snapshot.
_TASK = object()


def host_snapshot(tree: Any):
    """Cheap, self-owned host copy of a checkpoint state tree: numpy leaves
    are copied (they may alias live replay storage), device arrays ride ONE
    batched ``jax.device_get``, everything else (scalars, strings) passes
    through.  Containers are rebuilt by ``tree_map``, so later mutation of
    the caller's dicts/lists cannot reach the snapshot either."""
    import jax

    def copy_host(x: Any) -> Any:
        return x.copy() if isinstance(x, np.ndarray) else x

    copied = jax.tree_util.tree_map(copy_host, tree)
    return jax.device_get(copied)


class AsyncCheckpointWriter:
    """Background checkpoint writer behind ``ResilienceMonitor.save``.

    ``journal_fn(kind, **fields)`` may be None (direct callers);
    ``clock`` is injectable for deterministic tests.
    """

    def __init__(
        self,
        journal_fn: Optional[Callable[..., None]] = None,
        max_pending: int = 2,
        clock: Callable[[], float] = time.perf_counter,
    ):
        self._journal_fn = journal_fn
        self.max_pending = max(1, int(max_pending))
        self._clock = clock
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._writing = False
        self._closed = False
        self._thread: Optional[threading.Thread] = None

        self.written_total = 0
        self.failed_total = 0
        self.write_seconds_total = 0.0
        self.last_write_ms: Optional[float] = None
        self.last_step: Optional[int] = None
        self.last_path: Optional[str] = None
        # wall-clock stamps feeding the ckpt age / cadence gauges
        self.last_end_t: Optional[float] = None
        self.last_interval_s: Optional[float] = None

    # -- producer side (the training loop) ----------------------------------
    def submit(
        self,
        path: str,
        state: Mapping[str, Any],
        step: Optional[int] = None,
        group: Optional[Mapping[str, Any]] = None,
        delay_s: Optional[float] = None,
    ) -> float:
        """Snapshot ``state`` to host and enqueue the write; returns the
        critical-path seconds the caller paid.  Blocks only when
        ``max_pending`` snapshots are already waiting (backpressure).
        ``group`` is the coordinated-snapshot manifest record; ``delay_s``
        is the chaos ``slow_write`` injection — the writer thread sleeps it
        before serializing, inflating write cost OFF the critical path."""
        t0 = self._clock()
        snapshot = host_snapshot(state)
        with self._cond:
            if self._closed:
                raise RuntimeError("AsyncCheckpointWriter is closed")
            while len(self._queue) >= self.max_pending and not self._closed:
                self._cond.wait(timeout=1.0)
            self._queue.append((str(path), snapshot, step, time.time(), group, delay_s))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._worker, name="sheeprl-ckpt-writer", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()
        return self._clock() - t0

    def submit_task(self, fn: Callable[[], None]) -> None:
        """Enqueue an arbitrary off-critical-path task on the writer thread
        (dataset shard serialization rides here behind ``buffer.export`` —
        same FIFO as checkpoint writes, same backpressure, drained by
        ``drain``/``close`` so a preemption never abandons queued shards).
        A failing task warns and is dropped; it never raises into the loop."""
        with self._cond:
            if self._closed:
                raise RuntimeError("AsyncCheckpointWriter is closed")
            while len(self._queue) >= self.max_pending and not self._closed:
                self._cond.wait(timeout=1.0)
            self._queue.append((_TASK, fn, None, time.time(), None, None))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._worker, name="sheeprl-ckpt-writer", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()

    # -- consumer side (the writer thread) -----------------------------------
    def _worker(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait(timeout=1.0)
                if not self._queue:
                    return  # closed and drained
                path, snapshot, step, enqueued_t, group, delay_s = self._queue.popleft()
                self._writing = True
                self._cond.notify_all()
            try:
                if path is _TASK:
                    try:
                        snapshot()  # the submitted callable
                    except Exception as err:
                        warnings.warn(
                            f"async writer task failed: {err!r} (the run continues)",
                            RuntimeWarning,
                        )
                else:
                    self._write_one(path, snapshot, step, enqueued_t, group, delay_s)
            finally:
                with self._cond:
                    self._writing = False
                    self._cond.notify_all()

    def _write_one(
        self,
        path: str,
        snapshot: Any,
        step: Optional[int],
        enqueued_t: float,
        group: Optional[Mapping[str, Any]] = None,
        delay_s: Optional[float] = None,
    ) -> None:
        from sheeprl_tpu.resilience.manifest import checkpoint_step, save_verified_checkpoint

        if delay_s:
            time.sleep(delay_s)  # chaos slow_write: cost lands in write_ms/queued_s
        step = step if step is not None else checkpoint_step(path, snapshot)
        queued_s = round(max(0.0, time.time() - enqueued_t), 3)
        self._journal("ckpt_begin", path=path, step=step, blocking=False, queued_s=queued_s)
        try:
            # group threaded only when coordinated: the single-process call is
            # bit-identical to the pre-coordination one (and compatible with
            # test doubles carrying the original signature)
            kwargs = {"group": group} if group is not None else {}
            result = save_verified_checkpoint(path, snapshot, step=step, **kwargs)
        except Exception as err:
            # stats mutate under the condition's lock (stats() reads there);
            # the journal emission stays outside — fsync under a contended
            # lock would stall submit()/drain()
            with self._cond:
                self.failed_total += 1
            self._journal(
                "ckpt_end",
                path=path,
                step=step,
                blocking=False,
                status="failed",
                error=repr(err)[:200],
            )
            warnings.warn(
                f"async checkpoint write to '{path}' failed: {err!r} "
                "(the run continues; the next periodic checkpoint is the retry)",
                RuntimeWarning,
            )
            return
        now = time.time()
        with self._cond:
            if self.last_end_t is not None:
                self.last_interval_s = round(max(0.0, now - self.last_end_t), 3)
            self.last_end_t = now
            self.written_total += 1
            self.write_seconds_total += result["write_ms"] / 1e3
            self.last_write_ms = result["write_ms"]
            self.last_step = result["step"]
            self.last_path = result["path"]
        self._journal(
            "ckpt_end", blocking=False, status="ok", verified=True, queued_s=queued_s, **result
        )

    def _journal(self, kind: str, **fields: Any) -> None:
        if self._journal_fn is not None:
            self._journal_fn(kind, **fields)

    # -- lifecycle -----------------------------------------------------------
    @property
    def busy(self) -> bool:
        with self._cond:
            return bool(self._queue) or self._writing

    def drain(self, timeout: Optional[float] = 120.0) -> bool:
        """Block until every submitted snapshot is on disk (True) or the
        timeout passes (False) — the preemption path calls this so the
        emergency snapshot is durable before the process exits."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._queue or self._writing:
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return False
                self._cond.wait(timeout=min(1.0, remaining) if remaining is not None else 1.0)
        return True

    def close(self, timeout: Optional[float] = 120.0) -> None:
        self.drain(timeout=timeout)
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def stats(self) -> Dict[str, Any]:
        # one consistent snapshot: the worker publishes all write stats in a
        # single locked block, so written_total/last_* never mix two writes
        with self._cond:
            return {
                "written_total": self.written_total,
                "failed_total": self.failed_total,
                "write_seconds_total": round(self.write_seconds_total, 3),
                "last_write_ms": self.last_write_ms,
                "last_step": self.last_step,
                "last_path": self.last_path,
                "last_end_t": self.last_end_t,
                "last_interval_s": self.last_interval_s,
            }
