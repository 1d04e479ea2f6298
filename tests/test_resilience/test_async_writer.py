"""Async off-critical-path checkpointing units (ISSUE 13 tentpole pillar 1):
state equality vs a synchronous save, journal protocol, snapshot isolation,
backpressure, failure containment and lock discipline."""

from __future__ import annotations

import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

import sheeprl_tpu.resilience.manifest as manifest_mod
from sheeprl_tpu.resilience.async_writer import AsyncCheckpointWriter, host_snapshot
from sheeprl_tpu.resilience.manifest import save_verified_checkpoint, verify_checkpoint
from sheeprl_tpu.utils.checkpoint import load_state


def _state(step: int):
    return {
        "agent": {"w": jnp.arange(16, dtype=jnp.float32) * step, "b": np.ones(4, np.float32)},
        "opt_state": [np.full((2, 2), step, np.float32)],
        "policy_step": step,
    }


def _tree_equal(a, b):
    import jax

    leaves_a, tree_a = jax.tree_util.tree_flatten(a)
    leaves_b, tree_b = jax.tree_util.tree_flatten(b)
    assert tree_a == tree_b
    for la, lb in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_async_saved_state_equals_synchronous_save_at_same_step(tmp_path):
    """Acceptance: the async-saved state is equal to a synchronous save of
    the same step (same pytree, same values, both manifest-verified)."""
    state = _state(64)
    sync_path = str(tmp_path / "sync" / "ckpt_64_0.ckpt")
    async_path = str(tmp_path / "async" / "ckpt_64_0.ckpt")
    save_verified_checkpoint(sync_path, state)
    writer = AsyncCheckpointWriter()
    writer.submit(async_path, state)
    writer.close()
    _tree_equal(load_state(sync_path), load_state(async_path))
    assert verify_checkpoint(sync_path, deep=True) == (True, "verified")
    assert verify_checkpoint(async_path, deep=True) == (True, "verified")


def test_journal_protocol_begin_then_end_with_duration_and_bytes(tmp_path):
    events = []
    writer = AsyncCheckpointWriter(journal_fn=lambda kind, **f: events.append({"event": kind, **f}))
    path = str(tmp_path / "ckpt_16_0.ckpt")
    writer.submit(path, _state(16))
    writer.close()
    kinds = [e["event"] for e in events]
    assert kinds == ["ckpt_begin", "ckpt_end"]
    begin, end = events
    assert begin["path"] == path and begin["step"] == 16 and begin["blocking"] is False
    assert end["status"] == "ok" and end["verified"] is True
    assert end["bytes"] == os.path.getsize(path)
    assert end["write_ms"] > 0
    stats = writer.stats()
    assert stats["written_total"] == 1 and stats["failed_total"] == 0
    assert stats["last_step"] == 16 and stats["last_path"] == path


def test_submit_returns_before_serialization_happens(tmp_path, monkeypatch):
    """The critical-path contract, deterministically: with the serializer
    artificially slowed to 0.3 s, submit must return in a fraction of that
    (the caller pays only snapshot + enqueue) and the file lands on drain."""
    real_save = manifest_mod.save_verified_checkpoint

    def slow_save(path, state, step=None):
        time.sleep(0.3)
        return real_save(path, state, step=step)

    monkeypatch.setattr(manifest_mod, "save_verified_checkpoint", slow_save)
    writer = AsyncCheckpointWriter()
    path = str(tmp_path / "ckpt_8_0.ckpt")
    t0 = time.perf_counter()
    writer.submit(path, _state(8))
    crit = time.perf_counter() - t0
    assert crit < 0.15, f"submit blocked for {crit:.3f}s — serialization on the critical path"
    assert writer.drain(timeout=30)
    writer.close()
    assert os.path.exists(path)


def test_snapshot_isolated_from_caller_mutation(tmp_path):
    """The truncated-flag surgery in CheckpointCallback is UNDONE right after
    submit, and replay slabs keep mutating — the snapshot must not alias."""
    buf = np.zeros(8, np.float32)
    state = {"rb": {"truncated": buf}, "policy_step": 4}
    snap = host_snapshot(state)
    buf[:] = 99.0
    state["rb"]["extra"] = "mutated-container"
    np.testing.assert_array_equal(snap["rb"]["truncated"], np.zeros(8, np.float32))
    assert "extra" not in snap["rb"]


def test_backpressure_bounds_pending_snapshots_and_all_land(tmp_path, monkeypatch):
    real_save = manifest_mod.save_verified_checkpoint

    def slow_save(path, state, step=None):
        time.sleep(0.05)
        return real_save(path, state, step=step)

    monkeypatch.setattr(manifest_mod, "save_verified_checkpoint", slow_save)
    writer = AsyncCheckpointWriter(max_pending=1)
    for step in (1, 2, 3):
        writer.submit(str(tmp_path / f"ckpt_{step}_0.ckpt"), _state(step))
    writer.close()
    assert writer.stats()["written_total"] == 3
    for step in (1, 2, 3):
        assert load_state(str(tmp_path / f"ckpt_{step}_0.ckpt"))["policy_step"] == step


def test_failed_write_journals_and_warns_but_never_raises(tmp_path, monkeypatch):
    def boom(path, state, step=None):
        raise OSError("disk full")

    monkeypatch.setattr(manifest_mod, "save_verified_checkpoint", boom)
    events = []
    writer = AsyncCheckpointWriter(journal_fn=lambda kind, **f: events.append({"event": kind, **f}))
    with pytest.warns(RuntimeWarning, match="disk full"):
        writer.submit(str(tmp_path / "ckpt_1_0.ckpt"), _state(1))
        writer.close()
    (end,) = [e for e in events if e["event"] == "ckpt_end"]
    assert end["status"] == "failed" and "disk full" in end["error"]
    assert writer.stats()["failed_total"] == 1 and writer.stats()["written_total"] == 0


def test_blocking_save_failure_journals_ckpt_end_and_counts(tmp_path, monkeypatch):
    """The blocking path mirrors the async failure contract: ckpt_begin is
    never left dangling, the failure counter moves — then the exception
    propagates (pre-resilience abort semantics)."""
    from sheeprl_tpu.resilience.monitor import ResilienceMonitor

    monitor = ResilienceMonitor(
        {"diagnostics": {"resilience": {"async_checkpoint": False, "preempt": {"enabled": False}}}}
    )
    events = []
    monitor.open(lambda kind, **f: events.append({"event": kind, **f}), None)

    def boom(path, state, step=None):
        raise OSError("disk full")

    monkeypatch.setattr(manifest_mod, "save_verified_checkpoint", boom)
    with pytest.raises(OSError, match="disk full"):
        monitor.save(str(tmp_path / "ckpt_1_0.ckpt"), _state(1))
    assert [e["event"] for e in events] == ["ckpt_begin", "ckpt_end"]
    assert events[-1]["status"] == "failed" and events[-1]["blocking"] is True
    assert monitor.snapshot()["counters"]["ckpt_failures_total"] == 1
    monitor.close()


def test_no_recent_ckpt_banner_shared_thresholds():
    from sheeprl_tpu.diagnostics.report import NO_RECENT_CKPT_FALLBACK_S, no_recent_ckpt_banner

    assert no_recent_ckpt_banner(None, 60.0) is None
    assert no_recent_ckpt_banner(100.0, 60.0) is None  # under 3 intervals
    assert "NO-RECENT-CKPT" in no_recent_ckpt_banner(200.0, 60.0)
    # no cadence yet (single checkpoint / endpoint without an interval):
    # the hard-ceiling fallback still fires — the stuck-after-one-checkpoint
    # run is exactly the case the banner exists for
    assert no_recent_ckpt_banner(NO_RECENT_CKPT_FALLBACK_S - 1, None) is None
    assert "no cadence" in no_recent_ckpt_banner(NO_RECENT_CKPT_FALLBACK_S + 1, None)


def test_write_stats_publish_under_the_cond_and_journal_outside_it(tmp_path):
    """LCK regression: the worker used to mutate written_total/last_* with no
    lock while stats() read them from the main thread, and a locked journal
    emission would stall submit()/drain() behind the checkpoint fsync.  The
    probe runs ON the worker thread: at emission time the condition's lock
    must not be owned by the emitter."""
    emissions = []

    def probing_journal(kind, **fields):
        # Condition._is_owned: does the CALLING thread hold the lock?
        assert not writer._cond._is_owned(), f"journal `{kind}` emitted under _cond"
        emissions.append(kind)

    writer = AsyncCheckpointWriter(journal_fn=probing_journal)
    for step in (8, 16):
        writer.submit(str(tmp_path / f"ckpt_{step}_0.ckpt"), _state(step))
    writer.close()
    assert emissions == ["ckpt_begin", "ckpt_end", "ckpt_begin", "ckpt_end"]
    stats = writer.stats()
    assert stats["written_total"] == 2 and stats["failed_total"] == 0
    assert stats["last_step"] == 16
