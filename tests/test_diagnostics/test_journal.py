"""Unit tests for the crash-safe run journal: write/flush durability,
crash-truncation recovery, strict-JSON sanitization and rank-0 gating."""

from __future__ import annotations

import json
import os

import pytest

from sheeprl_tpu.diagnostics import Diagnostics, build_diagnostics
from sheeprl_tpu.diagnostics.journal import RunJournal, find_journal, read_journal

DIAG_CFG = {
    "diagnostics": {
        "enabled": True,
        "journal": {"enabled": True, "fsync_every": 1},
        "sentinel": {"enabled": False},
        "trace": {"enabled": False},
    },
    "algo": {"name": "ppo"},
    "env": {"id": "discrete_dummy"},
    "seed": 0,
}


def test_write_is_durable_before_close(tmp_path):
    path = tmp_path / "journal.jsonl"
    journal = RunJournal(str(path))
    journal.write("metrics", step=16, metrics={"Rewards/rew_avg": 1.5})
    # flushed per event: the line must be on disk BEFORE close — that is the
    # whole crash-safety contract
    events = read_journal(str(path))
    assert len(events) == 1
    assert events[0]["event"] == "metrics"
    assert events[0]["step"] == 16
    assert events[0]["metrics"]["Rewards/rew_avg"] == 1.5
    journal.close()


def test_truncated_tail_is_skipped(tmp_path):
    path = tmp_path / "journal.jsonl"
    journal = RunJournal(str(path))
    for step in (1, 2, 3):
        journal.write("metrics", step=step, metrics={"Loss/policy_loss": 0.1 * step})
    journal.close()
    # simulate a SIGKILL mid-write: chop the last line in half
    raw = path.read_bytes()
    assert raw.endswith(b"\n")
    path.write_bytes(raw[: len(raw) - 17])
    events = read_journal(str(path))
    assert [e["step"] for e in events if e["event"] == "metrics"] == [1, 2]


def test_nonfinite_values_stay_strict_json(tmp_path):
    path = tmp_path / "journal.jsonl"
    journal = RunJournal(str(path))
    journal.write("metrics", step=1, metrics={"Loss/a": float("nan"), "Loss/b": float("inf"), "ok": 2.0})
    journal.close()

    def reject_constant(name):  # bare NaN/Infinity tokens must never appear
        raise AssertionError(f"non-strict JSON constant in journal: {name}")

    (line,) = [l for l in path.read_text().splitlines() if l]
    event = json.loads(line, parse_constant=reject_constant)
    assert event["metrics"]["Loss/a"] == "nan"
    assert event["metrics"]["Loss/b"] == "inf"
    assert event["metrics"]["ok"] == 2.0


def test_numpy_values_serialize(tmp_path):
    np = pytest.importorskip("numpy")
    path = tmp_path / "journal.jsonl"
    journal = RunJournal(str(path))
    journal.write("metrics", step=np.int64(4), metrics={"m": np.float32(0.25)})
    journal.close()
    (event,) = read_journal(str(path))
    assert event["step"] == 4
    assert event["metrics"]["m"] == 0.25


def test_facade_rank_gating(tmp_path):
    diag = build_diagnostics(DIAG_CFG)
    diag.open(str(tmp_path), rank_zero=False)
    diag.log_metrics(1, {"Rewards/rew_avg": 1.0})
    diag.on_checkpoint(1, "x.ckpt")
    diag.close()
    assert not (tmp_path / "journal.jsonl").exists(), "non-rank-0 host must not write a journal"


def test_facade_run_lifecycle_and_config_hash(tmp_path):
    import jax

    diag = build_diagnostics(DIAG_CFG)
    device = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    diag.open(str(tmp_path), rank_zero=True, device=device)
    diag.log_metrics(16, {"Rewards/rew_avg": 0.5})
    diag.on_checkpoint(16, "ckpt_16.ckpt")
    diag.close("completed")
    events = read_journal(str(tmp_path / "journal.jsonl"))
    kinds = [e["event"] for e in events]
    # the compile-cache directory in force (the suite's, tests/conftest.py) is
    # journaled right after run_start; telemetry (default-on since ISSUE 3)
    # and memory (default-on since ISSUE 4) each close with a cumulative
    # summary right before run_end
    assert kinds == [
        "run_start",
        "compilation_cache",
        "metrics",
        "checkpoint",
        "telemetry_summary",
        "memory_summary",
        "run_end",
    ]
    start = events[0]
    assert start["algo"] == "ppo" and start["env"] == "discrete_dummy"
    assert len(start["config_hash"]) == 16
    # what the mesh resolved to rides run_start, so a CPU run is never
    # mistaken for a chip run
    assert {k: start[k] for k in device} == device
    assert events[1]["dir"] == jax.config.jax_compilation_cache_dir
    assert events[-1]["status"] == "completed"
    # close is idempotent and open-once: no duplicate run_end
    diag.close("again")
    assert len(read_journal(str(tmp_path / "journal.jsonl"))) == len(kinds)


def test_disabled_facade_is_inert(tmp_path):
    diag = Diagnostics({"diagnostics": {"enabled": False}})
    diag.open(str(tmp_path))
    with diag.span("rollout"):
        pass
    diag.log_metrics(1, {"a": 1.0})
    diag.close()
    assert list(os.listdir(tmp_path)) == []


def test_concurrent_writers_never_interleave_lines(tmp_path):
    """The stall watchdog and the metrics-server HTTP threads write
    concurrently with the loop thread (ISSUE 8): every line must stay intact
    JSON and every event must land exactly once."""
    import threading

    path = tmp_path / "journal.jsonl"
    journal = RunJournal(str(path))
    n_threads, n_events = 4, 200

    def writer(thread_id):
        for i in range(n_events):
            journal.write("metrics", step=thread_id * n_events + i, metrics={"who": thread_id})
            if i % 50 == 0:
                journal.sync()  # the stall path syncs from its own thread

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    journal.close()
    raw_lines = [l for l in path.read_text().splitlines() if l]
    events = [json.loads(line) for line in raw_lines]  # every line parses whole
    assert len(events) == n_threads * n_events
    assert sorted(e["step"] for e in events) == list(range(n_threads * n_events))


def test_find_journal_walks_run_dirs(tmp_path):
    version = tmp_path / "run" / "version_0"
    version.mkdir(parents=True)
    journal = RunJournal(str(version / "journal.jsonl"))
    journal.write("run_start")
    journal.close()
    assert find_journal(str(tmp_path)) == str(version / "journal.jsonl")
    assert find_journal(str(version / "journal.jsonl")) == str(version / "journal.jsonl")
    assert find_journal(str(tmp_path / "nowhere")) is None
