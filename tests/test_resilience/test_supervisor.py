"""Supervisor units (backoff, budget, restart journal, verified-resume
hand-off) with stub children, plus the supervised SIGKILL→auto-resume e2e
(ISSUE 13 tentpole pillar 4 + acceptance)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from sheeprl_tpu.diagnostics.journal import read_journal
from sheeprl_tpu.resilience.manifest import save_verified_checkpoint
from sheeprl_tpu.resilience.monitor import RESTARTS_ENV_VAR
from sheeprl_tpu.resilience.preemption import PREEMPTED_EXIT_CODE
from sheeprl_tpu.resilience.supervisor import (
    SUPERVISOR_JOURNAL,
    backoff_delay,
    supervise_command,
)

REPO_ROOT = Path(__file__).resolve().parents[2]

#: Stub child: exits with the rc stored in a countdown file (one line per
#: attempt), recording its argv and restart env var for the assertions.
_STUB = """
import json, os, sys
plan_path, log_path = sys.argv[1], sys.argv[2]
lines = open(plan_path).read().split()
attempt = int(lines[0]); rcs = lines[1:]
with open(plan_path, "w") as fp:
    fp.write(" ".join([str(attempt + 1)] + rcs))
with open(log_path, "a") as fp:
    fp.write(json.dumps({
        "attempt": attempt,
        "resume": sys.argv[3] if len(sys.argv) > 3 else None,
        "restarts_env": os.environ.get(%r),
    }) + "\\n")
sys.exit(int(rcs[min(attempt, len(rcs) - 1)]))
""" % (RESTARTS_ENV_VAR,)


def _stub_builder(tmp_path, rcs):
    plan = tmp_path / "plan.txt"
    plan.write_text(" ".join(["0"] + [str(rc) for rc in rcs]))
    log = tmp_path / "children.jsonl"

    def argv_builder(resume):
        argv = [sys.executable, "-c", _STUB, str(plan), str(log)]
        if resume is not None:
            argv.append(str(resume))
        return argv

    return argv_builder, log


def test_backoff_delay_caps_exponential_growth():
    assert [backoff_delay(a, 1.0, 60.0) for a in (1, 2, 3, 4, 5, 6, 7)] == [
        1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 60.0,
    ]
    assert backoff_delay(0, 1.0, 60.0) == 0.0


def test_supervise_restarts_until_clean_exit_and_journals_each(tmp_path):
    run_dir = tmp_path / "run"
    argv_builder, log = _stub_builder(tmp_path, [1, 1, 0])
    sleeps = []
    rc = supervise_command(
        argv_builder,
        str(run_dir),
        max_restarts=5,
        backoff_base_s=0.25,
        backoff_max_s=60.0,
        sleep_fn=sleeps.append,
    )
    assert rc == 0
    assert sleeps == [0.25, 0.5]  # capped exponential per consecutive failure
    children = [json.loads(line) for line in log.read_text().splitlines()]
    assert [c["attempt"] for c in children] == [0, 1, 2]
    # the restart counter is handed to every child for sheeprl_restarts_total
    assert [c["restarts_env"] for c in children] == ["0", "1", "2"]
    events = read_journal(str(run_dir / SUPERVISOR_JOURNAL))
    restarts = [e for e in events if e["event"] == "restart"]
    assert [e["attempt"] for e in restarts] == [1, 2]
    assert all(e["rc"] == 1 and not e["preempted"] for e in restarts)
    assert [e["backoff_s"] for e in restarts] == [0.25, 0.5]
    assert all(isinstance(e["down_s"], (int, float)) for e in restarts)


def test_supervise_budget_exhausted_returns_last_rc_and_journals_give_up(tmp_path):
    run_dir = tmp_path / "run"
    argv_builder, _ = _stub_builder(tmp_path, [7, 7, 7, 7])
    rc = supervise_command(
        argv_builder, str(run_dir), max_restarts=2, backoff_base_s=0.0, sleep_fn=lambda s: None
    )
    assert rc == 7
    events = read_journal(str(run_dir / SUPERVISOR_JOURNAL))
    restarts = [e for e in events if e["event"] == "restart"]
    assert [e.get("gave_up") for e in restarts] == [None, None, True]


def test_supervise_preempted_child_respawns_without_backoff(tmp_path):
    run_dir = tmp_path / "run"
    argv_builder, _ = _stub_builder(tmp_path, [PREEMPTED_EXIT_CODE, 0])
    sleeps = []
    rc = supervise_command(
        argv_builder, str(run_dir), max_restarts=5, backoff_base_s=5.0, sleep_fn=sleeps.append
    )
    assert rc == 0
    assert sleeps == []  # preemption = restart immediately
    events = read_journal(str(run_dir / SUPERVISOR_JOURNAL))
    (restart,) = [e for e in events if e["event"] == "restart"]
    assert restart["preempted"] is True and restart["rc"] == PREEMPTED_EXIT_CODE


def test_supervise_hands_newest_verified_checkpoint_to_restarted_child(tmp_path):
    run_dir = tmp_path / "run"
    ckpt_dir = run_dir / "version_0" / "checkpoint"
    ckpt_dir.mkdir(parents=True)
    good = str(ckpt_dir / "ckpt_32_0.ckpt")
    save_verified_checkpoint(good, {"agent": {"w": np.ones(2, np.float32)}, "policy_step": 32})
    (ckpt_dir / "ckpt_48_0.ckpt").write_bytes(b"corrupt newest")
    argv_builder, log = _stub_builder(tmp_path, [1, 0])
    rc = supervise_command(
        argv_builder, str(run_dir), max_restarts=2, backoff_base_s=0.0, sleep_fn=lambda s: None
    )
    assert rc == 0
    children = [json.loads(line) for line in log.read_text().splitlines()]
    # both the first spawn and the restart resume from the newest VERIFIED
    # checkpoint, skipping the planted corrupt newest
    assert [c["resume"] for c in children] == [good, good]
    events = read_journal(str(run_dir / SUPERVISOR_JOURNAL))
    (restart,) = [e for e in events if e["event"] == "restart"]
    assert restart["resume_from"] == good


#: Stub child of the kill drill: the first attempt leaves a verified checkpoint
#: and hangs; a restarted one, handed a checkpoint to resume from, completes.
_CKPT_STUB = """
import sys, time
sys.path.insert(0, %r)
if len(sys.argv) > 2:
    sys.exit(0)
import numpy as np
from sheeprl_tpu.resilience.manifest import save_verified_checkpoint
save_verified_checkpoint(sys.argv[1], {"agent": {"w": np.ones(2, np.float32)}, "policy_step": 16})
time.sleep(300)
""" % (str(REPO_ROOT),)


def test_supervise_kill_drill_kills_the_first_child_once_and_the_second_resumes(tmp_path):
    """``kill_after_first_checkpoint`` with a stub child (the slow e2e below drives a
    real run): SIGKILL as soon as a verified checkpoint exists, one restart, which is
    handed that checkpoint and is left alone."""
    run_dir = tmp_path / "run"
    ckpt = run_dir / "version_0" / "checkpoint" / "ckpt_16_0.ckpt"
    ckpt.parent.mkdir(parents=True)

    def argv_builder(resume):
        return [sys.executable, "-c", _CKPT_STUB, str(ckpt)] + ([str(resume)] if resume else [])

    rc = supervise_command(
        argv_builder,
        str(run_dir),
        max_restarts=2,
        backoff_base_s=0.0,
        kill_after_first_checkpoint=True,
        poll_s=0.05,
        sleep_fn=lambda s: None,
    )
    assert rc == 0
    events = read_journal(str(run_dir / SUPERVISOR_JOURNAL))
    (restart,) = [e for e in events if e["event"] == "restart"]
    assert restart["rc"] == -9 and not restart["preempted"]  # SIGKILL
    assert restart["resume_from"] == str(ckpt)


@pytest.mark.slow
def test_supervised_sigkill_auto_resume_e2e_with_goodput_report(tmp_path):
    """Acceptance: a supervised training run SIGKILLed mid-training (the
    --kill-after-first-checkpoint drill) auto-restarts, resumes from the
    newest verified checkpoint, completes, and ``tools/goodput_report.py``
    reports the KILLED segment, a finite time-to-recover, and the
    supervisor's measured restart."""
    overrides = [
        "exp=ppo",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.num_envs=2",
        "env.capture_video=False",
        "buffer.memmap=False",
        "metric.log_level=1",
        "metric.log_every=1",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=4",
        "algo.update_epochs=1",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
        "algo.run_test=False",
        "run_name=sup_e2e",
        "algo.total_steps=512",
        "checkpoint.every=16",
        "checkpoint.save_last=False",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            str(REPO_ROOT / "tools" / "supervise.py"),
            "--max-restarts",
            "2",
            "--backoff",
            "0.5",
            "--kill-after-first-checkpoint",
            *overrides,
        ],
        cwd=os.getcwd(),  # tmp dir from the autouse fixture
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    run_dir = Path("logs") / "runs" / "ppo" / "discrete_dummy" / "sup_e2e"
    report = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "goodput_report.py"), str(run_dir), "--json"],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert report.returncode == 0, report.stderr[-2000:]
    (analysis,) = json.loads(report.stdout).values()
    labels = [s["label"] for s in analysis["segments"]]
    assert labels == ["KILLED", "completed"], analysis
    assert analysis["time_to_recover_s"] is not None and analysis["time_to_recover_s"] >= 0
    supervisor = analysis["supervisor"]
    assert supervisor["restarts"] == 1 and not supervisor["gave_up"]
    assert supervisor["measured_down_s"] is not None
    (restart,) = supervisor["events"]
    assert restart["rc"] == -9  # SIGKILL
    assert restart["resume_from"] and restart["resume_from"].endswith(".ckpt")
    # the human view carries the supervisor line and the measured downtime
    pretty = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "goodput_report.py"), str(run_dir)],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert pretty.returncode == 0
    assert "supervisor: 1 restart(s)" in pretty.stdout
    assert "measured downtime" in pretty.stdout
