"""Auto-restart supervisor: ``sheeprl-supervise`` / ``tools/supervise.py``.

Wraps ``cli.run`` as a child process and owns the kill-to-recovered loop:

* a child that exits cleanly (0) ends supervision;
* any non-clean exit — crash, OOM-kill, SIGKILL from the scheduler, or the
  graceful-preemption code 75 — triggers a restart with capped exponential
  backoff (preempted exits skip the backoff: the emergency snapshot already
  landed and the pool wants the slot back *now*) under a total restart
  budget;
* every restart resumes from the newest checkpoint whose manifest verifies
  (``checkpoint.resume_from=<run dir>`` semantics — corrupt/partial files
  are skipped, never crashed on), or from scratch when none exists yet;
* each restart is journaled to ``<run dir>/supervisor.jsonl`` (``restart``
  events: attempt, rc, backoff, measured downtime, resume source) so
  ``tools/goodput_report.py`` reports time-to-recover measured on real
  kill/resume cycles rather than inferred from segment gaps.

The run name must be pinned for resumes to land in the same run dir; when the
caller does not pass ``run_name=...`` the supervisor pins the composed
(timestamped) one and says so.

``--kill-after-first-checkpoint`` is the chaos drill used by the e2e tests:
the supervisor SIGKILLs its *first* child
the moment a verified checkpoint exists, then lets the normal restart path
prove the whole cycle.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, List, Optional, Sequence

from sheeprl_tpu.resilience.monitor import RESTARTS_ENV_VAR
from sheeprl_tpu.resilience.preemption import PREEMPTED_EXIT_CODE

SUPERVISOR_JOURNAL = "supervisor.jsonl"


def backoff_delay(attempt: int, base_s: float, cap_s: float) -> float:
    """Capped exponential: ``base * 2**(attempt-1)``, clamped to ``cap``."""
    if attempt <= 0:
        return 0.0
    return float(min(cap_s, base_s * (2 ** (attempt - 1))))


def _child_env(restarts: int) -> dict:
    env = dict(os.environ)
    # the child must import sheeprl_tpu from the same checkout/venv we did
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    existing = env.get("PYTHONPATH", "")
    if pkg_root not in existing.split(os.pathsep):
        env["PYTHONPATH"] = pkg_root + (os.pathsep + existing if existing else "")
    env[RESTARTS_ENV_VAR] = str(int(restarts))
    return env


def _kill_after_checkpoint(proc: subprocess.Popen, run_dir: str, poll_s: float) -> None:
    """Drill thread: SIGKILL the child the instant a verified checkpoint
    exists under the run dir (simulates the scheduler's no-grace kill)."""
    from sheeprl_tpu.resilience.manifest import newest_verified_checkpoint

    while proc.poll() is None:
        best, _ = newest_verified_checkpoint(run_dir, deep=True)
        if best is not None:
            try:
                proc.send_signal(signal.SIGKILL)
            except OSError:  # pragma: no cover - child already gone
                pass
            return
        time.sleep(poll_s)


def supervise_command(
    argv_builder: Callable[[Optional[str]], List[str]],
    run_dir: str,
    max_restarts: int = 5,
    backoff_base_s: float = 1.0,
    backoff_max_s: float = 60.0,
    kill_after_first_checkpoint: bool = False,
    poll_s: float = 0.5,
    sleep_fn: Callable[[float], None] = time.sleep,
) -> int:
    """Core restart loop over an arbitrary child command.

    ``argv_builder(resume_path)`` produces the child argv for this attempt —
    the indirection keeps the loop unit-testable with stub children.
    Returns the exit code supervision ends with (0 = the run completed).
    """
    from sheeprl_tpu.diagnostics.journal import RunJournal
    from sheeprl_tpu.resilience.manifest import newest_verified_checkpoint

    os.makedirs(run_dir, exist_ok=True)
    journal = RunJournal(os.path.join(run_dir, SUPERVISOR_JOURNAL))
    restarts = 0
    last_rc: Optional[int] = None
    exit_t: Optional[float] = None
    backoff_s = 0.0
    drill_pending = bool(kill_after_first_checkpoint)
    try:
        while True:
            resume, _skipped = newest_verified_checkpoint(run_dir, deep=True)
            if restarts > 0:
                journal.write(
                    "restart",
                    attempt=restarts,
                    rc=last_rc,
                    preempted=last_rc == PREEMPTED_EXIT_CODE,
                    backoff_s=round(backoff_s, 3),
                    down_s=round(time.time() - exit_t, 3) if exit_t is not None else None,
                    resume_from=resume,
                )
                journal.sync()
            argv = argv_builder(resume)
            proc = subprocess.Popen(argv, env=_child_env(restarts))
            if drill_pending:
                drill_pending = False
                threading.Thread(
                    target=_kill_after_checkpoint,
                    args=(proc, run_dir, poll_s),
                    name="sheeprl-supervise-drill",
                    daemon=True,
                ).start()
            try:
                rc = proc.wait()
            except KeyboardInterrupt:
                proc.terminate()
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    proc.kill()
                raise
            exit_t = time.time()
            if rc == 0:
                return 0
            last_rc = rc
            if restarts >= max_restarts:
                journal.write("restart", attempt=restarts, rc=rc, gave_up=True)
                journal.sync()
                print(
                    f"sheeprl-supervise: restart budget exhausted after {restarts} "
                    f"restart(s); last exit code {rc}",
                    file=sys.stderr,
                )
                return rc
            restarts += 1
            # graceful preemption already saved its snapshot and freed the
            # slot on purpose — respawn immediately; crashes back off
            backoff_s = 0.0 if rc == PREEMPTED_EXIT_CODE else backoff_delay(
                restarts, backoff_base_s, backoff_max_s
            )
            print(
                f"sheeprl-supervise: child exited rc={rc}"
                f"{' (preempted)' if rc == PREEMPTED_EXIT_CODE else ''}; "
                f"restart {restarts}/{max_restarts} in {backoff_s:.1f}s",
                file=sys.stderr,
            )
            if backoff_s > 0:
                sleep_fn(backoff_s)
    finally:
        journal.close()


def supervise(
    overrides: Sequence[str],
    max_restarts: int = 5,
    backoff_base_s: float = 1.0,
    backoff_max_s: float = 60.0,
    kill_after_first_checkpoint: bool = False,
) -> int:
    """Supervise a ``cli.run`` training described by Hydra-style overrides."""
    from sheeprl_tpu.config import compose

    overrides = list(overrides)
    cfg = compose(overrides)
    if not any(str(o).startswith("run_name=") for o in overrides):
        # resumes must land in the SAME run dir: pin the composed
        # (timestamped) run name for every child
        overrides.append(f"run_name={cfg.run_name}")
        print(
            f"sheeprl-supervise: run_name not pinned; using '{cfg.run_name}' "
            "for every (re)start",
            file=sys.stderr,
        )
    run_dir = os.path.join("logs", "runs", str(cfg.root_dir), str(cfg.run_name))

    def argv_builder(resume: Optional[str]) -> List[str]:
        argv = [sys.executable, "-m", "sheeprl_tpu", *overrides]
        if resume is not None:
            argv.append(f"checkpoint.resume_from={resume}")
        return argv

    return supervise_command(
        argv_builder,
        run_dir,
        max_restarts=max_restarts,
        backoff_base_s=backoff_base_s,
        backoff_max_s=backoff_max_s,
        kill_after_first_checkpoint=kill_after_first_checkpoint,
    )


def main(args: Optional[Sequence[str]] = None) -> Any:
    parser = argparse.ArgumentParser(
        description="Auto-restart supervisor for sheeprl-tpu training runs "
        "(resumes from the newest verified checkpoint after any non-clean exit)."
    )
    parser.add_argument("--max-restarts", type=int, default=5, help="restart budget (default 5)")
    parser.add_argument(
        "--backoff", type=float, default=1.0, help="base backoff seconds (doubles per restart)"
    )
    parser.add_argument("--backoff-max", type=float, default=60.0, help="backoff cap in seconds")
    parser.add_argument(
        "--kill-after-first-checkpoint",
        action="store_true",
        help="chaos drill: SIGKILL the first child once a verified checkpoint "
        "exists, then recover through the normal restart path",
    )
    parser.add_argument(
        "overrides", nargs=argparse.REMAINDER, help="Hydra-style overrides passed to cli.run"
    )
    ns = parser.parse_args(list(args) if args is not None else None)
    overrides = [o for o in ns.overrides if o != "--"]
    return sys.exit(
        supervise(
            overrides,
            max_restarts=ns.max_restarts,
            backoff_base_s=ns.backoff,
            backoff_max_s=ns.backoff_max,
            kill_after_first_checkpoint=ns.kill_after_first_checkpoint,
        )
    )


if __name__ == "__main__":
    main()
