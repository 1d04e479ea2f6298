"""The client's side of the measurement: what every env of the benchmark keeps.

An env of the benchmark stamps ``time.time()`` at every ``step`` call it
receives, with the action it was given and a mark of its own (the index of
the newest frame, token or row it has emitted), into preallocated arrays that
are written to ``log_path`` every ``flush_every`` steps and at ``close``.
The end-to-end metrics are taken from these stamps (``window.py``) and a
family's comparison holds the program's replay path against the actions and
marks; neither knows which env wrote them.

A vector of envs writes one file each: the env of index 0 to ``log_path``
itself, env ``i`` to ``log_path.<i>`` (the envs may live in processes of their
own).  :func:`read_step_log` reads them all.
"""

from __future__ import annotations

import glob
import os
import re
import time
from typing import Dict, Optional

import numpy as np

_LOG_CAPACITY = 1 << 20


class StepLog:
    """One env's stamps.  ``stamp`` is called first thing in ``step``."""

    def __init__(self, log_path: Optional[str], index: int = 0, flush_every: int = 512):
        self.path = log_path if not log_path or int(index) == 0 else f"{log_path}.{int(index)}"
        self._flush_every = int(flush_every)
        self.times = np.zeros(_LOG_CAPACITY, np.float64)
        self.actions = np.full(_LOG_CAPACITY, -1, np.int16)
        self.marks = np.zeros(_LOG_CAPACITY, np.int64)
        self.n = 0

    def stamp(self, action, mark: int) -> None:
        now = time.time()
        if self.n >= _LOG_CAPACITY:
            return
        self.times[self.n] = now
        self.actions[self.n] = int(np.asarray(action).reshape(-1)[0])
        self.marks[self.n] = mark
        self.n += 1
        if self.path and self.n % self._flush_every == 0:
            self.flush()

    def flush(self) -> None:
        """Write the log so far; replace, never append, so a reader sees a whole file."""
        if not self.path:
            return
        tmp = self.path + ".part"
        with open(tmp, "wb") as fh:
            np.savez(fh, times=self.times[: self.n], actions=self.actions[: self.n], marks=self.marks[: self.n])
        os.replace(tmp, self.path)


def read_step_log(path: str) -> Dict[str, np.ndarray]:
    """Every env's stamps in the order of their times: ``times``, ``actions``,
    ``marks`` and ``env``, the index of the env that wrote each."""
    files = {0: path} if os.path.isfile(path) else {}
    for other in glob.glob(glob.escape(path) + ".*"):
        suffix = other[len(path) + 1:]
        if re.fullmatch(r"\d+", suffix):
            files[int(suffix)] = other
    if not files:
        raise FileNotFoundError(f"no step log at {path}")
    parts = []
    for index in sorted(files):
        with np.load(files[index]) as data:
            part = {k: np.array(data[k]) for k in ("times", "actions", "marks")}
        part["env"] = np.full(len(part["times"]), index, np.int64)
        parts.append(part)
    merged = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
    order = np.argsort(merged["times"], kind="stable")
    return {k: v[order] for k, v in merged.items()}
