"""A vector-observation env for a second family's cells: one new file.

Every observation is a pure function of ``(seed, env index, k)``, ``k`` being
the running index of the observations this env has emitted (resets
included), and carries ``k`` and the env's index in its first two numbers, so
a row of a training batch says which observation it claims to be.  Rewards
and episode ends are functions of the same three.  The client's side of the
measurement is the benchmark's own (``steplog.py``): one log a env, found by
the env's index.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import gymnasium as gym
import numpy as np

from benchmarks.chip.steplog import StepLog

STATE_DIM = 8


def state_of(seed: int, env: int, k: int) -> np.ndarray:
    """Observation ``k`` of env ``env``: its own index, the env's, then six seeded numbers in [0, 1)."""
    j = np.arange(STATE_DIM - 2, dtype=np.int64)
    mixed = (int(k) * 2654435761 + int(env) * 40503 + (j + 1) * 2246822519 + int(seed) * 3266489917) % 1000003
    return np.concatenate([[float(k), float(env)], mixed / 1000003.0]).astype(np.float32)


def reward_of(seed: int, env: int, k: int) -> float:
    """The reward that arrives with observation ``k`` when a step produced it."""
    return float((int(k) * 7 + int(env) * 3 + int(seed)) % 10 == 0)


def episode_length(episode_len: int, env: int) -> int:
    """Envs end their episodes at different steps, so a vector step rarely resets them all."""
    return int(episode_len) + 3 * int(env)


class SeqEnv(gym.Env):
    metadata: Dict[str, Any] = {"render_modes": []}

    def __init__(self, seed: int = 0, base_seed: int = 0, n_actions: int = 4, episode_len: int = 200,
                 step_ms: float = 0.0, log_path: Optional[str] = None, flush_every: int = 4096):
        # the loop seeds env i with seed + i; the traffic is a function of the run's seed and the env's index
        self.observation_space = gym.spaces.Dict({"state": gym.spaces.Box(-np.inf, np.inf, shape=(STATE_DIM,), dtype=np.float32)})
        self.action_space = gym.spaces.Discrete(int(n_actions))
        self._seed, self._index = int(base_seed), int(seed) - int(base_seed)
        self._length = episode_length(episode_len, self._index)
        self._step_s = max(0.0, float(step_ms)) / 1000.0
        self._k, self._left = -1, 0
        self.log = StepLog(log_path, index=self._index, flush_every=flush_every)

    def _emit(self) -> Dict[str, np.ndarray]:
        self._k += 1
        return {"state": state_of(self._seed, self._index, self._k)}

    def reset(self, *, seed: Optional[int] = None, options: Optional[dict] = None):
        self._left = self._length
        return self._emit(), {}

    def step(self, action) -> Tuple[Dict[str, np.ndarray], float, bool, bool, dict]:
        self.log.stamp(action, self._k)
        if self._step_s > 0.0:
            time.sleep(self._step_s)
        obs = self._emit()
        self._left -= 1
        return obs, reward_of(self._seed, self._index, self._k), self._left <= 0, False, {}

    def close(self) -> None:
        self.log.flush()


def make_seq_env(seed: int = 0, **params: Any) -> SeqEnv:
    """The ``_target_`` of ``hydra/env/seqprobe.yaml``."""
    return SeqEnv(seed=seed, **params)
