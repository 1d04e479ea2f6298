"""The token env of a sequence policy's cells: the program's own
(``sheeprl_tpu/envs/token.py``) with the client's side of the measurement
(``steplog.py``: one log an env, found by the env's index).

The traffic is a pure function of ``(seed, env index, actions taken)``, so
:func:`emitted` replays an env from its logged actions and says what every
step's row of a training batch has to hold.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

from benchmarks.chip.steplog import StepLog
from sheeprl_tpu.envs.token import TokenEnv


class TokenBenchEnv(TokenEnv):
    def __init__(self, log_path: Optional[str] = None, flush_every: int = 4096, **params: Any):
        super().__init__(**params)
        self.log = StepLog(log_path, index=self._index, flush_every=flush_every)

    def step(self, action):
        self.log.stamp(action, self.k)
        return super().step(action)

    def close(self) -> None:
        self.log.flush()


def make_token_bench_env(seed: int = 0, **params: Any) -> TokenBenchEnv:
    """The ``_target_`` of ``hydra/env/tokenbench.yaml``."""
    return TokenBenchEnv(seed=seed, **params)


def emitted(seed: int, index: int, actions: Sequence[int], env: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """What env ``index`` of a run emits when given ``actions`` one after the
    other under the vector env's same-step reset: for every step the token it
    was acted on, the reward and the done flag it answered with, and whether
    the episode before the step was over (``resets``)."""
    sim = TokenEnv(seed=seed + index, base_seed=seed, **{k: v for k, v in env.items()})
    n = len(actions)
    out = {"token": np.zeros(n, np.int64), "rewards": np.zeros(n, np.float32), "dones": np.zeros(n, np.float32),
           "resets": np.zeros(n, np.float32)}
    obs, _ = sim.reset()
    done = False
    for j, action in enumerate(actions):
        out["token"][j], out["resets"][j] = obs["token"], float(done)
        obs, reward, done, _, _ = sim.step(int(action))
        out["rewards"][j], out["dones"][j] = reward, float(done)
        if done:
            obs, _ = sim.reset()
    return out
