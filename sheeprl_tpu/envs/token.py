"""A seeded token environment: documents a sequence policy writes a token at a time.

The observation is one token id, the action the next one, both ``Discrete``
over the ids the policy holds (``vocab``: a sliced vocabulary is a smaller
vocabulary, and the traffic draws its ids from the slice).  An episode is a
document of a seeded length, drawn log-uniformly from ``[episode_min,
episode_max]`` tokens: the env first emits a seeded prompt of
``prompt_share`` of that length, whatever the policy answers, and from then
on echoes the policy's last token back, as a sampler feeding a decoder does.
The reward is sparse and seeded (``reward_pct`` of the steps), a stand-in for
a programmatic reward; ``step_ms`` of wall clock a step stands for its cost.

Everything is a pure function of ``(seed, env index, actions taken)``:
:class:`TokenEnv` fed the same actions emits the same tokens, rewards and
episode ends, which is what lets an observer hold a training batch against
the env that produced it.  ``first_episodes`` fixes the lengths of the first
episodes (each env's staggered by ``stagger`` tokens times its index, so a
vector of envs does not end them in the same step).
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import gymnasium as gym
import numpy as np

_MIX = (2654435761, 40503, 2246822519, 3266489917)
_PRIME = 2147483629


def _hash(seed: int, env: int, k: int, salt: int) -> int:
    """A seeded integer in ``[0, _PRIME)`` for observation ``k`` of env ``env``."""
    x = (int(k) + 1) * _MIX[0] + (int(env) + 1) * _MIX[1] + (int(salt) + 1) * _MIX[2] + int(seed) * _MIX[3]
    x ^= x >> 15
    return (x * _MIX[2]) % _PRIME


def prompt_token(seed: int, env: int, k: int, vocab: int) -> int:
    return _hash(seed, env, k, 0) % int(vocab)


def reward_of(seed: int, env: int, k: int, reward_pct: float) -> float:
    """The reward that arrives with observation ``k`` when a step produced it."""
    return float(_hash(seed, env, k, 1) % 10000 < int(round(100 * reward_pct)))


def episode_length(seed: int, env: int, episode: int, episode_min: int, episode_max: int,
                   first_episodes: Sequence[int] = (), stagger: int = 0) -> int:
    """Tokens in episode ``episode`` (counted from 0) of env ``env``."""
    if episode < len(first_episodes):
        return int(first_episodes[episode]) + int(stagger) * int(env)
    u = _hash(seed, env, episode, 2) / _PRIME
    return int(round(math.exp(math.log(episode_min) + u * (math.log(episode_max) - math.log(episode_min)))))


def longest_episode(episode_max: int, first_episodes: Sequence[int], stagger: int, num_envs: int) -> int:
    """The longest episode any of ``num_envs`` envs can have: what a cache must hold."""
    return max([int(episode_max)] + [int(n) + int(stagger) * (int(num_envs) - 1) for n in first_episodes])


class TokenEnv(gym.Env):
    metadata: Dict[str, Any] = {"render_modes": []}

    def __init__(self, seed: int = 0, base_seed: int = 0, vocab: int = 256, episode_min: int = 16, episode_max: int = 64,
                 prompt_share: float = 0.25, reward_pct: float = 5.0, step_ms: float = 0.0,
                 first_episodes: Sequence[int] = (), stagger: int = 0):
        # the loop seeds env i with seed + i; the traffic is a function of the run's seed and the env's index
        self.vocab = int(vocab)
        self.observation_space = gym.spaces.Dict({"token": gym.spaces.Discrete(self.vocab)})
        self.action_space = gym.spaces.Discrete(self.vocab)
        self._seed, self._index = int(base_seed), int(seed) - int(base_seed)
        self._lengths = (int(episode_min), int(episode_max), tuple(int(n) for n in first_episodes), int(stagger))
        self._prompt_share, self._reward_pct = float(prompt_share), float(reward_pct)
        self._step_s = max(0.0, float(step_ms)) / 1000.0
        self.k = -1  # running index of the observations emitted, resets included
        self._episode, self._position, self._length, self._prompt = -1, 0, 0, 0

    def _emit(self, action: Optional[int]) -> Dict[str, np.ndarray]:
        self.k += 1
        if self._position < self._prompt or action is None:
            token = prompt_token(self._seed, self._index, self.k, self.vocab)
        else:
            token = int(action) % self.vocab  # past the prompt the policy reads its own last token
        self._position += 1
        return {"token": np.int64(token)}

    def reset(self, *, seed: Optional[int] = None, options: Optional[dict] = None):
        self._episode += 1
        self._length = episode_length(self._seed, self._index, self._episode, *self._lengths)
        self._prompt = max(1, int(math.ceil(self._prompt_share * self._length)))
        self._position = 0
        return self._emit(None), {}

    def step(self, action) -> Tuple[Dict[str, np.ndarray], float, bool, bool, dict]:
        if self._step_s > 0.0:
            time.sleep(self._step_s)
        done = self._position >= self._length  # this step's observation would be token number length + 1
        obs = self._emit(int(np.asarray(action).reshape(-1)[0]))
        return obs, reward_of(self._seed, self._index, self.k, self._reward_pct), done, False, {}


def make_token_env(seed: int = 0, **params: Any) -> TokenEnv:
    """The ``_target_`` of ``configs/env/token.yaml``."""
    return TokenEnv(seed=seed, **params)
