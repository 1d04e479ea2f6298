"""The benchmark's own environment: the traffic generator of every cell.

One general generator, driven by the parameters of a cell's data file
(``workloads/<cell>.json`` -> ``env``): seeded 3x64x64 uint8 frames that
change every step, a seeded sparse reward, ``n_actions`` discrete actions,
episode lengths drawn from a seeded range, ``step_ms`` of wall clock a step.

Everything an observer needs to check what the program did with the traffic
is a pure function of ``(seed, k)``, ``k`` being the running index of the
frames the env has emitted (resets included): :func:`frame_of`,
:func:`reward_of` and :class:`EpisodeSchedule`.  Every frame carries ``k`` in
its first eight bytes, so a replayed row says which frame it claims to be.

The env also keeps the client's side of the measurement (``steplog.py``):
``time.time()`` at every ``step`` call, the action it was given and the index
of the newest frame.  The program reaches this module through
``hydra/env/chipbench.yaml``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Sequence, Tuple

import gymnasium as gym
import numpy as np

from benchmarks.chip.steplog import StepLog

FRAME_SHAPE = (3, 64, 64)
BANK = 251  # seeded base frames; prime, so the xor pattern below never lines up with it
LEVEL_BLOCK = 128  # frames that share a brightness level
LEVELS = 4093  # prime
REWARD_TABLE = 65521  # prime


class FrameBank:
    """The seeded material every frame of a run is derived from: ``BANK`` base
    frames and a brightness level (32..256 of 256) for every block of
    ``LEVEL_BLOCK`` frames, so that the sequences of a batch differ in scale as
    the scenes of a game do, and a loss taken over part of a batch is not the
    loss over all of it."""

    def __init__(self, seed: int):
        rng = np.random.Generator(np.random.PCG64(int(seed) % (1 << 63)))
        self.frames = rng.integers(0, 256, size=(BANK,) + FRAME_SHAPE, dtype=np.uint8)
        self.levels = rng.integers(32, 257, size=LEVELS).astype(np.uint16)


def frame_bank(seed: int) -> FrameBank:
    return FrameBank(seed)


def frame_of(bank: FrameBank, k: int) -> np.ndarray:
    """Frame ``k``: a base frame xor a byte that changes every ``BANK`` frames,
    scaled to its block's brightness, stamped with ``k`` (little endian) in
    its first eight bytes."""
    k = int(k)
    raw = bank.frames[k % BANK] ^ np.uint8((k // BANK) * 37 % 256)
    frame = ((raw.astype(np.uint16) * bank.levels[(k // LEVEL_BLOCK) % LEVELS]) >> 8).astype(np.uint8)
    frame.reshape(-1)[:8] = np.frombuffer(np.uint64(k).tobytes(), np.uint8)
    return frame


def frame_index(frames: np.ndarray) -> np.ndarray:
    """The index ``k`` stamped into uint8 frames of shape ``[..., 3, 64, 64]``."""
    flat = np.ascontiguousarray(frames.reshape(frames.shape[:-3] + (-1,))[..., :8])
    return flat.view(np.uint64)[..., 0].astype(np.int64)


def reward_table(seed: int, reward_pct: float) -> np.ndarray:
    rng = np.random.Generator(np.random.PCG64((int(seed) + 0x9E3779B9) % (1 << 63)))
    return (rng.random(REWARD_TABLE) < reward_pct / 100.0).astype(np.float32)


def reward_of(table: np.ndarray, k: int) -> float:
    """The reward that arrives with frame ``k`` when a step produced it."""
    return float(table[int(k) % REWARD_TABLE])


class EpisodeSchedule:
    """Episode lengths drawn from the seed, one after the other.  ``resets``
    holds the index of every frame a reset emitted and ``finals`` that of
    every episode's last frame, up to whatever index was asked for."""

    def __init__(self, seed: int, episode_min: int, episode_max: int, first_episodes: Sequence[int] = ()):
        self._rng = np.random.Generator(np.random.PCG64((int(seed) + 0x51ED27) % (1 << 63)))
        self._lo, self._hi = int(episode_min), int(episode_max)
        self._first = [int(n) for n in first_episodes]
        self.resets = [0]
        self.finals: list[int] = []

    def next_length(self) -> int:
        """``first_episodes`` fixes the lengths of the first episodes, so that
        the loop meets an episode's end inside its prefill and another just
        after training starts, and both reset paths are compiled before the
        window whatever the seed."""
        if self._first:
            return self._first.pop(0)
        return int(self._rng.integers(self._lo, self._hi + 1))

    def extend_to(self, k: int) -> None:
        while not self.finals or self.finals[-1] < k:
            final = self.resets[-1] + self.next_length()
            self.finals.append(final)
            self.resets.append(final + 1)


class BenchEnv(gym.Env):
    """See the module docstring.  ``step`` returns ``terminated`` on the last
    frame of an episode; nothing is ever truncated."""

    metadata: Dict[str, Any] = {"render_modes": []}

    def __init__(
        self,
        seed: int = 0,
        n_actions: int = 9,
        episode_min: int = 500,
        episode_max: int = 2000,
        first_episodes: Sequence[int] = (),
        step_ms: float = 1.0,
        reward_pct: float = 5.0,
        log_path: Optional[str] = None,
        flush_every: int = 512,
    ):
        self.observation_space = gym.spaces.Dict(
            {"rgb": gym.spaces.Box(0, 255, shape=FRAME_SHAPE, dtype=np.uint8)}
        )
        self.action_space = gym.spaces.Discrete(int(n_actions))
        self.reward_range = (0.0, 1.0)
        self._seed = int(seed)
        self._bank = frame_bank(self._seed)
        self._rewards = reward_table(self._seed, reward_pct)
        self._schedule = EpisodeSchedule(self._seed, episode_min, episode_max, first_episodes)
        self._step_s = max(0.0, float(step_ms)) / 1000.0
        self._k = -1  # index of the newest frame emitted
        self._left = 0  # steps left in the episode
        self.log = StepLog(log_path, flush_every=flush_every)

    def _emit(self) -> Dict[str, np.ndarray]:
        self._k += 1
        return {"rgb": frame_of(self._bank, self._k)}

    def reset(self, *, seed: Optional[int] = None, options: Optional[dict] = None):
        # the traffic comes from the constructor's seed alone: a reset never
        # reseeds, so an auto-reset and the loop's first reset(seed=) agree
        self._left = self._schedule.next_length()
        return self._emit(), {}

    def step(self, action) -> Tuple[Dict[str, np.ndarray], float, bool, bool, dict]:
        self.log.stamp(action, self._k)
        if self._step_s > 0.0:
            time.sleep(self._step_s)
        obs = self._emit()
        self._left -= 1
        return obs, reward_of(self._rewards, self._k), self._left <= 0, False, {}

    def close(self) -> None:
        self.log.flush()


def make_bench_env(seed: int = 0, **params: Any) -> BenchEnv:
    """The ``_target_`` of ``hydra/env/chipbench.yaml``."""
    return BenchEnv(seed=seed, **params)
