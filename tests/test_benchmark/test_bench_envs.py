"""The benchmark's env: the same seed gives the same traffic, every frame
differs from the last and says which it is, and the episode and reward
statistics are what the parameters ask for."""

import numpy as np
import pytest

from benchmarks.chip.envs import BenchEnv, EpisodeSchedule, frame_bank, frame_index, frame_of, reward_of, reward_table
from benchmarks.chip.steplog import read_step_log


def _roll(seed, steps, **params):
    env = BenchEnv(seed=seed, step_ms=0.0, **params)
    obs, _ = env.reset(seed=123)
    frames, rewards, dones = [obs["rgb"]], [0.0], [False]
    for i in range(steps):
        obs, reward, terminated, truncated, _ = env.step(i % env.action_space.n)
        assert not truncated
        frames.append(obs["rgb"]); rewards.append(reward); dones.append(terminated)
        if terminated:
            obs, _ = env.reset()
            frames.append(obs["rgb"]); rewards.append(0.0); dones.append(False)
    return np.stack(frames), np.array(rewards), np.array(dones)


@pytest.mark.parametrize("seed", [0, 7, 2_200_000_123])
def test_same_seed_same_traffic(seed):
    a, b = _roll(seed, 200, episode_min=20, episode_max=60), _roll(seed, 200, episode_min=20, episode_max=60)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_another_seed_other_traffic():
    a, b = _roll(1, 50), _roll(2, 50)
    assert not np.array_equal(a[0][1], b[0][1])


def test_frames_change_every_step_and_carry_their_index():
    frames, _, _ = _roll(5, 600, episode_min=50, episode_max=90)
    assert frames.dtype == np.uint8 and frames.shape[1:] == (3, 64, 64)
    assert np.array_equal(frame_index(frames), np.arange(len(frames)))
    assert all(not np.array_equal(frames[i], frames[i + 1]) for i in range(len(frames) - 1))
    bank = frame_bank(5)
    for k in (0, 1, 250, 251, 599):
        assert np.array_equal(frames[k], frame_of(bank, k))


def test_episode_lengths_come_from_the_range_and_the_schedule_knows_them():
    frames, _, dones = _roll(11, 2000, episode_min=30, episode_max=50, first_episodes=[7])
    finals = np.nonzero(dones)[0]
    schedule = EpisodeSchedule(11, 30, 50, first_episodes=[7])
    schedule.extend_to(len(frames))
    assert list(finals) == [k for k in schedule.finals if k < len(frames)]
    lengths = np.diff(np.concatenate([[-1], finals]))  - 1
    assert lengths[0] == 7
    assert lengths[1:].min() >= 30 and lengths[1:].max() <= 50 and len(set(lengths[1:])) > 5


def test_reward_is_sparse_seeded_and_a_function_of_the_index():
    _, rewards, dones = _roll(3, 5000, episode_min=100, episode_max=200, reward_pct=5.0)
    assert set(np.unique(rewards)) <= {0.0, 1.0}
    assert 0.02 < rewards.mean() < 0.08
    table = reward_table(3, 5.0)
    schedule = EpisodeSchedule(3, 100, 200)
    schedule.extend_to(len(rewards))
    resets = set(schedule.resets)
    for k in range(len(rewards)):
        assert rewards[k] == (0.0 if k in resets else reward_of(table, k))


def test_the_step_log_keeps_the_clients_clock(tmp_path):
    path = str(tmp_path / "log.npz")
    env = BenchEnv(seed=1, step_ms=0.0, n_actions=4, episode_min=5, episode_max=9, log_path=path, flush_every=4)
    env.reset()
    for i in range(10):
        _, _, done, _, _ = env.step(i % 4)
        if done:
            env.reset()
    assert len(read_step_log(path)["times"]) == 8  # flushed every 4 steps
    env.close()
    log = read_step_log(path)
    assert len(log["times"]) == 10 and np.all(np.diff(log["times"]) >= 0)
    assert list(log["actions"]) == [i % 4 for i in range(10)]
    assert np.all(np.diff(log["marks"]) >= 1) and set(log["env"]) == {0}


def test_spaces():
    env = BenchEnv(n_actions=17)
    assert env.action_space.n == 17 and env.observation_space["rgb"].shape == (3, 64, 64)
