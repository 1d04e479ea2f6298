"""The seeded token env: a pure function of (seed, env index, actions taken)."""

import gymnasium as gym
import numpy as np

from sheeprl_tpu.envs.token import TokenEnv, episode_length, longest_episode, make_token_env, prompt_token

PARAMS = dict(vocab=16, episode_min=5, episode_max=24, prompt_share=0.25, reward_pct=25.0, first_episodes=(6, 14), stagger=2)


def _play(env, actions):
    rows = []
    obs, _ = env.reset()
    for a in actions:
        nxt, reward, done, truncated, _ = env.step(a)
        rows.append((int(obs["token"]), int(a), reward, done))
        obs = env.reset()[0] if done else nxt
    return rows


def test_spaces_are_discrete_over_the_held_ids():
    env = make_token_env(seed=3, base_seed=3, **PARAMS)
    assert isinstance(env.observation_space["token"], gym.spaces.Discrete) and env.observation_space["token"].n == 16
    assert isinstance(env.action_space, gym.spaces.Discrete) and env.action_space.n == 16


def test_the_same_actions_give_the_same_traffic_and_other_envs_another():
    actions = np.random.default_rng(0).integers(0, 16, 200)
    one = _play(TokenEnv(seed=7, base_seed=5, **PARAMS), actions)
    assert one == _play(TokenEnv(seed=7, base_seed=5, **PARAMS), actions)
    assert one != _play(TokenEnv(seed=8, base_seed=5, **PARAMS), actions)
    assert one != _play(TokenEnv(seed=8, base_seed=6, **PARAMS), actions)


def test_episodes_have_their_seeded_lengths_a_prompt_and_then_the_echo():
    env = TokenEnv(seed=6, base_seed=5, **PARAMS)  # env index 1
    actions = np.random.default_rng(1).integers(0, 16, 300)
    rows = _play(env, actions)
    ends = [i for i, row in enumerate(rows) if row[3]]
    lengths = np.diff([-1] + ends)
    want = [episode_length(5, 1, e, 5, 24, (6, 14), 2) for e in range(len(lengths))]
    assert list(lengths) == want and want[:2] == [8, 16] and all(5 <= n <= 24 for n in want[2:])
    start = 0
    for length in lengths:
        prompt = max(1, int(np.ceil(0.25 * length)))
        for position in range(prompt, length):  # past the prompt the env echoes the policy's last token
            assert rows[start + position][0] == rows[start + position - 1][1]
        start += length
    rewards = [row[2] for row in rows]
    assert set(rewards) <= {0.0, 1.0} and 0.1 < np.mean(rewards) < 0.45


def test_lengths_are_log_uniform_over_their_range():
    lengths = np.array([episode_length(11, 0, e, 128, 2048) for e in range(2000)])
    assert lengths.min() >= 128 and lengths.max() <= 2048
    assert abs(np.median(lengths) - np.sqrt(128 * 2048)) < 60  # the median of a log-uniform draw is the geometric mean
    assert 0 <= prompt_token(11, 0, 5, 16) < 16


def test_the_longest_episode_counts_the_stagger():
    assert longest_episode(24, (6, 14), 2, num_envs=8) == 28
    assert longest_episode(2048, (96, 384), 4, num_envs=32) == 2048


def test_the_vector_env_resets_in_the_same_step():
    from sheeprl_tpu.envs.env import vectorized_env

    envs = vectorized_env([lambda i=i: TokenEnv(seed=5 + i, base_seed=5, **PARAMS) for i in range(2)], sync=True)
    obs, _ = envs.reset(seed=5)
    assert obs["token"].shape == (2,) and obs["token"].dtype == np.int64
    dones = []
    for _ in range(20):
        obs, _, terminated, truncated, _ = envs.step(np.array([3, 4]))
        dones.append(terminated | truncated)
    dones = np.array(dones)
    # episodes of 6 and 14 tokens in env 0, of 8 and 16 in env 1 (the stagger)
    assert list(np.flatnonzero(dones[:, 0])) == [5, 19] and list(np.flatnonzero(dones[:, 1])) == [7]
    envs.close()
