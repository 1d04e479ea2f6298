"""Recurrent-PPO helper surface (reference /root/reference/sheeprl/algos/ppo_recurrent/utils.py)."""

from __future__ import annotations

from typing import Dict, Sequence

import gymnasium as gym
import jax
import numpy as np

from sheeprl_tpu.algos.ppo.utils import prepare_obs as _ppo_prepare_obs

AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/entropy_loss",
}
MODELS_TO_REGISTER = {"agent"}


def prepare_obs(
    obs: Dict[str, np.ndarray], *, cnn_keys: Sequence[str] = (), mlp_keys: Sequence[str] = (), num_envs: int = 1
) -> Dict[str, jax.Array]:
    """Like PPO's but with a leading sequence axis of 1: ``[1, N, ...]``."""
    out = _ppo_prepare_obs(obs, cnn_keys=cnn_keys, mlp_keys=mlp_keys, num_envs=num_envs)
    return {k: v[None] for k, v in out.items()}


class KeyStream:
    """The loop's random stream, shared with its player: every ``next()`` splits one key off."""

    def __init__(self, key: jax.Array):
        self.key = key

    def next(self) -> jax.Array:
        self.key, out = jax.random.split(self.key)
        return out


def test(player, params, env, cfg) -> float:
    """One greedy episode through the player, at one env (reference utils.py:19-66)."""
    player.start(None, KeyStream(jax.random.PRNGKey(cfg.seed or 0)), num_envs=1, rollout_steps=1, seq_len=1)
    done = False
    cumulative_rew = 0.0
    obs, _ = env.reset(seed=cfg.seed)
    is_first = np.zeros((1, 1), np.float32)
    while not done:
        player.begin_step(is_first)
        actions, _ = player.fetch(player.act(params, player.stage(obs, is_first)))
        if isinstance(env.action_space, gym.spaces.Box):
            env_actions = actions.reshape(env.action_space.shape)
        elif isinstance(env.action_space, gym.spaces.Discrete):
            env_actions = int(actions[0, 0])
        else:
            env_actions = actions[0].astype(np.int64)
        obs, reward, terminated, truncated, _ = env.step(env_actions)
        done = bool(terminated or truncated)
        cumulative_rew += float(reward)
        if cfg.dry_run:
            done = True
    env.close()
    return cumulative_rew
