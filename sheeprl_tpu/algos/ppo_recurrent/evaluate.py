"""Recurrent-PPO evaluation entrypoint
(reference /root/reference/sheeprl/algos/ppo_recurrent/evaluate.py)."""

from __future__ import annotations

from typing import Any, Dict

import gymnasium as gym

from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent
from sheeprl_tpu.algos.ppo_recurrent.players import make_player
from sheeprl_tpu.algos.ppo_recurrent.utils import test
from sheeprl_tpu.envs.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.registry import register_evaluation


@register_evaluation(algorithms="ppo_recurrent")
def evaluate_ppo_recurrent(runtime, cfg, state: Dict[str, Any]) -> None:
    logger = get_logger(runtime, cfg)
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
    env = make_env(cfg, cfg.seed, 0, log_dir, "test")()
    observation_space = env.observation_space
    action_space = env.action_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    agent, params, _ = build_agent(runtime, actions_dim, is_continuous, cfg, observation_space, state["agent"])
    cumulative_rew = test(make_player(agent, cfg, greedy=True), params, env, cfg)
    logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, 0)
    logger.finalize()
