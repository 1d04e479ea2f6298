"""A2C training loop — TPU-native re-design of
/root/reference/sheeprl/algos/a2c/a2c.py:28-440.

The reference takes ONE optimizer step per iteration, accumulating gradients
over minibatches with ``no_backward_sync`` and calling backward only at the
end (a2c.py:60-96).  Accumulated minibatch gradients with sum/mean reduction
are mathematically the whole-batch gradient, so here the update is a single
jitted step over the full local rollout — one XLA graph, batched MXU matmuls,
``pmean`` across the mesh replacing the DDP all-reduce.
"""

from __future__ import annotations

import os
from typing import Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.algos.a2c.agent import build_agent
from sheeprl_tpu.algos.a2c.loss import policy_loss, value_loss
from sheeprl_tpu.algos.a2c.utils import AGGREGATOR_KEYS, MODELS_TO_REGISTER, prepare_obs, test  # noqa: F401
from sheeprl_tpu.data.slab import step_slab
from sheeprl_tpu.envs.player import fetch_values, obs_sharding
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.envs.env import make_env, make_env_fns, pipelined_vector_env
from sheeprl_tpu.ops.numerics import gae
from sheeprl_tpu.parallel.precision import cast_floating, compute_dtype_of
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import get_diagnostics, save_configs


def make_train_step(agent, optimizer, cfg, mesh):
    """One whole-batch gradient step, data-parallel over the mesh.

    Returns metrics ``[pg_loss, v_loss, grad_norm, nonfinite_steps]``; under
    ``diagnostics.sentinel.policy=skip_update`` a non-finite update is
    discarded in-graph (params/opt state keep their pre-step values).  With
    ``diagnostics.health`` on, a learn-health stats dict (grad/update/param
    norms, update/weight ratio, dead-unit fraction, value EV) rides the same
    output fetch; the global grad norm is computed once there and shared
    with the sentinel's finiteness check.
    """
    from sheeprl_tpu.diagnostics.health import explained_variance, health_spec, health_stats
    from sheeprl_tpu.diagnostics.sentinel import finite_flag, select_finite, sentinel_spec

    sentinel = sentinel_spec(cfg)
    health = health_spec(cfg)
    world = mesh.devices.size
    distributed = world > 1
    cdt = compute_dtype_of(cfg)

    def loss_fn(params, batch):
        _, logprobs, _, values = agent.apply(
            cast_floating(params, cdt), cast_floating(batch["obs"], cdt), actions=batch["actions"]
        )
        values = values.astype(jnp.float32)
        advantages = batch["advantages"]
        if cfg.algo.get("normalize_advantages", False):
            mu, std = advantages.mean(), advantages.std()
            if distributed:
                mu, std = jax.lax.pmean(mu, "data"), jax.lax.pmean(std, "data")
            advantages = (advantages - mu) / (std + 1e-8)
        pg = policy_loss(logprobs, advantages, cfg.algo.loss_reduction)
        vl = value_loss(values, batch["returns"], cfg.algo.loss_reduction)
        return pg + cfg.algo.vf_coef * vl, (pg, vl)

    def update(params, opt_state, data):
        grads, aux = jax.grad(loss_fn, has_aux=True)(params, data)
        if distributed:
            grads = jax.lax.pmean(grads, "data")
            aux = jax.lax.pmean(aux, "data")
        updates, new_opt_state = optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        # one NaN/Inf leaf poisons the global norm: a single scalar health
        # flag — computed once by health_stats when the health layer is on
        if health.enabled:
            hstats = health_stats(
                grads, updates, params, per_module=health.per_module, dead_eps=health.dead_eps
            )
            gnorm = hstats["grad_norm"]
            # GAE's returns = advantages + values, so the logged rollout
            # values are recoverable without threading a new batch key
            ev = explained_variance(data["returns"] - data["advantages"], data["returns"])
            if distributed:
                ev = jax.lax.pmean(ev, "data")
            hstats["value_ev"] = ev
        else:
            hstats = {}
            gnorm = optax.global_norm(grads)
        finite = finite_flag(gnorm, *aux)
        if sentinel.skip_update:
            params = select_finite(finite, new_params, params)
            opt_state = select_finite(finite, new_opt_state, opt_state)
        else:
            params, opt_state = new_params, new_opt_state
        return params, opt_state, jnp.stack([*aux, gnorm, 1.0 - finite.astype(jnp.float32)]), hstats

    if distributed:
        from jax import shard_map

        def sharded(params, opt_state, data):
            return shard_map(
                update,
                mesh=mesh,
                in_specs=(P(), P(), P("data")),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )(params, opt_state, data)

        return jax.jit(sharded, donate_argnums=(0, 1))
    return jax.jit(update, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg):
    world_size = runtime.world_size
    num_envs = cfg.env.num_envs
    rollout_steps = cfg.algo.rollout_steps
    total_local = rollout_steps * num_envs
    if total_local % world_size != 0:
        raise ValueError(
            f"rollout_steps*num_envs ({total_local}) must be divisible by the number of devices ({world_size})"
        )

    rng_key = runtime.seed_everything(cfg.seed)
    logger = get_logger(runtime, cfg)
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name)
    if runtime.is_global_zero:
        save_configs(cfg, log_dir)
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    diag = get_diagnostics(runtime, cfg, log_dir)
    aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)
    if cfg.metric.log_level == 0:
        aggregator.disabled = True
    timer.disabled = cfg.metric.log_level == 0 or cfg.metric.disable_timer

    envs = pipelined_vector_env(cfg, make_env_fns(cfg, log_dir, "train"))
    observation_space = envs.single_observation_space
    action_space = envs.single_action_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    mlp_keys = cfg.algo.mlp_keys.encoder
    obs_keys = list(mlp_keys)
    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )

    state = runtime.load(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None
    agent, params, _ = build_agent(
        runtime, actions_dim, is_continuous, cfg, observation_space, state["agent"] if state else None
    )
    params = cast_floating(params, runtime.param_dtype)
    base_opt = instantiate(cfg.algo.optimizer)
    chain = []
    if cfg.algo.max_grad_norm and cfg.algo.max_grad_norm > 0:
        chain.append(optax.clip_by_global_norm(cfg.algo.max_grad_norm))
    chain.append(base_opt)
    optimizer = optax.chain(*chain)
    opt_state = optimizer.init(params)
    if state and "opt_state" in state:
        opt_state = jax.tree_util.tree_map(
            lambda ref, saved: jnp.asarray(saved, dtype=getattr(ref, "dtype", None)),
            opt_state,
            state["opt_state"],
        )

    from sheeprl_tpu.parallel.mesh import batch_sharding, replicated_sharding

    if world_size > 1:
        params = jax.device_put(params, replicated_sharding(runtime.mesh))
        opt_state = jax.device_put(opt_state, replicated_sharding(runtime.mesh))
        data_sharding = batch_sharding(runtime.mesh)
    else:
        data_sharding = None

    # telemetry instrumentation: watchdog + MFU FLOPs on the train step,
    # signature watch on the rollout policy (no shape-change injection here:
    # A2C's update consumes the whole batch, padding would alter the gradient)
    train_step = diag.instrument(
        "train_step",
        make_train_step(agent, optimizer, cfg, runtime.mesh),
        kind="train",
        donate_argnums=(0, 1),  # params, opt_state — audited at first dispatch
    )
    diag.register_footprint("params", params)
    diag.register_footprint("opt_state", opt_state)

    @jax.jit
    def policy_step(params, obs, key):
        actions, logprobs, _, values = agent.apply(params, obs, key=key)
        return actions, logprobs, values

    policy_step = diag.instrument("policy_step", policy_step, kind="rollout")
    # one staged h2d + one blocking fetch per vector step (see ppo.py)
    stage_sharding = obs_sharding(runtime.mesh if world_size > 1 else None)

    @jax.jit
    def value_step(params, obs):
        return agent.apply(params, obs, method="get_values")

    @jax.jit
    def gae_step(params, last_obs, rewards, values, dones):
        next_value = agent.apply(params, last_obs, method="get_values")
        return gae(rewards, values, dones, next_value, rollout_steps, cfg.algo.gamma, cfg.algo.gae_lambda)

    rb = ReplayBuffer(
        cfg.buffer.size,
        num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer"),
        obs_keys=obs_keys,
    )
    diag.track_buffer("replay", rb)

    start_iter = (state["iter_num"] if state else 0) + 1
    policy_step_count = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    policy_steps_per_iter = int(num_envs * rollout_steps)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1

    obs, _ = envs.reset(seed=cfg.seed)

    for iter_num in range(start_iter, total_iters + 1):
        with timer("Time/env_interaction_time"), diag.span("rollout"):
            for _ in range(rollout_steps):
                policy_step_count += num_envs
                diag.note_env_steps(num_envs)
                rng_key, step_key = jax.random.split(rng_key)
                torch_obs = prepare_obs(obs, mlp_keys=mlp_keys, num_envs=num_envs, sharding=stage_sharding)
                actions, logprobs, values = policy_step(params, torch_obs, step_key)
                actions_np, values_np = fetch_values(actions, values)
                if is_continuous:
                    env_actions = actions_np.reshape(num_envs, -1)
                elif is_multidiscrete:
                    env_actions = actions_np.astype(np.int64)
                else:
                    env_actions = actions_np[:, 0].astype(np.int64)

                # split-phase: env workers step while the host copies the
                # policy outputs + current obs into the step record (see
                # ppo.py — trajectories are identical to the serialized order)
                with diag.span("env_step_async"):
                    envs.step_async(env_actions)
                step_data: Dict[str, np.ndarray] = step_slab(
                    num_envs,
                    {**{k: obs[k] for k in obs_keys}, "actions": actions_np, "values": values_np},
                )
                with diag.span("env_wait"):
                    next_obs, rewards, terminated, truncated, info = envs.step_wait()
                dones = np.logical_or(terminated, truncated).reshape(num_envs, 1).astype(np.float32)
                rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, 1)
                if cfg.env.clip_rewards:
                    rewards = np.tanh(rewards)

                if "final_obs" in info and np.any(truncated):
                    final_obs = info["final_obs"]
                    trunc_idx = np.nonzero(truncated)[0]
                    stacked = {k: np.stack([np.asarray(final_obs[i][k]) for i in trunc_idx]) for k in obs_keys}
                    t_obs = prepare_obs(stacked, mlp_keys=mlp_keys, num_envs=len(trunc_idx))
                    vals = np.asarray(value_step(params, t_obs))
                    rewards[trunc_idx] += cfg.algo.gamma * vals.reshape(-1, 1)

                step_data.update(step_slab(num_envs, {"rewards": rewards, "dones": dones}))
                rb.add(step_data, validate_args=cfg.buffer.validate_args)

                if "final_info" in info and "episode" in info["final_info"]:
                    ep = info["final_info"]["episode"]
                    mask = ep.get("_r", info["final_info"].get("_episode"))
                    if mask is not None and np.any(mask):
                        for r, l in zip(ep["r"][mask], ep["l"][mask]):
                            aggregator.update("Rewards/rew_avg", float(r))
                            aggregator.update("Game/ep_len_avg", float(l))

                obs = next_obs

        local = {k: np.asarray(rb[k][:rollout_steps]) for k in rb.buffer.keys()}
        torch_last_obs = prepare_obs(obs, mlp_keys=mlp_keys, num_envs=num_envs)
        returns, advantages = gae_step(
            params,
            torch_last_obs,
            jnp.asarray(local["rewards"]),
            jnp.asarray(local["values"]),
            jnp.asarray(local["dones"]),
        )
        local["returns"] = np.asarray(returns)
        local["advantages"] = np.asarray(advantages)

        flat = {
            "obs": {k: local[k].reshape(total_local, *local[k].shape[2:]) for k in obs_keys},
            "actions": local["actions"].reshape(total_local, -1),
            "returns": local["returns"].reshape(total_local, -1),
            "advantages": local["advantages"].reshape(total_local, -1),
        }
        device_data = jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), data_sharding) if data_sharding else jnp.asarray(x),
            flat,
        )
        device_data = diag.maybe_inject_nan(iter_num, device_data)

        with timer("Time/train_time"), diag.span("train"):
            params, opt_state, losses, health = train_step(params, opt_state, device_data)
            # one blocking d2h for metrics + health stats together
            losses, health_host = fetch_values(losses, health)

        diag.on_health(policy_step_count, health_host)
        aggregator.update("Loss/policy_loss", float(losses[0]))
        aggregator.update("Loss/value_loss", float(losses[1]))
        aggregator.update("Grads/global_norm", float(losses[2]))
        diag.on_update(
            policy_step_count,
            {
                "Loss/policy_loss": float(losses[0]),
                "Loss/value_loss": float(losses[1]),
                "Grads/global_norm": float(losses[2]),
            },
            nonfinite=float(losses[3]),
        )

        if policy_step_count - last_log >= cfg.metric.log_every or iter_num == total_iters or cfg.dry_run:
            metrics = aggregator.compute()
            timers = timer.compute()
            if timers.get("Time/env_interaction_time", 0) > 0:
                metrics["Time/sps_env_interaction"] = (
                    (policy_step_count - last_log) / timers["Time/env_interaction_time"]
                )
            if timers.get("Time/train_time", 0) > 0:
                metrics["Time/sps_train"] = iter_num / timers["Time/train_time"]
            if runtime.is_global_zero:
                logger.log_metrics(metrics, policy_step_count)
            aggregator.reset()
            timer.reset()
            last_log = policy_step_count

        # a pending preemption (signal or drill) forces the branch: the save
        # below IS the emergency snapshot (howto/resilience.md)
        preempt_now = diag.preempt_due(iter_num)
        if (
            (cfg.checkpoint.every > 0 and policy_step_count - last_checkpoint >= cfg.checkpoint.every)
            or cfg.dry_run
            or preempt_now
            or (iter_num == total_iters and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step_count
            ckpt_state = {
                "agent": jax.tree_util.tree_map(np.asarray, params),
                "opt_state": jax.tree_util.tree_map(np.asarray, opt_state),
                "iter_num": iter_num,
                "policy_step": policy_step_count,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
            }
            ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step_count}_0.ckpt")
            with diag.span("checkpoint"):
                runtime.call("on_checkpoint_coupled", ckpt_path=ckpt_path, state=ckpt_state, replay_buffer=None)
            diag.on_checkpoint(policy_step_count, ckpt_path)
            if preempt_now:
                envs.close()
                diag.on_preempted(policy_step_count, iter_num, ckpt_path)

    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        test_env = make_env(cfg, cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
        cumulative_rew = test(agent.apply, params, test_env, runtime, cfg, log_dir)
        logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, policy_step_count)
    if cfg.model_manager.disabled is False and runtime.is_global_zero:  # pragma: no cover
        from sheeprl_tpu.utils.mlflow import log_models

        log_models(cfg, {"agent": params}, log_dir)
    logger.finalize()
    diag.close("completed")
