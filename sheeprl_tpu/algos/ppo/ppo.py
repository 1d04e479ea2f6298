"""PPO training loop — TPU-native re-design of
/root/reference/sheeprl/algos/ppo/ppo.py:30-453.

Shape of the redesign (SURVEY §7):
- The reference runs one process per device (Fabric DDP) with a Python
  minibatch loop and per-minibatch gradient all-reduce.  Here a single
  controller drives every chip: the **whole update phase** (epochs ×
  minibatches) is one jitted ``lax.scan`` graph, data-parallel over the mesh
  via ``shard_map`` with an in-graph ``pmean`` on gradients — the TPU ICI
  equivalent of DDP's NCCL all-reduce.
- Rollouts run on the host (gymnasium vector envs); the policy forward per env
  step is one small jit; observations transfer uint8 and are normalized on
  device.
- GAE is a reverse ``lax.scan`` (ops/numerics.py) instead of the reference's
  reversed Python loop (utils/utils.py:63-103).
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

from sheeprl_tpu.algos.ppo.agent import build_agent
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import AGGREGATOR_KEYS, MODELS_TO_REGISTER, prepare_obs, test  # noqa: F401
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.slab import step_slab
from sheeprl_tpu.envs.env import make_env, make_env_fns, pipelined_vector_env
from sheeprl_tpu.envs.player import fetch_values, obs_sharding
from sheeprl_tpu.ops.numerics import gae
from sheeprl_tpu.parallel.precision import cast_floating, compute_dtype_of
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import get_diagnostics, polynomial_decay, save_configs


def make_train_step(agent, optimizer, cfg, mesh, num_minibatches: int, batch_size: int):
    """Build the jitted update: (params, opt_state, data, key, coefs) ->
    (params, opt_state, metrics).

    ``data`` leaves are ``[N_local * world, ...]`` host-sharded along the
    mesh's ``data`` axis.  Each device permutes its local shard per epoch (the
    reference's per-rank RandomSampler, ppo.py:57-65) and gradients are
    ``pmean``-ed per minibatch (DDP all-reduce equivalent).

    ``metrics`` is ``[pg_loss, v_loss, e_loss, grad_norm, nonfinite_steps]``:
    the diagnostics sentinel's finiteness flag and the global grad norm ride
    the existing metric fetch, and under
    ``diagnostics.sentinel.policy=skip_update`` a non-finite minibatch update
    is discarded in-graph (params/opt state keep their pre-step values).

    With ``diagnostics.health`` on (the default) the step also returns a
    learn-health stats dict (``health_stats``: per-module grad/update/param
    norms, update/weight ratio, dead-unit fraction, plus the value-function
    explained variance) that rides the same output fetch — the global grad
    norm is computed ONCE there and shared with the sentinel's finiteness
    check.  Disabled, the fourth output is an empty dict and the graph is
    unchanged.
    """
    from sheeprl_tpu.diagnostics.health import explained_variance, health_spec, health_stats
    from sheeprl_tpu.diagnostics.sentinel import finite_flag, select_finite, sentinel_spec

    sentinel = sentinel_spec(cfg)
    health = health_spec(cfg)
    world = mesh.devices.size
    distributed = world > 1
    cdt = compute_dtype_of(cfg)  # bf16 under fabric.precision=bf16-*

    def loss_fn(params, batch, clip_coef, ent_coef, vf_coef):
        _, new_logprobs, entropy, new_values = agent.apply(
            cast_floating(params, cdt), cast_floating(batch["obs"], cdt), actions=batch["actions"]
        )
        new_values = new_values.astype(jnp.float32)  # loss math in fp32
        advantages = batch["advantages"]
        if cfg.algo.normalize_advantages:
            mu = advantages.mean()
            std = advantages.std()
            if distributed:
                mu = jax.lax.pmean(mu, "data")
                std = jax.lax.pmean(std, "data")
            advantages = (advantages - mu) / (std + 1e-8)
        pg_loss = policy_loss(
            new_logprobs, batch["logprobs"], advantages, clip_coef, cfg.algo.loss_reduction
        )
        v_loss = value_loss(
            new_values,
            batch["values"],
            batch["returns"],
            clip_coef,
            cfg.algo.clip_vloss,
            cfg.algo.loss_reduction,
        )
        e_loss = entropy_loss(entropy, cfg.algo.loss_reduction)
        total = pg_loss + vf_coef * v_loss + ent_coef * e_loss
        return total, (pg_loss, v_loss, e_loss)

    def update(params, opt_state, data, key, coefs):
        clip_coef, ent_coef, vf_coef = coefs
        n_local = num_minibatches * batch_size

        def epoch_body(carry, epoch_key):
            params, opt_state = carry
            perm = jax.random.permutation(epoch_key, n_local)
            idxs = perm.reshape(num_minibatches, batch_size)

            def mb_body(carry, mb_idx):
                params, opt_state = carry
                mb = jax.tree_util.tree_map(lambda x: x[mb_idx], data)
                grads, aux = jax.grad(loss_fn, has_aux=True)(
                    params, mb, clip_coef, ent_coef, vf_coef
                )
                if distributed:
                    grads = jax.lax.pmean(grads, "data")
                    aux = jax.lax.pmean(aux, "data")
                updates, new_opt_state = optimizer.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                # any NaN/Inf gradient leaf poisons the global norm, so one
                # scalar check covers the whole tree; pmean'd inputs mean
                # every device takes the same branch of the select below.
                # With health on, the norm comes from health_stats — one
                # whole-tree reduction shared by sentinel + health gauges.
                if health.enabled:
                    hstats = health_stats(
                        grads, updates, params, per_module=health.per_module, dead_eps=health.dead_eps
                    )
                    gnorm = hstats["grad_norm"]
                else:
                    hstats = {}
                    gnorm = optax.global_norm(grads)
                finite = finite_flag(gnorm, *aux)
                if sentinel.skip_update:
                    params = select_finite(finite, new_params, params)
                    opt_state = select_finite(finite, new_opt_state, opt_state)
                else:
                    params, opt_state = new_params, new_opt_state
                stats = jnp.stack([*aux, gnorm, 1.0 - finite.astype(jnp.float32)])
                return (params, opt_state), (stats, hstats)

            return jax.lax.scan(mb_body, (params, opt_state), idxs)

        keys = jax.random.split(key, cfg.algo.update_epochs)
        (params, opt_state), (losses, health_tree) = jax.lax.scan(
            epoch_body, (params, opt_state), keys
        )
        flat = losses.reshape(-1, 5)
        # mean losses/grad-norm over minibatches; nonfinite steps are a count
        metrics = jnp.concatenate([jnp.mean(flat[:, :4], axis=0), jnp.sum(flat[:, 4:], axis=0)])
        # health stats average over epochs x minibatches and ride the same
        # output fetch; value EV is whole-batch (pre-update critic vs returns)
        health_out = jax.tree_util.tree_map(jnp.mean, health_tree)
        if health.enabled:
            ev = explained_variance(data["values"], data["returns"])
            if distributed:
                ev = jax.lax.pmean(ev, "data")
            health_out["value_ev"] = ev
        return params, opt_state, metrics, health_out

    if distributed:
        from jax import shard_map

        def sharded_update(params, opt_state, data, key, coefs):
            # per-device independent permutation: fold the axis index into the key
            def body(params, opt_state, data, key, coefs):
                key = jax.random.fold_in(key, jax.lax.axis_index("data"))
                return update(params, opt_state, data, key, coefs)

            return shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), P(), P("data"), P(), P()),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )(params, opt_state, data, key, coefs)

        return jax.jit(sharded_update, donate_argnums=(0, 1))
    return jax.jit(update, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg):
    # ---- sizes & validation (reference ppo.py:110-135) -------------------
    world_size = runtime.world_size
    num_envs = cfg.env.num_envs
    rollout_steps = cfg.algo.rollout_steps
    batch_size = cfg.algo.per_rank_batch_size
    total_local = rollout_steps * num_envs
    if total_local % world_size != 0:
        raise ValueError(
            f"rollout_steps*num_envs ({total_local}) must be divisible by the number of devices ({world_size})"
        )
    n_per_device = total_local // world_size
    if batch_size is None or batch_size <= 0:
        raise ValueError(f"per_rank_batch_size must be a positive integer, got {batch_size}")
    if n_per_device % batch_size != 0:
        raise ValueError(
            f"Per-device rollout ({n_per_device}) must be divisible by per_rank_batch_size ({batch_size})"
        )
    num_minibatches = n_per_device // batch_size

    rng_key = runtime.seed_everything(cfg.seed)

    # ---- logger / metrics (reference ppo.py:129-166) ---------------------
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

    # ---- envs (reference ppo.py:137-150; split-phase pipeline layer) -----
    envs = pipelined_vector_env(cfg, make_env_fns(cfg, log_dir, "train"))
    observation_space = envs.single_observation_space
    action_space = envs.single_action_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    cnn_keys = cfg.algo.cnn_keys.encoder
    mlp_keys = cfg.algo.mlp_keys.encoder
    obs_keys = list(cnn_keys) + list(mlp_keys)
    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )

    # ---- agent + optimizer (reference ppo.py:168-205) --------------------
    state = runtime.load(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None
    agent, params, _ = build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["agent"] if state else None,
    )
    # bf16-true: weights live in bf16; *-mixed keeps fp32 masters, casting per-loss
    params = cast_floating(params, runtime.param_dtype)
    # lr annealing: bake a linear schedule into the optimizer's own step count
    # (reference anneals per-update on the host, ppo.py:230-263,415-424)
    policy_steps_per_iter = int(num_envs * rollout_steps)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    if cfg.algo.anneal_lr:
        schedule = optax.linear_schedule(
            init_value=cfg.algo.optimizer.learning_rate,
            end_value=0.0,
            transition_steps=max(1, total_iters * cfg.algo.update_epochs * num_minibatches),
        )
        base_opt = instantiate(cfg.algo.optimizer, learning_rate=schedule)
    else:
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

    # replicate params across the mesh (single-controller "DDP broadcast")
    from sheeprl_tpu.parallel.mesh import batch_sharding, replicated_sharding

    if world_size > 1:
        params = jax.device_put(params, replicated_sharding(runtime.mesh))
        opt_state = jax.device_put(opt_state, replicated_sharding(runtime.mesh))
        data_sharding = batch_sharding(runtime.mesh)
    else:
        data_sharding = None

    # telemetry instrumentation (recompile watchdog + cost_analysis FLOPs for
    # MFU): the train step dispatches through the AOT-compiled executable,
    # the rollout policy keeps native jit dispatch with signature watching
    train_step = diag.instrument(
        "train_step",
        make_train_step(agent, optimizer, cfg, runtime.mesh, num_minibatches, batch_size),
        kind="train",
        donate_argnums=(0, 1),  # params, opt_state — audited at first dispatch
    )
    diag.register_footprint("params", params)
    diag.register_footprint("opt_state", opt_state)

    # jitted rollout policy + value bootstrap
    @jax.jit
    def policy_step(params, obs, key):
        actions, logprobs, _, values = agent.apply(params, obs, key=key)
        return actions, logprobs, values

    policy_step = diag.instrument("policy_step", policy_step, kind="rollout")
    # device-resident batched inference: the obs slab is staged through ONE
    # device_put against this reused sharding, and all three policy outputs
    # come back in ONE blocking fetch — the per-step link cost is constant in
    # num_envs (fetch amortization = num_envs, emitted live by telemetry)
    stage_sharding = obs_sharding(runtime.mesh if world_size > 1 else None)

    @jax.jit
    def value_step(params, obs):
        return agent.apply(params, obs, method="get_values")

    @jax.jit
    def gae_step(params, last_obs, rewards, values, dones):
        next_value = agent.apply(params, last_obs, method="get_values")
        return gae(
            rewards,
            values,
            dones,
            next_value,
            rollout_steps,
            cfg.algo.gamma,
            cfg.algo.gae_lambda,
        )

    # ---- buffer (reference ppo.py:207-215) -------------------------------
    buffer_size = cfg.buffer.size
    rb = ReplayBuffer(
        buffer_size,
        num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer"),
        obs_keys=obs_keys,
    )
    diag.track_buffer("replay", rb)

    # ---- counters (reference ppo.py:217-263) -----------------------------
    start_iter = (state["iter_num"] if state else 0) + 1
    policy_step_count = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0

    # clip/entropy coefficient annealing state (reference ppo.py:230-263)
    initial_ent = cfg.algo.ent_coef
    initial_clip = cfg.algo.clip_coef
    ent_coef = initial_ent
    clip_coef = initial_clip

    obs, _ = envs.reset(seed=cfg.seed)

    for iter_num in range(start_iter, total_iters + 1):
        with timer("Time/env_interaction_time"), diag.span("rollout"):
            for _ in range(rollout_steps):
                policy_step_count += num_envs  # global env steps (num_envs spans the whole mesh)
                diag.note_env_steps(num_envs)
                # sample actions (device): one staged h2d, one blocking fetch
                rng_key, step_key = jax.random.split(rng_key)
                torch_obs = prepare_obs(
                    obs, cnn_keys=cnn_keys, mlp_keys=mlp_keys, num_envs=num_envs, sharding=stage_sharding
                )
                actions, logprobs, values = policy_step(params, torch_obs, step_key)
                actions_np, logprobs_np, values_np = fetch_values(actions, logprobs, values)
                if is_continuous:
                    env_actions = actions_np.reshape(num_envs, -1)
                elif is_multidiscrete:
                    env_actions = actions_np.astype(np.int64)
                else:
                    env_actions = actions_np[:, 0].astype(np.int64)

                # split-phase: the env workers step while this process copies
                # the policy outputs + current obs into the step record — the
                # per-step critical path is max(env_step, host copies) instead
                # of their sum (trajectories are bit-for-bit the serialized
                # order's: nothing the env sees changed, only when we wait)
                with diag.span("env_step_async"):
                    envs.step_async(env_actions)
                step_data: Dict[str, np.ndarray] = step_slab(
                    num_envs,
                    {
                        **{k: obs[k] for k in obs_keys},
                        "actions": actions_np,
                        "logprobs": logprobs_np,
                        "values": values_np,
                    },
                )
                with diag.span("env_wait"):
                    next_obs, rewards, terminated, truncated, info = envs.step_wait()
                dones = np.logical_or(terminated, truncated).reshape(num_envs, 1).astype(np.float32)
                rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, 1)
                if cfg.env.clip_rewards:
                    rewards = np.tanh(rewards)

                # truncation bootstrapping (reference ppo.py:287-306)
                if "final_obs" in info and np.any(truncated):
                    final_obs = info["final_obs"]
                    trunc_idx = np.nonzero(truncated)[0]
                    stacked = {
                        k: np.stack([np.asarray(final_obs[i][k]) for i in trunc_idx])
                        for k in obs_keys
                    }
                    t_obs = prepare_obs(stacked, cnn_keys=cnn_keys, mlp_keys=mlp_keys, num_envs=len(trunc_idx))
                    vals = np.asarray(value_step(params, t_obs))
                    rewards[trunc_idx] += cfg.algo.gamma * vals.reshape(-1, 1)

                step_data.update(step_slab(num_envs, {"rewards": rewards, "dones": dones}))
                rb.add(step_data, validate_args=cfg.buffer.validate_args)

                # episode stats (reference ppo.py:327-341)
                if "final_info" in info and "episode" in info["final_info"]:
                    ep = info["final_info"]["episode"]
                    mask = ep.get("_r", info["final_info"].get("_episode"))
                    if mask is not None and np.any(mask):
                        for r, l in zip(ep["r"][mask], ep["l"][mask]):
                            aggregator.update("Rewards/rew_avg", float(r))
                            aggregator.update("Game/ep_len_avg", float(l))

                obs = next_obs

        # ---- GAE over the collected rollout (reference ppo.py:344-360) ----
        with diag.span("buffer-sample"):
            local = {k: np.asarray(rb[k][:rollout_steps]) for k in rb.buffer.keys()}
            torch_last_obs = prepare_obs(obs, cnn_keys=cnn_keys, mlp_keys=mlp_keys, num_envs=num_envs)
            returns, advantages = gae_step(
                params,
                torch_last_obs,
                jnp.asarray(local["rewards"]),
                jnp.asarray(local["values"]),
                jnp.asarray(local["dones"]),
            )
            local["returns"] = np.asarray(returns)
            local["advantages"] = np.asarray(advantages)

            # flatten [T, N, ...] -> [T*N, ...]; device-shard along the data axis
            flat = {
                "obs": {k: local[k].reshape(total_local, *local[k].shape[2:]) for k in obs_keys},
                "actions": local["actions"].reshape(total_local, -1),
                "logprobs": local["logprobs"].reshape(total_local, -1),
                "values": local["values"].reshape(total_local, -1),
                "returns": local["returns"].reshape(total_local, -1),
                "advantages": local["advantages"].reshape(total_local, -1),
            }
            device_data = jax.tree_util.tree_map(
                lambda x: jax.device_put(jnp.asarray(x), data_sharding) if data_sharding else jnp.asarray(x),
                flat,
            )
        device_data = diag.maybe_inject_nan(iter_num, device_data)
        # recompile-watchdog drill: pads world_size rows that the minibatch
        # indexing never reads (training math unchanged, graph recompiles)
        device_data = diag.maybe_inject_shape_change(iter_num, device_data, pad=world_size)

        # ---- annealing (reference ppo.py:415-424) -------------------------
        if cfg.algo.anneal_clip_coef:
            clip_coef = polynomial_decay(
                iter_num, initial=initial_clip, final=0.0, max_decay_steps=total_iters, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            ent_coef = polynomial_decay(
                iter_num, initial=initial_ent, final=0.0, max_decay_steps=total_iters, power=1.0
            )

        # ---- update phase: one jitted graph (reference ppo.py:30-102) -----
        with timer("Time/train_time"), diag.span("train"):
            rng_key, train_key = jax.random.split(rng_key)
            coefs = (
                jnp.asarray(clip_coef, jnp.float32),
                jnp.asarray(ent_coef, jnp.float32),
                jnp.asarray(cfg.algo.vf_coef, jnp.float32),
            )
            params, opt_state, losses, health = train_step(
                params, opt_state, device_data, train_key, coefs
            )
            # ONE blocking d2h for metrics + health stats together: the
            # health tree rides the fetch the metric vector already paid
            # for (the CLI e2e pins dispatch and device_get counts)
            losses, health_host = fetch_values(losses, health)

        diag.on_health(policy_step_count, health_host)
        aggregator.update("Loss/policy_loss", float(losses[0]))
        aggregator.update("Loss/value_loss", float(losses[1]))
        aggregator.update("Loss/entropy_loss", float(losses[2]))
        aggregator.update("Grads/global_norm", float(losses[3]))
        diag.on_update(
            policy_step_count,
            {
                "Loss/policy_loss": float(losses[0]),
                "Loss/value_loss": float(losses[1]),
                "Loss/entropy_loss": float(losses[2]),
                "Grads/global_norm": float(losses[3]),
            },
            nonfinite=float(losses[4]),
        )

        # ---- logging (reference ppo.py:386-413) ---------------------------
        if policy_step_count - last_log >= cfg.metric.log_every or iter_num == total_iters or cfg.dry_run:
            metrics = aggregator.compute()
            timers = timer.compute()
            if timers.get("Time/env_interaction_time", 0) > 0:
                metrics["Time/sps_env_interaction"] = (
                    (policy_step_count - last_log) / timers["Time/env_interaction_time"]
                )
            if timers.get("Time/train_time", 0) > 0:
                metrics["Time/sps_train"] = (
                    (iter_num * cfg.algo.update_epochs * num_minibatches) / timers["Time/train_time"]
                )
            if runtime.is_global_zero:
                logger.log_metrics(metrics, policy_step_count)
            aggregator.reset()
            timer.reset()
            last_log = policy_step_count

        # ---- checkpoint (reference ppo.py:428-442) ------------------------
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
                "batch_size": batch_size * world_size,
            }
            ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step_count}_0.ckpt")
            with diag.span("checkpoint"):
                runtime.call(
                    "on_checkpoint_coupled",
                    ckpt_path=ckpt_path,
                    state=ckpt_state,
                    replay_buffer=None,
                )
            diag.on_checkpoint(policy_step_count, ckpt_path)
            if preempt_now:
                envs.close()
                diag.on_preempted(policy_step_count, iter_num, ckpt_path)

    envs.close()
    # ---- final test episode (reference ppo.py:445-453) --------------------
    cumulative_rew = None
    if runtime.is_global_zero and cfg.algo.run_test:
        test_env = make_env(cfg, cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
        cumulative_rew = test(agent.apply, params, test_env, runtime, cfg, log_dir)
        logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, policy_step_count)

    if cfg.model_manager.disabled is False and runtime.is_global_zero:  # pragma: no cover
        from sheeprl_tpu.utils.mlflow import log_models

        log_models(cfg, {"agent": params}, log_dir)
    logger.finalize()
    diag.close("completed")
    return cumulative_rew
