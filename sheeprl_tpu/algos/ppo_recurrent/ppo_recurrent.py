"""Recurrent-PPO training loop — TPU-native re-design of
/root/reference/sheeprl/algos/ppo_recurrent/ppo_recurrent.py:30-524.

The reference splits the rollout into episodes, pads them and trains with
`pack_padded_sequence` masking (ppo_recurrent.py:420-447).  Ragged episodes
are hostile to XLA's static shapes, so this build uses the equivalent
fixed-length formulation: the rollout ``[T, N]`` is cut into sequences of
``per_rank_sequence_length`` (T must be a multiple, like the reference
requires at :226), each sequence starts from its stored LSTM state, and the
`reset_recurrent_state_on_done` semantics are preserved by in-graph masked
state resets at done steps.  No padding, no masks, one `lax.scan` per BPTT.

Three backbones (``algo.backbone``), each a player (``players.py``): ``lstm`` is
the reference's, ``olmo_hybrid`` a hybrid language model as a token-action
policy (``models/hybrid_lm.py``), ``sparse_moe`` a sparse-attention, routed-expert
one (``models/sparse_moe_lm.py``).  What a policy carries through a rollout,
where it lives and what a training sequence starts from is the player's; the
loop below names no backbone.
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

from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent
from sheeprl_tpu.algos.ppo_recurrent.players import make_player, make_token_player  # noqa: F401
from sheeprl_tpu.algos.ppo_recurrent.utils import AGGREGATOR_KEYS, MODELS_TO_REGISTER, KeyStream  # noqa: F401
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.slab import step_slab
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.envs.env import make_env_fns, pipelined_vector_env
from sheeprl_tpu.ops.numerics import gae
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.parallel.precision import cast_floating
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import get_diagnostics, polynomial_decay, save_configs


def make_train_step(player, optimizer, cfg, mesh, num_minibatches: int, seq_batch: int):
    """Jitted update over sequence minibatches: data leaves are
    ``[L, S, ...]`` with S sequences sharded over the mesh."""
    world = mesh.devices.size
    distributed = world > 1

    def loss_fn(params, batch, clip_coef, ent_coef, vf_coef):
        new_logprobs, entropy, new_values, *own = player.evaluate(params, batch)
        with jax.named_scope("ppo_loss"):
            loss, reported = ppo_loss(new_logprobs, entropy, new_values, batch, clip_coef, ent_coef, vf_coef)
        for term, reports in own:  # a policy's own term of the loss, and what it reports beside the three
            loss, reported = loss + term, reported + tuple(reports)
        return loss, reported

    def ppo_loss(new_logprobs, entropy, new_values, batch, clip_coef, ent_coef, vf_coef):
        new_values = new_values.astype(jnp.float32)
        advantages = batch["advantages"]
        if cfg.algo.normalize_advantages:
            mu, std = advantages.mean(), advantages.std()
            if distributed:
                mu, std = jax.lax.pmean(mu, "data"), jax.lax.pmean(std, "data")
            advantages = (advantages - mu) / (std + 1e-8)
        pg_loss = policy_loss(new_logprobs, batch["logprobs"], advantages, clip_coef, "mean")
        v_loss = value_loss(
            new_values, batch["values"], batch["returns"], clip_coef, cfg.algo.clip_vloss, "mean"
        )
        e_loss = entropy_loss(entropy, cfg.algo.loss_reduction)
        return pg_loss + vf_coef * v_loss + ent_coef * e_loss, (pg_loss, v_loss, e_loss)

    def update(params, opt_state, data, key, coefs):
        clip_coef, ent_coef, vf_coef = coefs
        n_local = num_minibatches * seq_batch

        def epoch_body(carry, epoch_key):
            params, opt_state = carry
            perm = jax.random.permutation(epoch_key, n_local)
            idxs = perm.reshape(num_minibatches, seq_batch)

            def mb_body(carry, mb_idx):
                params, opt_state = carry
                mb = jax.tree_util.tree_map(lambda x: x[:, mb_idx], data)
                grads, aux = jax.grad(loss_fn, has_aux=True)(params, mb, clip_coef, ent_coef, vf_coef)
                if distributed:
                    grads = jax.lax.pmean(grads, "data")
                    aux = jax.lax.pmean(aux, "data")
                with jax.named_scope("optim"):
                    grad_norms = jnp.stack([jnp.linalg.norm(g.astype(jnp.float32)) for g in jax.tree_util.tree_leaves(grads)])
                    updates, opt_state = optimizer.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return (params, opt_state), (jnp.stack(aux), grad_norms)

            return jax.lax.scan(mb_body, (params, opt_state), idxs)

        keys = jax.random.split(key, cfg.algo.update_epochs)
        (params, opt_state), (losses, grad_norms) = jax.lax.scan(epoch_body, (params, opt_state), keys)
        # the mean losses as ever; then every gradient step's own, and the norm of
        # every leaf's gradient as the optimizer got it, in the order of the steps
        by_step = {"losses": losses.reshape(-1, losses.shape[-1]), "grad_norms": grad_norms.reshape(-1, grad_norms.shape[-1])}
        return params, opt_state, jnp.mean(by_step["losses"], axis=0), by_step

    if distributed:
        from jax import shard_map

        def sharded(params, opt_state, data, key, coefs):
            def body(params, opt_state, data, key, coefs):
                key = jax.random.fold_in(key, jax.lax.axis_index("data"))
                return update(params, opt_state, data, key, coefs)

            # every data leaf is [L|1, S, ...]: shard the sequence axis
            return shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), P(), P(None, "data"), P(), P()),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )(params, opt_state, data, key, coefs)

        return jax.jit(sharded, donate_argnums=(0, 1))
    return jax.jit(update, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg):
    world_size = runtime.world_size
    num_envs = cfg.env.num_envs
    rollout_steps = cfg.algo.rollout_steps
    seq_len = cfg.algo.per_rank_sequence_length
    if not seq_len or seq_len <= 0:
        raise ValueError(f"per_rank_sequence_length must be positive, got {seq_len}")
    if rollout_steps % seq_len != 0:
        raise ValueError(
            f"rollout_steps ({rollout_steps}) must be a multiple of per_rank_sequence_length ({seq_len})"
        )
    num_sequences = (rollout_steps // seq_len) * num_envs
    if num_sequences % world_size != 0:
        raise ValueError(
            f"Number of sequences ({num_sequences}) must be divisible by the number of devices ({world_size})"
        )
    seq_per_device = num_sequences // world_size
    num_batches = max(1, cfg.algo.get("per_rank_num_batches", 4))
    seq_batch = max(1, seq_per_device // num_batches)
    num_minibatches = seq_per_device // seq_batch

    keys = KeyStream(runtime.seed_everything(cfg.seed))  # the loop's random stream, which its player draws from too
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
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
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
    player = make_player(agent, cfg)
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

    # telemetry + memory instrumentation — see tools/check_instrumentation.py
    # The update differentiates the forward of a player that is never started.  The rollout's player holds `diag`,
    # which holds this step: reached from the step's closure it would close a cycle through a jitted function, which
    # the collector does not free, and what it carries (a gigabyte of cache) would outlive the loop on the device.
    train_step = diag.instrument(
        "train_step",
        make_train_step(make_player(agent, cfg), optimizer, cfg, runtime.mesh, num_minibatches, seq_batch),
        kind="train",
        donate_argnums=(0, 1),
    )
    diag.register_footprint("params", params)
    diag.register_footprint("opt_state", opt_state)

    rb = ReplayBuffer(
        rollout_steps,
        num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer"),
        obs_keys=obs_keys,
    )

    start_iter = (state["iter_num"] if state else 0) + 1
    policy_step_count = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0

    initial_ent = cfg.algo.ent_coef
    initial_clip = cfg.algo.clip_coef
    ent_coef = initial_ent
    clip_coef = initial_clip

    obs, _ = envs.reset(seed=cfg.seed)
    prev_dones = np.zeros((num_envs, 1), np.float32)
    player.start(diag, keys, num_envs, rollout_steps, seq_len)  # after the train step is instrumented

    for iter_num in range(start_iter, total_iters + 1):
        with timer("Time/env_interaction_time"), diag.span("rollout"):
            for step in range(rollout_steps):
                policy_step_count += num_envs
                diag.note_env_steps(num_envs)
                player.begin_step(prev_dones)
                with diag.span("rollout/obs-stage"):
                    staged = player.stage(obs, prev_dones)
                with diag.span("rollout/player-forward"):
                    actions = player.act(params, staged)
                with diag.span("rollout/action-fetch"):
                    actions_np, row_extras = player.fetch(actions)
                if is_continuous:
                    env_actions = actions_np.reshape(num_envs, -1)
                elif is_multidiscrete:
                    env_actions = actions_np.astype(np.int64)
                else:
                    env_actions = actions_np[:, 0].astype(np.int64)

                next_obs, rewards, terminated, truncated, info = envs.step(env_actions)
                dones = np.logical_or(terminated, truncated).reshape(num_envs, 1).astype(np.float32)
                rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, 1)
                if cfg.env.clip_rewards:
                    rewards = np.tanh(rewards)

                with diag.span("rollout/replay-add"):
                    row = {
                        **{k: obs[k] for k in obs_keys},
                        "actions": actions_np.reshape(num_envs, -1),
                        "rewards": rewards,
                        "dones": dones,
                        "resets": prev_dones,
                        **row_extras,
                    }
                    step_data: Dict[str, np.ndarray] = step_slab(num_envs, row)
                    rb.add(step_data, validate_args=cfg.buffer.validate_args)

                if "final_info" in info and "episode" in info["final_info"]:
                    ep = info["final_info"]["episode"]
                    mask = ep.get("_r", info["final_info"].get("_episode"))
                    if mask is not None and np.any(mask):
                        for r, l in zip(ep["r"][mask], ep["l"][mask]):
                            aggregator.update("Rewards/rew_avg", float(r))
                            aggregator.update("Game/ep_len_avg", float(l))

                prev_dones = dones
                obs = next_obs

        # bootstrap + GAE (reference ppo_recurrent.py:358-396)
        with diag.span("gae"):
            local = {k: np.asarray(rb[k][:rollout_steps]) for k in rb.buffer.keys()}
            next_values, kept = player.end_rollout(params, obs, prev_dones)
            local.update(kept)
            returns, advantages = gae(
                jnp.asarray(local["rewards"]),
                jnp.asarray(local["values"]),
                jnp.asarray(local["dones"]),
                jnp.asarray(next_values),
                rollout_steps,
                cfg.algo.gamma,
                cfg.algo.gae_lambda,
            )
            local["returns"] = np.asarray(returns)
            local["advantages"] = np.asarray(advantages)

        # [T, N, ...] -> sequences [L, S, ...], S = (T/L)*N
        def to_seq(x):
            T, N = x.shape[:2]
            chunks = T // seq_len
            return (
                x.reshape(chunks, seq_len, N, *x.shape[2:])
                .swapaxes(1, 2)
                .reshape(chunks * N, seq_len, *x.shape[2:])
                .swapaxes(0, 1)
            )

        data = player.initial_state(local)  # what each sequence starts from; takes out of `local` what only it reads
        data.update({k: to_seq(v) for k, v in local.items()})
        device_data = jax.tree_util.tree_map(jnp.asarray, data)
        if world_size > 1:
            from sheeprl_tpu.parallel.mesh import replicated_sharding
            from jax.sharding import NamedSharding

            seq_sharding = NamedSharding(runtime.mesh, P(None, "data"))
            device_data = jax.tree_util.tree_map(lambda x: jax.device_put(x, seq_sharding), device_data)

        if cfg.algo.anneal_clip_coef:
            clip_coef = polynomial_decay(
                iter_num, initial=initial_clip, final=0.0, max_decay_steps=total_iters, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            ent_coef = polynomial_decay(
                iter_num, initial=initial_ent, final=0.0, max_decay_steps=total_iters, power=1.0
            )

        with timer("Time/train_time"):
            with diag.span("train"):
                train_key = keys.next()
                coefs = (
                    jnp.asarray(clip_coef, jnp.float32),
                    jnp.asarray(ent_coef, jnp.float32),
                    jnp.asarray(cfg.algo.vf_coef, jnp.float32),
                )
                params, opt_state, losses, _ = train_step(params, opt_state, device_data, train_key, coefs)
                del device_data, data
            # the wait for the update, under no span of its own: the device is at work
            losses = np.asarray(losses)

        with diag.span("bookkeeping"):
            aggregator.update("Loss/policy_loss", float(losses[0]))
            aggregator.update("Loss/value_loss", float(losses[1]))
            aggregator.update("Loss/entropy_loss", float(losses[2]))
            player.after_update(losses)

            if policy_step_count - last_log >= cfg.metric.log_every or iter_num == total_iters or cfg.dry_run:
                metrics = aggregator.compute()
                timers = timer.compute()
                if timers.get("Time/env_interaction_time", 0) > 0:
                    metrics["Time/sps_env_interaction"] = (
                        (policy_step_count - last_log) / timers["Time/env_interaction_time"]
                    )
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
            # the state goes to the writer as it lies on the device: the writer's own snapshot is then
            # the one host copy (np.asarray here and its copy there would be two, of 9 GB for a language model)
            ckpt_state = {
                "agent": params,
                "opt_state": opt_state,
                "iter_num": iter_num,
                "policy_step": policy_step_count,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "batch_size": seq_batch * world_size,
            }
            ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step_count}_0.ckpt")
            runtime.call("on_checkpoint_coupled", ckpt_path=ckpt_path, state=ckpt_state, replay_buffer=None)
            if preempt_now:
                envs.close()
                diag.on_preempted(policy_step_count, iter_num, ckpt_path)

    envs.close()
    cumulative_rew = player.test(params, log_dir) if runtime.is_global_zero and cfg.algo.run_test else None
    if cumulative_rew is not None:
        logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, policy_step_count)
    logger.finalize()
