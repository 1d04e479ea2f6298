"""SAC training loop — TPU-native re-design of
/root/reference/sheeprl/algos/sac/sac.py:32-427.

Off-policy machinery: host-side replay buffer (numpy/memmap), ``Ratio``-driven
gradient-step count per iteration, and a jitted update that runs ALL the
iteration's gradient steps as one ``lax.scan`` graph — each scan step does
critic update → Polyak target EMA → actor update → entropy(α) update
(reference sac.py:32-80), data-parallel over the mesh with ``pmean`` replacing
the DDP all-reduce (including the reference's explicit ``log_alpha`` grad
all-reduce, sac.py:72).
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

from sheeprl_tpu.algos.sac.agent import build_agent
from sheeprl_tpu.algos.sac.loss import conservative_q_penalty, critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.utils import AGGREGATOR_KEYS, MODELS_TO_REGISTER, prepare_obs, test  # noqa: F401
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.slab import step_slab
from sheeprl_tpu.envs.env import make_env, make_env_fns, pipelined_vector_env
from sheeprl_tpu.envs.player import fetch_values, obs_sharding
from sheeprl_tpu.parallel.dp import local_sample_size
from sheeprl_tpu.parallel.precision import cast_floating, compute_dtype_of
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, get_diagnostics, save_configs


def make_train_step(actor_def, critic_def, optimizers, cfg, mesh, target_entropy: float):
    """Jitted multi-gradient-step update over ``[G, B, ...]`` batches.

    The returned metric vector is ``[qf_loss, actor_loss, alpha_loss,
    grad_norm, nonfinite_steps]``; under
    ``diagnostics.sentinel.policy=skip_update`` a scan step whose losses or
    combined grad norm go non-finite has its whole critic/target/actor/alpha
    update discarded in-graph (the carry keeps its pre-step values).  With
    ``diagnostics.health`` on, a learn-health stats dict over the
    actor/critic/alpha module trio (grad/update/param norms, update/weight
    ratio, dead-unit fraction — averaged over the scan's gradient steps)
    rides the same output fetch; the combined grad norm is computed once
    there and shared with the sentinel's finiteness check.
    """
    from sheeprl_tpu.diagnostics.health import health_spec, health_stats
    from sheeprl_tpu.diagnostics.sentinel import finite_flag, select_finite, sentinel_spec

    sentinel = sentinel_spec(cfg)
    health = health_spec(cfg)
    world = mesh.devices.size
    distributed = world > 1
    tau = cfg.algo.tau
    cdt = compute_dtype_of(cfg)
    # conservative Q penalty (offline mode, howto/offline_rl.md): a
    # trace-time constant — cql_alpha=0 (the default, and every online run)
    # leaves the compiled graph bit-identical to the pre-offline step
    offline_cfg = cfg.algo.get("offline") or {}
    cql_alpha = float(offline_cfg.get("cql_alpha", 0.0) or 0.0)
    cql_samples = int(offline_cfg.get("cql_samples", 4) or 4)
    act_low = np.asarray(actor_def.action_low, np.float32).reshape(-1)
    act_high = np.asarray(actor_def.action_high, np.float32).reshape(-1)
    if cql_alpha > 0 and not (np.isfinite(act_low).all() and np.isfinite(act_high).all()):
        raise ValueError(
            "algo.offline.cql_alpha > 0 needs finite action bounds for its uniform "
            "action proposals (set algo.offline.action_low/high)"
        )

    def one_step(carry, inp):
        params, opt_states = carry
        batch, key = inp
        # snapshots for the sentinel's skip selection: tree_map rebuilds every
        # container (leaves shared), so the snapshot can never alias a dict
        # the update below mutates in place
        if sentinel.skip_update:
            prev_params = jax.tree_util.tree_map(lambda leaf: leaf, params)
            prev_opt_states = jax.tree_util.tree_map(lambda leaf: leaf, opt_states)
        # network inputs in the compute dtype; TD targets stay fp32
        obs_c = cast_floating(batch["observations"], cdt)
        next_obs_c = cast_floating(batch["next_observations"], cdt)
        # the cql key is split ONLY when the penalty is armed so the
        # cql_alpha=0 graph (and its RNG stream) stays bit-identical
        if cql_alpha > 0:
            key, cql_key = jax.random.split(key)

        # --- critic update (reference sac.py:45-53) -----------------------
        def qf_loss_fn(critic_params):
            next_actions, next_logprobs = actor_def.apply(
                cast_floating(params["actor"], cdt), next_obs_c, key, method="sample_and_log_prob"
            )
            next_q = critic_def.apply(
                cast_floating(params["target_critic"], cdt), next_obs_c, next_actions
            ).astype(jnp.float32)
            min_next_q = jnp.min(next_q, axis=-1, keepdims=True)
            alpha = jnp.exp(params["log_alpha"])
            next_qf_value = batch["rewards"] + (1 - batch["terminated"]) * cfg.algo.gamma * (
                min_next_q - alpha * next_logprobs.astype(jnp.float32)
            )
            next_qf_value = jax.lax.stop_gradient(next_qf_value)
            qf_values = critic_def.apply(
                cast_floating(critic_params, cdt), obs_c, cast_floating(batch["actions"], cdt)
            ).astype(jnp.float32)
            loss = critic_loss(qf_values, next_qf_value, cfg.algo.critic.n)
            if cql_alpha > 0:
                actor_c = cast_floating(params["actor"], cdt)
                critic_c = cast_floating(critic_params, cdt)
                loss = loss + cql_alpha * conservative_q_penalty(
                    cql_key,
                    obs_c,
                    qf_values,
                    lambda o, k: actor_def.apply(actor_c, o, k, method="sample_and_log_prob"),
                    lambda o, a: critic_def.apply(critic_c, o, a),
                    act_low,
                    act_high,
                    cql_samples,
                )
            return loss

        qf_l, qf_grads = jax.value_and_grad(qf_loss_fn)(params["critic"])
        if distributed:
            qf_grads = jax.lax.pmean(qf_grads, "data")
            qf_l = jax.lax.pmean(qf_l, "data")
        critic_updates, opt_states["critic"] = optimizers["critic"].update(
            qf_grads, opt_states["critic"], params["critic"]
        )
        params["critic"] = optax.apply_updates(params["critic"], critic_updates)

        # --- Polyak target EMA (reference sac.py:55-57, agent.py qfs_target_ema)
        params["target_critic"] = optax.incremental_update(
            params["critic"], params["target_critic"], tau
        )

        # --- actor update (reference sac.py:59-66) ------------------------
        def actor_loss_fn(actor_params):
            actions, logprobs = actor_def.apply(
                cast_floating(actor_params, cdt), obs_c, key, method="sample_and_log_prob"
            )
            q = critic_def.apply(cast_floating(params["critic"], cdt), obs_c, actions).astype(
                jnp.float32
            )
            min_q = jnp.min(q, axis=-1, keepdims=True)
            alpha = jnp.exp(params["log_alpha"])
            return policy_loss(alpha, logprobs.astype(jnp.float32), min_q), logprobs

        (actor_l, logprobs), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params["actor"])
        if distributed:
            actor_grads = jax.lax.pmean(actor_grads, "data")
            actor_l = jax.lax.pmean(actor_l, "data")
        actor_updates, opt_states["actor"] = optimizers["actor"].update(
            actor_grads, opt_states["actor"], params["actor"]
        )
        params["actor"] = optax.apply_updates(params["actor"], actor_updates)

        # --- entropy coefficient update (reference sac.py:68-73) ----------
        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, logprobs, target_entropy)

        alpha_l, alpha_grads = jax.value_and_grad(alpha_loss_fn)(params["log_alpha"])
        if distributed:
            alpha_grads = jax.lax.pmean(alpha_grads, "data")
            alpha_l = jax.lax.pmean(alpha_l, "data")
        alpha_updates, opt_states["alpha"] = optimizers["alpha"].update(
            alpha_grads, opt_states["alpha"], params["log_alpha"]
        )
        params["log_alpha"] = optax.apply_updates(params["log_alpha"], alpha_updates)

        # combined grad norm over the three sequential updates; a NaN/Inf in
        # any grad tree (or loss) poisons it, giving one scalar health flag.
        # health_stats over the {actor, critic, alpha} trio computes the
        # EXACT same combined norm, so the two layers share one reduction.
        if health.enabled:
            hstats = health_stats(
                {"actor": actor_grads, "critic": qf_grads, "alpha": alpha_grads},
                {"actor": actor_updates, "critic": critic_updates, "alpha": alpha_updates},
                {"actor": params["actor"], "critic": params["critic"], "alpha": params["log_alpha"]},
                per_module=health.per_module,
                dead_eps=health.dead_eps,
            )
            gnorm = hstats["grad_norm"]
        else:
            hstats = {}
            gnorm = jnp.sqrt(
                optax.global_norm(qf_grads) ** 2
                + optax.global_norm(actor_grads) ** 2
                + optax.global_norm(alpha_grads) ** 2
            )
        finite = finite_flag(gnorm, qf_l, actor_l, alpha_l)
        if sentinel.skip_update:
            params = select_finite(finite, params, prev_params)
            opt_states = select_finite(finite, opt_states, prev_opt_states)

        stats = jnp.stack([qf_l, actor_l, alpha_l, gnorm, 1.0 - finite.astype(jnp.float32)])
        return (params, opt_states), (stats, hstats)

    def update(params, opt_states, data, keys):
        (params, opt_states), (losses, health_tree) = jax.lax.scan(
            one_step, (params, opt_states), (data, keys)
        )
        # mean losses/grad-norm over gradient steps; nonfinite steps are a count
        metrics = jnp.concatenate([jnp.mean(losses[:, :4], axis=0), jnp.sum(losses[:, 4:], axis=0)])
        # health stats average over the scan's gradient steps and ride the
        # same output fetch as the metric vector
        return params, opt_states, metrics, jax.tree_util.tree_map(jnp.mean, health_tree)

    if distributed:
        from jax import shard_map

        def sharded(params, opt_states, data, keys):
            return shard_map(
                update,
                mesh=mesh,
                in_specs=(P(), P(), P(None, "data"), P()),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )(params, opt_states, data, keys)

        return jax.jit(sharded, donate_argnums=(0, 1))
    return jax.jit(update, donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg):
    world_size = runtime.world_size
    num_envs = cfg.env.num_envs

    if cfg.algo.cnn_keys.encoder:
        import warnings

        warnings.warn("SAC only uses vector observations; CNN keys are ignored (reference sac.py:100)")

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
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("SAC supports only continuous (Box) action spaces")
    mlp_keys = cfg.algo.mlp_keys.encoder

    state = runtime.load(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None
    actor_def, critic_def, params, target_entropy = build_agent(
        runtime, cfg, observation_space, action_space, state["agent"] if state else None
    )
    params = cast_floating(params, runtime.param_dtype)
    optimizers = {
        "actor": instantiate(cfg.algo.actor.optimizer),
        "critic": instantiate(cfg.algo.critic.optimizer),
        "alpha": instantiate(cfg.algo.alpha.optimizer),
    }
    opt_states = {
        "actor": optimizers["actor"].init(params["actor"]),
        "critic": optimizers["critic"].init(params["critic"]),
        "alpha": optimizers["alpha"].init(params["log_alpha"]),
    }
    if state and "opt_states" in state:
        opt_states = jax.tree_util.tree_map(
            lambda ref, saved: jnp.asarray(saved, dtype=getattr(ref, "dtype", None)),
            opt_states,
            state["opt_states"],
        )

    from sheeprl_tpu.parallel.mesh import replicated_sharding

    if world_size > 1:
        params = jax.device_put(params, replicated_sharding(runtime.mesh))
        opt_states = jax.device_put(opt_states, replicated_sharding(runtime.mesh))

    train_step = diag.instrument(
        "train_step",
        make_train_step(actor_def, critic_def, optimizers, cfg, runtime.mesh, target_entropy),
        kind="train",
        donate_argnums=(0, 1),  # params, opt_states — audited at first dispatch
    )
    diag.register_footprint("params", params)
    diag.register_footprint("opt_state", opt_states)

    @jax.jit
    def policy_step(actor_params, obs, key):
        actions, _ = actor_def.apply(actor_params, obs, key, method="sample_and_log_prob")
        return actions

    policy_step = diag.instrument("policy_step", policy_step, kind="rollout")
    # one staged h2d + one blocking action fetch per vector step (see ppo.py)
    stage_sharding = obs_sharding(runtime.mesh if world_size > 1 else None)

    rb = ReplayBuffer(
        cfg.buffer.size,
        num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer"),
        obs_keys=("observations",),
    )
    diag.track_buffer("replay", rb)
    if state and "rb" in state and state["rb"] is not None:
        rb.load_state_dict(state["rb"])

    start_iter = (state["iter_num"] if state else 0) + 1
    policy_step_count = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    policy_steps_per_iter = int(num_envs)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if cfg.checkpoint.resume_from:
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state and "ratio" in state:
        ratio.load_state_dict(state["ratio"])

    batch_size = cfg.algo.per_rank_batch_size
    obs, _ = envs.reset(seed=cfg.seed)

    def run_train(iter_num: int, per_rank_gradient_steps: int) -> None:
        """Sample + dispatch this iteration's gradient steps and fetch the
        metrics (the blocking fetch included, so the whole thing can ride
        inside the env-step overlap window)."""
        nonlocal rng_key, params, opt_states
        with timer("Time/train_time"):
            with diag.span("buffer-sample"):
                sample = rb.sample(
                    batch_size=local_sample_size(batch_size * world_size),
                    n_samples=per_rank_gradient_steps,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )  # [G, B*world, ...]
                data = {
                    k: jnp.asarray(np.asarray(v), jnp.float32)
                    for k, v in sample.items()
                    if k in ("observations", "next_observations", "actions", "rewards", "terminated")
                }
            data = diag.maybe_inject_nan(iter_num, data)
            with diag.span("train"):
                rng_key, scan_key = jax.random.split(rng_key)
                keys = jax.random.split(scan_key, per_rank_gradient_steps)
                params, opt_states, losses, health = train_step(params, opt_states, data, keys)
                # one blocking d2h for metrics + health stats together
                losses, health_host = fetch_values(losses, health)
        diag.on_health(policy_step_count, health_host)
        aggregator.update("Loss/value_loss", float(losses[0]))
        aggregator.update("Loss/policy_loss", float(losses[1]))
        aggregator.update("Loss/alpha_loss", float(losses[2]))
        aggregator.update("Grads/global_norm", float(losses[3]))
        diag.on_update(
            policy_step_count,
            {
                "Loss/value_loss": float(losses[0]),
                "Loss/policy_loss": float(losses[1]),
                "Loss/alpha_loss": float(losses[2]),
                "Grads/global_norm": float(losses[3]),
            },
            nonfinite=float(losses[4]),
        )

    for iter_num in range(start_iter, total_iters + 1):
        policy_step_count += policy_steps_per_iter
        diag.note_env_steps(num_envs)
        with timer("Time/env_interaction_time"), diag.span("rollout"):
            if iter_num <= learning_starts:
                actions = envs.action_space.sample()
            else:
                rng_key, step_key = jax.random.split(rng_key)
                flat_obs = prepare_obs(obs, mlp_keys=mlp_keys, num_envs=num_envs, sharding=stage_sharding)
                actions = np.asarray(policy_step(params["actor"], flat_obs, step_key))
            with diag.span("env_step_async"):
                envs.step_async(actions.reshape(envs.action_space.shape))

        # --- two-stage pipeline: gradient steps overlap the env workers ----
        # The sample sees transitions through t-1 (t's transition needs the
        # next obs, which is still being computed) — a bounded one-transition
        # lag (howto/async_envs.md) in exchange for a critical path of
        # max(train_dispatch + metric fetch, env_step) instead of their sum.
        # A still-empty buffer (learning_starts=0 first iteration) falls back
        # to training after the add, i.e. the serialized order.
        per_rank_gradient_steps = 0
        trained = False
        if iter_num >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step_count - prefill_steps * policy_steps_per_iter)
            if cfg.dry_run:
                per_rank_gradient_steps = 1
            if per_rank_gradient_steps > 0 and not rb.empty:
                run_train(iter_num, per_rank_gradient_steps)
                trained = True

        with timer("Time/env_interaction_time"), diag.span("env_wait"):
            next_obs, rewards, terminated, truncated, info = envs.step_wait()
        rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, -1)

        if "final_info" in info and "episode" in info["final_info"]:
            ep = info["final_info"]["episode"]
            mask = ep.get("_r", info["final_info"].get("_episode"))
            if mask is not None and np.any(mask):
                for r, l in zip(ep["r"][mask], ep["l"][mask]):
                    aggregator.update("Rewards/rew_avg", float(r))
                    aggregator.update("Game/ep_len_avg", float(l))

        # real next obs for done envs (reference sac.py:276-284)
        real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in mlp_keys}
        if "final_obs" in info:
            for idx, final_obs in enumerate(info["final_obs"]):
                if final_obs is not None:
                    for k in mlp_keys:
                        real_next_obs[k][idx] = np.asarray(final_obs[k])

        flat = {
            "observations": np.concatenate(
                [np.asarray(obs[k], np.float32).reshape(num_envs, -1) for k in mlp_keys], axis=-1
            ),
            "actions": actions.reshape(num_envs, -1),
            "rewards": rewards,
            "terminated": terminated,
            "truncated": truncated,
        }
        if not cfg.buffer.sample_next_obs:
            flat["next_observations"] = np.concatenate(
                [real_next_obs[k].astype(np.float32).reshape(num_envs, -1) for k in mlp_keys], axis=-1
            )
        step_data: Dict[str, np.ndarray] = step_slab(
            num_envs, flat, dtypes={"terminated": np.float32, "truncated": np.float32}
        )
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
        obs = next_obs

        # --- train fallback (reference sac.py:299-355): only taken when the
        # pipelined site above skipped because the buffer was still empty ----
        if per_rank_gradient_steps > 0 and not trained:
            run_train(iter_num, per_rank_gradient_steps)

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
            ckpt_state = {
                "agent": jax.tree_util.tree_map(np.asarray, params),
                "opt_states": jax.tree_util.tree_map(np.asarray, opt_states),
                "ratio": ratio.state_dict(),
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
                    replay_buffer=rb if cfg.buffer.checkpoint else None,
                )
            diag.on_checkpoint(policy_step_count, ckpt_path)
            if preempt_now:
                envs.close()
                diag.on_preempted(policy_step_count, iter_num, ckpt_path)

    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        test_env = make_env(cfg, cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
        cumulative_rew = test(actor_def.apply, params["actor"], test_env, runtime, cfg, log_dir)
        logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, policy_step_count)
    if cfg.model_manager.disabled is False and runtime.is_global_zero:  # pragma: no cover
        from sheeprl_tpu.utils.mlflow import log_models

        log_models(cfg, {"agent": params}, log_dir)
    logger.finalize()
    diag.close("completed")
