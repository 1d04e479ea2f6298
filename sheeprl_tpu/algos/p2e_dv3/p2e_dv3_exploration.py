"""Plan2Explore-DV3 exploration (reference
/root/reference/sheeprl/algos/p2e_dv3/p2e_dv3_exploration.py:41-1059).

One jitted gradient step fuses the five reference phases into a single XLA
graph (the reference runs five separate backward passes on the torch tape):

1. world-model learning (identical to DreamerV3);
2. ensemble learning — N vmapped MLPs predict the next stochastic state from
   ``(posterior, recurrent, action)`` (reference :207-231);
3. exploration behaviour — imagination with the exploration actor; each
   exploration critic contributes a weighted normalized advantage, where
   ``intrinsic`` critics are rewarded by the ensembles' prediction variance
   (reference :252-303) and ``task`` critics by the world-model reward head;
4. per-critic two-hot value losses with their own target critics (:345-372);
5. task behaviour — standard DV3 actor/critic learning, trained zero-shot on
   the exploration data (:384-470).

Data parallelism follows the DV3 pattern: shard_map over the ``data`` mesh
axis, pmean'd grads, all-gathered Moments quantiles (one Moments state per
exploration critic + one for the task actor, reference :663-676).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import optax

from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3  # noqa: F401  (re-export for evaluate)
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _dreamer_main
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (
    dynamic_learning_scan,
    init_moments_state,
    rssm_scan_spec,
    test,
    update_moments,
)
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent
from sheeprl_tpu.algos.p2e_dv3.utils import (  # noqa: F401
    AGGREGATOR_KEYS,
    MODELS_TO_REGISTER,
    expand_exploration_metric_keys,
)
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.ops.distributions import (
    Bernoulli,
    MSEDistribution,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.ops.numerics import compute_lambda_values
from sheeprl_tpu.parallel.dp import P, batch_spec, dp_axis, dp_jit, fold_key, pmean_tree
from sheeprl_tpu.parallel.precision import cast_floating, compute_dtype_of
from sheeprl_tpu.utils.registry import register_algorithm

# filled by _build_agent before make_train_step runs (same single-controller
# stash pattern as the JEPA variant)
_P2E = {"ensemble_def": None, "critics_spec": None}


def metric_order(critics_spec) -> list:
    """Static order of the train-step metrics vector."""
    order = [
        "Loss/world_model_loss",
        "Loss/observation_loss",
        "Loss/reward_loss",
        "Loss/state_loss",
        "Loss/continue_loss",
        "State/kl",
        "Loss/ensemble_loss",
        "Loss/policy_loss_exploration",
        "Loss/policy_loss_task",
        "Loss/value_loss_task",
        "Grads/world_model",
        "Grads/ensemble",
        "Grads/actor_exploration",
        "Grads/actor_task",
        "Grads/critic_task",
    ]
    for name, _, reward_type in critics_spec:
        order.append(f"Loss/value_loss_exploration_{name}")
        order.append(f"Values_exploration/predicted_values_{name}")
        order.append(f"Values_exploration/lambda_values_{name}")
        if reward_type == "intrinsic":
            order.append(f"Rewards/intrinsic_{name}")
    return order


def make_train_step(
    world_model_def,
    actor_def,
    critic_def,
    optimizers,
    cfg,
    actions_dim: Sequence[int],
    is_continuous: bool,
    mesh=None,
):
    axis = dp_axis(mesh)
    cdt = compute_dtype_of(cfg)
    ensemble_def = _P2E["ensemble_def"]
    critics_spec = _P2E["critics_spec"]
    wm_cfg = cfg.algo.world_model
    stoch_flat = wm_cfg.stochastic_size * wm_cfg.discrete_size
    recurrent_size = wm_cfg.recurrent_model.recurrent_state_size
    horizon = cfg.algo.horizon
    gamma = cfg.algo.gamma
    cnn_dec_keys = list(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = list(cfg.algo.mlp_keys.decoder)
    weights_sum = sum(w for _, w, _ in critics_spec)
    intrinsic_mult = cfg.algo.intrinsic_reward_multiplier
    # chunked sequence-parallel RSSM scan + unroll lever (inherited from the
    # shared DV3 config surface — see dreamer_v3.py::make_train_step)
    scan_unroll = int(cfg.algo.get("scan_unroll", 1))
    rssm_chunks, rssm_burn_in = rssm_scan_spec(cfg)

    def ensembles_apply(ens_params, x):
        return jax.vmap(lambda p: ensemble_def.apply(p, x))(ens_params)

    def imagine(wm_params, actor_params, posteriors, recurrents, k_a0, k_img):
        """Imagination rollout shared by the exploration and task phases
        (reference :234-250 / :384-400): returns [H+1, TB, ...] latents and
        the actions taken."""
        latent0 = jnp.concatenate([posteriors, recurrents], axis=-1)
        a0 = actor_def.apply(actor_params, jax.lax.stop_gradient(latent0), k_a0, False, method="act")

        def img_body(carry, key_t):
            prior, recurrent, actions = carry
            k_dyn, k_act = jax.random.split(key_t)
            prior, recurrent = world_model_def.apply(
                wm_params, prior, recurrent, actions, k_dyn, method="imagination"
            )
            latent = jnp.concatenate([prior, recurrent], axis=-1)
            actions = actor_def.apply(
                actor_params, jax.lax.stop_gradient(latent), k_act, False, method="act"
            )
            return (prior, recurrent, actions), (latent, actions)

        keys_h = jax.random.split(k_img, horizon)
        _, (latents_h, actions_h) = jax.lax.scan(
            img_body, (posteriors, recurrents, a0), keys_h, unroll=scan_unroll
        )
        trajectories = jnp.concatenate([latent0[None], latents_h], axis=0)
        actions = jnp.concatenate([a0[None], actions_h], axis=0)
        return trajectories, actions

    def train_step(params, opt_states, moments_state, batch, key, tau):
        T, B = batch["actions"].shape[:2]
        key = fold_key(key, axis)
        k_wm, k_img_e, k_a0_e, k_img_t, k_a0_t = jax.random.split(key, 5)

        # --- target Polyak updates (task + every exploration critic,
        # reference :911-925) --------------------------------------------
        params["target_critic_task"] = jax.tree_util.tree_map(
            lambda c, t: tau * c + (1 - tau) * t, params["critic_task"], params["target_critic_task"]
        )
        for name, _, _ in critics_spec:
            c = params["critics_exploration"][name]
            c["target_module"] = jax.tree_util.tree_map(
                lambda cm, tm: tau * cm + (1 - tau) * tm, c["module"], c["target_module"]
            )

        target_obs = {k: batch[k] for k in set(cnn_dec_keys + mlp_dec_keys)}  # fp32 targets
        batch_obs = cast_floating(target_obs, cdt)
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0
        ).astype(cdt)
        is_first = batch["is_first"].at[0].set(1.0).astype(cdt)

        # ---------------- 1) DYNAMIC LEARNING (as DV3) --------------------
        def wm_loss_fn(wm_params):
            wm_params = cast_floating(wm_params, cdt)
            embedded = world_model_def.apply(wm_params, batch_obs, method="encode")

            recurrents, posteriors, post_logits, prior_logits = dynamic_learning_scan(
                world_model_def,
                wm_params,
                batch_actions,
                embedded,
                is_first,
                k_wm,
                stoch_flat=stoch_flat,
                recurrent_size=recurrent_size,
                cdt=cdt,
                chunks=rssm_chunks,
                burn_in=rssm_burn_in,
                stored_recurrent=batch.get("rssm_recurrent"),
                stored_posterior=batch.get("rssm_posterior"),
                stored_valid=batch.get("rssm_valid"),
                unroll=scan_unroll,
            )
            latents = jnp.concatenate([posteriors, recurrents], axis=-1)
            recon = world_model_def.apply(wm_params, latents, method="decode")
            po = {k: MSEDistribution(recon[k], dims=len(recon[k].shape[2:])) for k in cnn_dec_keys}
            po.update(
                {k: SymlogDistribution(recon[k], dims=len(recon[k].shape[2:])) for k in mlp_dec_keys}
            )
            pr = TwoHotEncodingDistribution(
                world_model_def.apply(wm_params, latents, method="reward_logits"), dims=1
            )
            pc = Bernoulli(
                world_model_def.apply(wm_params, latents, method="continue_logits"), event_dims=1
            )
            continues_targets = 1 - batch["terminated"]
            pl = prior_logits.reshape(T, B, wm_cfg.stochastic_size, wm_cfg.discrete_size)
            ql = post_logits.reshape(T, B, wm_cfg.stochastic_size, wm_cfg.discrete_size)
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                po,
                target_obs,
                pr,
                batch["rewards"],
                pl,
                ql,
                wm_cfg.kl_dynamic,
                wm_cfg.kl_representation,
                wm_cfg.kl_free_nats,
                wm_cfg.kl_regularizer,
                pc,
                continues_targets,
                wm_cfg.continue_scale_factor,
            )
            aux = {
                "posteriors": posteriors,
                "recurrents": recurrents,
                "kl": kl,
                "state_loss": state_loss,
                "reward_loss": reward_loss,
                "observation_loss": observation_loss,
                "continue_loss": continue_loss,
            }
            return rec_loss, aux

        (rec_loss, aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["world_model"])
        wm_grads = pmean_tree(wm_grads, axis)
        updates, opt_states["world_model"] = optimizers["world_model"].update(
            wm_grads, opt_states["world_model"], params["world_model"]
        )
        params["world_model"] = optax.apply_updates(params["world_model"], updates)
        wm_params = cast_floating(params["world_model"], cdt)

        posteriors = jax.lax.stop_gradient(aux["posteriors"])  # [T, B, S]
        recurrents = jax.lax.stop_gradient(aux["recurrents"])  # [T, B, R]

        # ---------------- 2) ENSEMBLE LEARNING (reference :207-231) -------
        def ens_loss_fn(ens_params):
            inp = jnp.concatenate([posteriors, recurrents, batch["actions"].astype(cdt)], axis=-1)
            outs = ensembles_apply(cast_floating(ens_params, cdt), inp)[:, :-1]  # [N, T-1, B, S]
            target = posteriors[1:]
            # sum over ensemble members of the MSE "log prob" loss
            lp = MSEDistribution(outs, dims=1).log_prob(
                jnp.broadcast_to(target[None], outs.shape)
            )  # [N, T-1, B]
            return -jnp.mean(lp, axis=(1, 2)).sum()

        ens_loss, ens_grads = jax.value_and_grad(ens_loss_fn)(params["ensembles"])
        ens_grads = pmean_tree(ens_grads, axis)
        updates, opt_states["ensembles"] = optimizers["ensembles"].update(
            ens_grads, opt_states["ensembles"], params["ensembles"]
        )
        params["ensembles"] = optax.apply_updates(params["ensembles"], updates)

        flat_post = posteriors.reshape(T * B, stoch_flat)
        flat_rec = recurrents.reshape(T * B, recurrent_size)
        true_continue = (1 - batch["terminated"]).reshape(T * B, 1)

        # ---------------- 3) EXPLORATION BEHAVIOUR (reference :233-343) ----
        def actor_expl_loss_fn(actor_params, moments_expl):
            actor_params = cast_floating(actor_params, cdt)
            trajectories, actions = imagine(wm_params, actor_params, flat_post, flat_rec, k_a0_e, k_img_e)
            continues = Bernoulli(
                world_model_def.apply(wm_params, trajectories, method="continue_logits"), event_dims=1
            ).mode
            continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)
            discount = jax.lax.stop_gradient(jnp.cumprod(continues * gamma, axis=0) / gamma)

            # intrinsic reward: ensemble disagreement (unbiased variance as
            # torch's Tensor.var, reference :259-263)
            ens_in = jax.lax.stop_gradient(jnp.concatenate([trajectories, actions], axis=-1))
            preds = ensembles_apply(cast_floating(params["ensembles"], cdt), ens_in).astype(
                jnp.float32
            )  # [N, H+1, TB, S]; disagreement variance in fp32
            intrinsic_reward = (
                jnp.var(preds, axis=0, ddof=1).mean(-1, keepdims=True) * intrinsic_mult
            )
            task_reward = TwoHotEncodingDistribution(
                world_model_def.apply(wm_params, trajectories, method="reward_logits"), dims=1
            ).mean

            advantage = 0.0
            new_moments = {}
            critic_aux = {}
            for name, weight, reward_type in critics_spec:
                values = TwoHotEncodingDistribution(
                    critic_def.apply(
                        cast_floating(params["critics_exploration"][name]["module"], cdt), trajectories
                    ),
                    dims=1,
                ).mean
                reward = intrinsic_reward if reward_type == "intrinsic" else task_reward
                lam = compute_lambda_values(
                    reward[1:], values[1:], continues[1:] * gamma, lmbda=cfg.algo.lmbda
                )
                offset, invscale, new_moments[name] = update_moments(
                    moments_expl[name],
                    lam,
                    cfg.algo.actor.moments.decay,
                    cfg.algo.actor.moments.max,
                    cfg.algo.actor.moments.percentile.low,
                    cfg.algo.actor.moments.percentile.high,
                    axis_name=axis,
                )
                baseline = values[:-1]
                advantage = advantage + ((lam - offset) / invscale - (baseline - offset) / invscale) * (
                    weight / weights_sum
                )
                critic_aux[name] = {
                    "lambda_values": jax.lax.stop_gradient(lam),
                    "predicted_values": jnp.mean(jax.lax.stop_gradient(values)),
                    "intrinsic_reward": jnp.mean(jax.lax.stop_gradient(reward)),
                }

            log_probs, entropies = actor_def.apply(
                actor_params,
                jax.lax.stop_gradient(trajectories),
                jax.lax.stop_gradient(actions),
                method="log_prob_entropy",
            )
            if is_continuous:
                objective = advantage
            else:
                objective = log_probs[:-1] * jax.lax.stop_gradient(advantage)
            entropy = cfg.algo.actor.ent_coef * entropies
            loss = -jnp.mean(discount[:-1] * (objective + entropy[:-1]))
            aux2 = {
                "trajectories": jax.lax.stop_gradient(trajectories),
                "discount": discount,
                "moments": new_moments,
                "critic_aux": critic_aux,
            }
            return loss, aux2

        (policy_loss_expl, aux_e), actor_expl_grads = jax.value_and_grad(actor_expl_loss_fn, has_aux=True)(
            params["actor_exploration"], moments_state["exploration"]
        )
        actor_expl_grads = pmean_tree(actor_expl_grads, axis)
        updates, opt_states["actor_exploration"] = optimizers["actor_exploration"].update(
            actor_expl_grads, opt_states["actor_exploration"], params["actor_exploration"]
        )
        params["actor_exploration"] = optax.apply_updates(params["actor_exploration"], updates)
        moments_state["exploration"] = aux_e["moments"]

        # ---------------- 4) EXPLORATION CRITICS (reference :345-372) ------
        expl_traj = aux_e["trajectories"]
        expl_discount = aux_e["discount"]
        critic_metrics = []
        for name, _, reward_type in critics_spec:
            lam = aux_e["critic_aux"][name]["lambda_values"]

            def critic_loss_fn(critic_params):
                qv = TwoHotEncodingDistribution(
                    critic_def.apply(cast_floating(critic_params, cdt), expl_traj[:-1]), dims=1
                )
                target_vals = TwoHotEncodingDistribution(
                    critic_def.apply(
                        cast_floating(params["critics_exploration"][name]["target_module"], cdt),
                        expl_traj[:-1],
                    ),
                    dims=1,
                ).mean
                loss = -qv.log_prob(lam) - qv.log_prob(jax.lax.stop_gradient(target_vals))
                return jnp.mean(loss * expl_discount[:-1, ..., 0])

            vloss, cgrads = jax.value_and_grad(critic_loss_fn)(
                params["critics_exploration"][name]["module"]
            )
            cgrads = pmean_tree(cgrads, axis)
            updates, opt_states["critics_exploration"][name] = optimizers["critics_exploration"].update(
                cgrads, opt_states["critics_exploration"][name], params["critics_exploration"][name]["module"]
            )
            params["critics_exploration"][name]["module"] = optax.apply_updates(
                params["critics_exploration"][name]["module"], updates
            )
            critic_metrics.append(vloss)
            critic_metrics.append(aux_e["critic_aux"][name]["predicted_values"])
            critic_metrics.append(jnp.mean(lam))
            if reward_type == "intrinsic":
                critic_metrics.append(aux_e["critic_aux"][name]["intrinsic_reward"])

        # ---------------- 5) TASK BEHAVIOUR (zero-shot, reference :384-470) -
        def actor_task_loss_fn(actor_params, moments_task):
            actor_params = cast_floating(actor_params, cdt)
            trajectories, actions = imagine(wm_params, actor_params, flat_post, flat_rec, k_a0_t, k_img_t)
            predicted_values = TwoHotEncodingDistribution(
                critic_def.apply(cast_floating(params["critic_task"], cdt), trajectories), dims=1
            ).mean
            predicted_rewards = TwoHotEncodingDistribution(
                world_model_def.apply(wm_params, trajectories, method="reward_logits"), dims=1
            ).mean
            continues = Bernoulli(
                world_model_def.apply(wm_params, trajectories, method="continue_logits"), event_dims=1
            ).mode
            continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)
            lam = compute_lambda_values(
                predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda=cfg.algo.lmbda
            )
            discount = jax.lax.stop_gradient(jnp.cumprod(continues * gamma, axis=0) / gamma)
            offset, invscale, new_moments = update_moments(
                moments_task,
                lam,
                cfg.algo.actor.moments.decay,
                cfg.algo.actor.moments.max,
                cfg.algo.actor.moments.percentile.low,
                cfg.algo.actor.moments.percentile.high,
                axis_name=axis,
            )
            baseline = predicted_values[:-1]
            advantage = (lam - offset) / invscale - (baseline - offset) / invscale
            log_probs, entropies = actor_def.apply(
                actor_params,
                jax.lax.stop_gradient(trajectories),
                jax.lax.stop_gradient(actions),
                method="log_prob_entropy",
            )
            if is_continuous:
                objective = advantage
            else:
                objective = log_probs[:-1] * jax.lax.stop_gradient(advantage)
            entropy = cfg.algo.actor.ent_coef * entropies
            loss = -jnp.mean(discount[:-1] * (objective + entropy[:-1]))
            aux3 = {
                "trajectories": jax.lax.stop_gradient(trajectories),
                "lambda_values": jax.lax.stop_gradient(lam),
                "discount": discount,
                "moments": new_moments,
            }
            return loss, aux3

        (policy_loss_task, aux_t), actor_task_grads = jax.value_and_grad(actor_task_loss_fn, has_aux=True)(
            params["actor_task"], moments_state["task"]
        )
        actor_task_grads = pmean_tree(actor_task_grads, axis)
        updates, opt_states["actor_task"] = optimizers["actor_task"].update(
            actor_task_grads, opt_states["actor_task"], params["actor_task"]
        )
        params["actor_task"] = optax.apply_updates(params["actor_task"], updates)
        moments_state["task"] = aux_t["moments"]

        def critic_task_loss_fn(critic_params):
            qv = TwoHotEncodingDistribution(
                critic_def.apply(cast_floating(critic_params, cdt), aux_t["trajectories"][:-1]), dims=1
            )
            target_vals = TwoHotEncodingDistribution(
                critic_def.apply(cast_floating(params["target_critic_task"], cdt), aux_t["trajectories"][:-1]),
                dims=1,
            ).mean
            loss = -qv.log_prob(aux_t["lambda_values"]) - qv.log_prob(jax.lax.stop_gradient(target_vals))
            return jnp.mean(loss * aux_t["discount"][:-1, ..., 0])

        value_loss_task, critic_task_grads = jax.value_and_grad(critic_task_loss_fn)(params["critic_task"])
        critic_task_grads = pmean_tree(critic_task_grads, axis)
        updates, opt_states["critic_task"] = optimizers["critic_task"].update(
            critic_task_grads, opt_states["critic_task"], params["critic_task"]
        )
        params["critic_task"] = optax.apply_updates(params["critic_task"], updates)

        metrics = jnp.stack(
            [
                rec_loss,
                aux["observation_loss"],
                aux["reward_loss"],
                aux["state_loss"],
                aux["continue_loss"],
                aux["kl"],
                ens_loss,
                policy_loss_expl,
                policy_loss_task,
                value_loss_task,
                optax.global_norm(wm_grads),
                optax.global_norm(ens_grads),
                optax.global_norm(actor_expl_grads),
                optax.global_norm(actor_task_grads),
                optax.global_norm(critic_task_grads),
                *critic_metrics,
            ]
        )
        metrics = pmean_tree(metrics, axis)
        return params, opt_states, moments_state, metrics

    from sheeprl_tpu.parallel.dp import fsdp_min_shard_bytes

    return dp_jit(
        train_step,
        mesh,
        in_specs=(P(), P(), P(), batch_spec(batch_axis=1), P(), P()),
        out_specs=(P(), P(), P(), P()),
        donate_argnums=(0, 1, 2),
        min_shard_bytes=fsdp_min_shard_bytes(cfg),
    )


def _build_agent(runtime, actions_dim, is_continuous, cfg, obs_space, state):
    world_model_def, actor_def, critic_def, ensemble_def, params, critics_spec = build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        obs_space,
        state["world_model"] if state else None,
        state["ensembles"] if state else None,
        state["actor_task"] if state else None,
        state["critic_task"] if state else None,
        state["target_critic_task"] if state else None,
        state["actor_exploration"] if state else None,
        state["critics_exploration"] if state else None,
    )
    _P2E["ensemble_def"] = ensemble_def
    _P2E["critics_spec"] = critics_spec
    return world_model_def, actor_def, critic_def, params


def _make_optimizers(cfg, params, agent_state):
    """World/actor_task/critic_task/actor_exploration/ensembles optimizers +
    one shared-definition optimizer per exploration critic
    (reference p2e_dv3_exploration.py:617-660)."""
    chain = lambda clip, opt_cfg: optax.chain(  # noqa: E731
        optax.clip_by_global_norm(clip), instantiate(opt_cfg)
    )
    optimizers = {
        "world_model": chain(cfg.algo.world_model.clip_gradients, cfg.algo.world_model.optimizer),
        "actor_task": chain(cfg.algo.actor.clip_gradients, cfg.algo.actor.optimizer),
        "critic_task": chain(cfg.algo.critic.clip_gradients, cfg.algo.critic.optimizer),
        "actor_exploration": chain(cfg.algo.actor.clip_gradients, cfg.algo.actor.optimizer),
        "ensembles": chain(cfg.algo.ensembles.clip_gradients, cfg.algo.ensembles.optimizer),
        # the reference instantiates each exploration-critic optimizer from
        # cfg.algo.critic.optimizer (p2e_dv3_exploration.py:623-626)
        "critics_exploration": chain(cfg.algo.critic.clip_gradients, cfg.algo.critic.optimizer),
    }
    opt_states = {
        "world_model": optimizers["world_model"].init(params["world_model"]),
        "actor_task": optimizers["actor_task"].init(params["actor_task"]),
        "critic_task": optimizers["critic_task"].init(params["critic_task"]),
        "actor_exploration": optimizers["actor_exploration"].init(params["actor_exploration"]),
        "ensembles": optimizers["ensembles"].init(params["ensembles"]),
        "critics_exploration": {
            k: optimizers["critics_exploration"].init(v["module"])
            for k, v in params["critics_exploration"].items()
        },
    }
    if agent_state and "opt_states" in agent_state:
        opt_states = jax.tree_util.tree_map(
            lambda ref, saved: jnp.asarray(saved, dtype=getattr(ref, "dtype", None)),
            opt_states,
            agent_state["opt_states"],
        )
    return optimizers, opt_states


def _init_moments(cfg, agent_state):
    critics_spec = _P2E["critics_spec"]
    moments = {
        "task": init_moments_state(),
        "exploration": {name: init_moments_state() for name, _, _ in critics_spec},
    }
    if agent_state and "moments" in agent_state:
        moments = jax.tree_util.tree_map(jnp.asarray, agent_state["moments"])
    return moments


def _player_actor(cfg):
    actor_type = cfg.algo.player.actor_type

    def fn(params, has_trained):
        return params["actor_exploration"] if actor_type == "exploration" else params["actor_task"]

    return fn


def _zero_shot_test(player, params, runtime, cfg, log_dir):
    """Final task test with the *task* actor (reference :1032-1037)."""
    return test(
        player, params["world_model"], params["actor_task"], runtime, cfg, log_dir, "zero-shot", greedy=False
    )


@register_algorithm()
def main(runtime, cfg):
    # exploration always plays with the exploration actor (reference :530)
    cfg.algo.player.actor_type = "exploration"
    from sheeprl_tpu.algos.p2e_dv3.agent import exploration_critics_spec

    critics_spec = exploration_critics_spec(cfg)
    expand_exploration_metric_keys(cfg, [name for name, _, _ in critics_spec])
    return _dreamer_main(
        runtime,
        cfg,
        _build_agent,
        make_train_step,
        make_optimizers_fn=_make_optimizers,
        init_moments_fn=_init_moments,
        player_actor_fn=_player_actor(cfg),
        metric_order=metric_order(critics_spec),
        final_test_fn=_zero_shot_test,
    )
