"""DreamerV3-JEPA training loop (fork feature, reference
/root/reference/sheeprl/algos/dreamer_v3_jepa/dreamer_v3_jepa.py:100-909).

DV3 with a decoder-optional world model and a JEPA auxiliary loss on the
encoder: two masked views of the batch are encoded (online vs EMA-target
branch) and a cosine prediction loss (weight ``jepa_coef``) is added to the
world-model objective; the target encoder/projector track the online ones
with momentum ``jepa_ema`` (reference :230-246).  The JEPA projector and
predictor train under the world-model optimizer, exactly like the reference
attaches the head to the WorldModel module (agent.py:96).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import optax

from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _dreamer_main
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import dynamic_learning_scan, rssm_scan_spec, update_moments
from sheeprl_tpu.algos.dreamer_v3_jepa.agent import build_agent as _build_agent_full, encoder_subtree
from sheeprl_tpu.algos.dreamer_v3_jepa.utils import AGGREGATOR_KEYS, MODELS_TO_REGISTER  # noqa: F401
from sheeprl_tpu.models.jepa import jepa_loss, make_two_views
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

_HEADS = {}  # filled by the wrapped build_agent; keyed per-process (single controller)


def _build_agent(runtime, actions_dim, is_continuous, cfg, obs_space, state):
    world_model_def, actor_def, critic_def, head_defs, params = _build_agent_full(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        obs_space,
        state["world_model"] if state else None,
        state["actor"] if state else None,
        state["critic"] if state else None,
        state["target_critic"] if state else None,
    )
    _HEADS["projector_def"], _HEADS["predictor_def"] = head_defs
    if state and "jepa" in state:
        import jax as _jax

        params["jepa"] = _jax.tree_util.tree_map(jnp.asarray, state["jepa"])
    return world_model_def, actor_def, critic_def, params


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
    wm_cfg = cfg.algo.world_model
    stoch_flat = wm_cfg.stochastic_size * wm_cfg.discrete_size
    recurrent_size = wm_cfg.recurrent_model.recurrent_state_size
    horizon = cfg.algo.horizon
    gamma = cfg.algo.gamma
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_dec_keys = list(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = list(cfg.algo.mlp_keys.decoder)
    jepa_coef = cfg.algo.jepa_coef
    ema_m = cfg.algo.jepa_ema
    # chunked sequence-parallel RSSM scan + unroll lever (inherited from the
    # shared DV3 config surface — see dreamer_v3.py::make_train_step)
    scan_unroll = int(cfg.algo.get("scan_unroll", 1))
    rssm_chunks, rssm_burn_in = rssm_scan_spec(cfg)
    projector_def = _HEADS["projector_def"]
    predictor_def = _HEADS["predictor_def"]

    from sheeprl_tpu.diagnostics.health import health_spec, health_stats
    from sheeprl_tpu.diagnostics.sentinel import select_finite, sentinel_spec

    sentinel = sentinel_spec(cfg)
    health = health_spec(cfg)

    def train_step(params, opt_states, moments_state, batch, key, tau):
        T, B = batch["actions"].shape[:2]
        key = fold_key(key, axis)
        k_wm, k_img, k_img_actions, k_views = jax.random.split(key, 4)

        # sentinel snapshots for the skip_update guard at the end.  tree_map
        # rebuilds every container (leaves shared): a plain dict(params) would
        # alias the nested params["jepa"] dict, which IS mutated in place
        # below, and the guard could never revert the JEPA heads
        if sentinel.skip_update:
            copy = lambda tree: jax.tree_util.tree_map(lambda leaf: leaf, tree)  # noqa: E731
            prev_state = (copy(params), copy(opt_states), moments_state)

        params["target_critic"] = jax.tree_util.tree_map(
            lambda c, t: tau * c + (1 - tau) * t, params["critic"], params["target_critic"]
        )

        target_obs = {k: batch[k] for k in set(cnn_keys + mlp_keys)}  # fp32 targets
        batch_obs = cast_floating(target_obs, cdt)
        # JEPA views need (T,B,C,H,W) pixels / (T,B,D) vectors
        view_obs = {k: batch_obs[k] for k in batch_obs}
        obs_q, obs_k = make_two_views(
            view_obs, k_views, cfg.algo.jepa_mask.erase_frac, cfg.algo.jepa_mask.vec_dropout
        )
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0
        ).astype(cdt)
        is_first = batch["is_first"].at[0].set(1.0).astype(cdt)

        def wm_loss_fn(combined):
            wm_params, jepa_online = combined
            wm_params = cast_floating(wm_params, cdt)
            jepa_online = cast_floating(jepa_online, cdt)
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
            po.update({k: SymlogDistribution(recon[k], dims=len(recon[k].shape[2:])) for k in mlp_dec_keys})
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
                {k: target_obs[k] for k in set(cnn_dec_keys + mlp_dec_keys)},
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
            # --- JEPA auxiliary objective (reference :230-231) ------------
            jl = jepa_loss(
                lambda o: world_model_def.apply(wm_params, o, method="encode"),
                lambda o: world_model_def.apply(
                    cast_floating(params["jepa"]["target_encoder"], cdt), o, method="encode"
                ),
                projector_def,
                predictor_def,
                jepa_online["projector"],
                jepa_online["predictor"],
                cast_floating(params["jepa"]["target_projector"], cdt),
                obs_q,
                obs_k,
            )
            total = rec_loss + jepa_coef * jl
            aux = {
                "posteriors": posteriors,
                "recurrents": recurrents,
                "kl": kl,
                "state_loss": state_loss,
                "reward_loss": reward_loss,
                "observation_loss": observation_loss,
                "continue_loss": continue_loss,
                "jepa_loss": jl,
                "rec_loss": rec_loss,
            }
            return total, aux

        jepa_online = {"projector": params["jepa"]["projector"], "predictor": params["jepa"]["predictor"]}
        (total_loss, aux), grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(
            (params["world_model"], jepa_online)
        )
        grads = pmean_tree(grads, axis)
        wm_updates, opt_states["world_model"] = optimizers["world_model"].update(
            grads, opt_states["world_model"], (params["world_model"], jepa_online)
        )
        (params["world_model"], jepa_online) = optax.apply_updates(
            (params["world_model"], jepa_online), wm_updates
        )
        params["jepa"]["projector"] = jepa_online["projector"]
        params["jepa"]["predictor"] = jepa_online["predictor"]

        # --- JEPA momentum update (reference :245-246) ---------------------
        params["jepa"]["target_encoder"] = optax.incremental_update(
            encoder_subtree(params["world_model"]), params["jepa"]["target_encoder"], 1 - ema_m
        )
        params["jepa"]["target_projector"] = optax.incremental_update(
            params["jepa"]["projector"], params["jepa"]["target_projector"], 1 - ema_m
        )

        # ---------------- BEHAVIOUR LEARNING (same as DV3) -----------------
        wm_params = cast_floating(params["world_model"], cdt)
        posteriors = jax.lax.stop_gradient(aux["posteriors"]).reshape(T * B, stoch_flat)
        recurrents = jax.lax.stop_gradient(aux["recurrents"]).reshape(T * B, recurrent_size)
        true_continue = (1 - batch["terminated"]).reshape(T * B, 1)

        def actor_loss_fn(actor_params, moments_state):
            actor_params = cast_floating(actor_params, cdt)
            latent0 = jnp.concatenate([posteriors, recurrents], axis=-1)
            a0 = actor_def.apply(actor_params, jax.lax.stop_gradient(latent0), k_img_actions, False, method="act")

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
            imagined_trajectories = jnp.concatenate([latent0[None], latents_h], axis=0)
            imagined_actions = jnp.concatenate([a0[None], actions_h], axis=0)

            predicted_values = TwoHotEncodingDistribution(
                critic_def.apply(cast_floating(params["critic"], cdt), imagined_trajectories), dims=1
            ).mean
            predicted_rewards = TwoHotEncodingDistribution(
                world_model_def.apply(wm_params, imagined_trajectories, method="reward_logits"), dims=1
            ).mean
            continues = Bernoulli(
                world_model_def.apply(wm_params, imagined_trajectories, method="continue_logits"),
                event_dims=1,
            ).mode
            continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)

            lambda_values = compute_lambda_values(
                predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda=cfg.algo.lmbda
            )
            discount = jnp.cumprod(continues * gamma, axis=0) / gamma
            discount = jax.lax.stop_gradient(discount)
            baseline = predicted_values[:-1]
            offset, invscale, new_moments = update_moments(
                moments_state,
                lambda_values,
                cfg.algo.actor.moments.decay,
                cfg.algo.actor.moments.max,
                cfg.algo.actor.moments.percentile.low,
                cfg.algo.actor.moments.percentile.high,
                axis_name=axis,
            )
            advantage = (lambda_values - offset) / invscale - (baseline - offset) / invscale
            log_probs, entropies = actor_def.apply(
                actor_params,
                jax.lax.stop_gradient(imagined_trajectories),
                jax.lax.stop_gradient(imagined_actions),
                method="log_prob_entropy",
            )
            if is_continuous:
                objective = advantage
            else:
                objective = log_probs[:-1] * jax.lax.stop_gradient(advantage)
            entropy = cfg.algo.actor.ent_coef * entropies
            policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[:-1]))
            aux2 = {
                "imagined_trajectories": jax.lax.stop_gradient(imagined_trajectories),
                "lambda_values": jax.lax.stop_gradient(lambda_values),
                "discount": discount,
                "moments": new_moments,
            }
            return policy_loss, aux2

        (policy_loss, aux2), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"], moments_state
        )
        actor_grads = pmean_tree(actor_grads, axis)
        actor_updates, opt_states["actor"] = optimizers["actor"].update(
            actor_grads, opt_states["actor"], params["actor"]
        )
        params["actor"] = optax.apply_updates(params["actor"], actor_updates)
        moments_state = aux2["moments"]

        imagined_trajectories = aux2["imagined_trajectories"]
        lambda_values = aux2["lambda_values"]
        discount = aux2["discount"]

        def critic_loss_fn(critic_params):
            qv = TwoHotEncodingDistribution(
                critic_def.apply(cast_floating(critic_params, cdt), imagined_trajectories[:-1]), dims=1
            )
            predicted_target_values = TwoHotEncodingDistribution(
                critic_def.apply(cast_floating(params["target_critic"], cdt), imagined_trajectories[:-1]),
                dims=1,
            ).mean
            value_loss = -qv.log_prob(lambda_values)
            value_loss = value_loss - qv.log_prob(jax.lax.stop_gradient(predicted_target_values))
            return jnp.mean(value_loss * discount[:-1, ..., 0])

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        critic_grads = pmean_tree(critic_grads, axis)
        critic_updates, opt_states["critic"] = optimizers["critic"].update(
            critic_grads, opt_states["critic"], params["critic"]
        )
        params["critic"] = optax.apply_updates(params["critic"], critic_updates)

        metrics = jnp.stack(
            [
                aux["rec_loss"] + jepa_coef * aux["jepa_loss"],
                aux["observation_loss"],
                aux["reward_loss"],
                aux["state_loss"],
                aux["continue_loss"],
                aux["kl"],
                policy_loss,
                value_loss,
                optax.global_norm(grads[0]),
                optax.global_norm(actor_grads),
                optax.global_norm(critic_grads),
            ]
        )
        metrics = pmean_tree(metrics, axis)
        # learn-health stats: the JEPA heads are their own top-level module
        # (grads[1] / wm_updates[1] are the online projector+predictor); all
        # inputs are pmean'd/replicated so the dict rides the metric drain's
        # batched fetch unchanged across devices
        if health.enabled:
            hstats = health_stats(
                {
                    "world_model": grads[0],
                    "jepa": grads[1],
                    "actor": actor_grads,
                    "critic": critic_grads,
                },
                {
                    "world_model": wm_updates[0],
                    "jepa": wm_updates[1],
                    "actor": actor_updates,
                    "critic": critic_updates,
                },
                {
                    "world_model": params["world_model"],
                    "jepa": {
                        "projector": params["jepa"]["projector"],
                        "predictor": params["jepa"]["predictor"],
                    },
                    "actor": params["actor"],
                    "critic": params["critic"],
                },
                per_module=health.per_module,
                dead_eps=health.dead_eps,
            )
        else:
            hstats = {}
        if sentinel.skip_update:
            finite = jnp.all(jnp.isfinite(metrics))
            params, opt_states, moments_state = select_finite(
                finite, (params, opt_states, moments_state), prev_state
            )
        return params, opt_states, moments_state, metrics, hstats

    from sheeprl_tpu.parallel.dp import fsdp_min_shard_bytes

    return dp_jit(
        train_step,
        mesh,
        in_specs=(P(), P(), P(), batch_spec(batch_axis=1), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        donate_argnums=(0, 1, 2),
        min_shard_bytes=fsdp_min_shard_bytes(cfg),
    )


def _extra_opt_setup(optimizers, opt_states, params):
    """The world optimizer also trains the JEPA projector/predictor
    (reference: jepa head is attached to the WorldModel module)."""
    jepa_online = {"projector": params["jepa"]["projector"], "predictor": params["jepa"]["predictor"]}
    opt_states["world_model"] = optimizers["world_model"].init((params["world_model"], jepa_online))
    return opt_states


@register_algorithm()
def main(runtime, cfg):
    return _dreamer_main(runtime, cfg, _build_agent, make_train_step, extra_opt_setup=_extra_opt_setup)
