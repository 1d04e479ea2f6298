"""DreamerV3 training loop — TPU-native re-design of
/root/reference/sheeprl/algos/dreamer_v3/dreamer_v3.py:48-830.

The reference's two hot loops are Python ``for`` loops over GRU cells
(dynamic learning over T≈64 steps, dreamer_v3.py:134-145; imagination over
H=15, :235-241).  Here each gradient step is ONE jitted XLA graph:

- dynamic learning = `lax.scan` over the sequence axis, whose body holds only
  what depends on its carry `(posterior, recurrent)`: the state's half of the
  recurrent and representation models' input products, the LayerNorm-GRU and
  the representation head.  The action's and the embedded observation's
  halves of those products, the learned initial state, the draws' noise
  (before the loop) and the whole prior head (after it, on the stacked
  recurrent states) run once on all T x B rows, and so do, after the
  transposed loop, the gradients of the four kernels the body multiplies by
  (`utils.py::dynamic_learning_scan`, shared with the JEPA and P2E steps);
- imagination = `lax.scan` over the horizon **inside the actor loss**, so
  gradients flow through the dynamics for continuous control exactly as the
  reference's autograd tape does;
- the three optimizer updates (world/actor/critic), the Moments percentile
  EMA and the target-critic Polyak update all live in the same graph;
- data-parallelism is `shard_map` over the 1-D ``"data"`` mesh axis: the
  batch enters sharded ``P(None, "data")`` (time × **sharded batch**), params
  replicated; the three gradient pytrees are explicitly `lax.pmean`-reduced
  before their optimizer updates and the Moments quantile runs on the
  `lax.all_gather`-ed lambda values (reference `fabric.all_gather` in
  Moments, utils.py:56-64).  Per-device batch math: each device computes
  ``per_rank_batch_size`` of the staged ``per_rank_batch_size * world_size``
  sequences, so adding devices scales global batch exactly like reference
  DDP ranks.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3, build_agent
from sheeprl_tpu.algos.dreamer_v3.loop_order import TRAIN_FIRST, LoopOrder
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (  # noqa: F401
    AGGREGATOR_KEYS,
    MODELS_TO_REGISTER,
    dynamic_learning_scan,
    init_moments_state,
    prepare_obs,
    rssm_scan_spec,
    test,
    update_moments,
)
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.factory import make_dreamer_replay_buffer
from sheeprl_tpu.diagnostics.health import mean_stats
from sheeprl_tpu.data.slab import rssm_state_slab, step_slab
from sheeprl_tpu.envs.env import make_env_fns, pipelined_vector_env
from sheeprl_tpu.envs.player import fetch_values, obs_sharding
from sheeprl_tpu.ops.distributions import (
    Bernoulli,
    MSEDistribution,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.ops.numerics import compute_lambda_values
from sheeprl_tpu.parallel.dp import P, batch_spec, dp_axis, dp_jit, fold_key, pmean_tree, train_batches, local_sample_size
from sheeprl_tpu.parallel.precision import cast_floating, compute_dtype_of
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import DeviceMetricsDrain, MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, get_diagnostics, save_configs


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
    """Build the jitted single-gradient-step update.

    Signature: (params, opt_states, moments_state, batch, key, tau) ->
    (params, opt_states, moments_state, metrics_vec).
    ``batch`` leaves are [T, B, ...] float arrays (pixels already in [-0.5, .5]).
    With a >1-device ``mesh`` the step is shard_map'd: B is sharded over
    ``"data"``, grads pmean'd, Moments quantiles all-gathered.
    """
    axis = dp_axis(mesh)
    cdt = compute_dtype_of(cfg)  # bf16 under fabric.precision=bf16-*
    wm_cfg = cfg.algo.world_model
    stoch_flat = wm_cfg.stochastic_size * wm_cfg.discrete_size
    recurrent_size = wm_cfg.recurrent_model.recurrent_state_size
    horizon = cfg.algo.horizon
    # lax.scan unroll factor for the RSSM/imagination loops: unrolling
    # amortizes per-iteration scan overhead (one pre-PR-1 S-size sweep showed
    # ~6% at unroll=8; it has no chip verdict: ROADMAP S3's sweep on the
    # DV3 cells decides it and deletes what loses, PERF.md §5) at the cost
    # of ~unroll x longer compiles, so it defaults to 1 and is a
    # deploy-time knob.  Caveat: cost_analysis() FLOPs inflate under
    # unrolling, so compare step_ms — the telemetry_cost journal event
    # carries this caveat (cost_note) whenever unroll > 1.
    scan_unroll = int(cfg.algo.get("scan_unroll", 1))
    # chunked sequence-parallel RSSM scan (PERF.md §5): split the T-step
    # dynamic-learning scan into K chunks seeded from replay-stored states
    # and fold the chunk axis into the batch axis — the GRU GEMM then runs at
    # B*K rows.  rssm_chunks=1 is the sequential scan.
    rssm_chunks, rssm_burn_in = rssm_scan_spec(cfg)
    gamma = cfg.algo.gamma
    cnn_dec_keys = list(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = list(cfg.algo.mlp_keys.decoder)

    from sheeprl_tpu.diagnostics.health import health_spec, health_stats
    from sheeprl_tpu.diagnostics.sentinel import select_finite, sentinel_spec

    sentinel = sentinel_spec(cfg)
    health = health_spec(cfg)

    def train_step(params, opt_states, moments_state, batch, key, tau):
        T, B = batch["actions"].shape[:2]
        key = fold_key(key, axis)
        k_wm, k_img, k_img_actions = jax.random.split(key, 3)

        # sentinel snapshots: the skip_update guard at the end reverts to
        # these when the step's metric vector — which includes every loss and
        # grad norm — goes non-finite.  tree_map rebuilds every container
        # (leaves shared) so nested in-place mutation can never alias the
        # snapshot
        if sentinel.skip_update:
            copy = lambda tree: jax.tree_util.tree_map(lambda leaf: leaf, tree)  # noqa: E731
            prev_state = (copy(params), copy(opt_states), moments_state)

        def apply_optimizer(module, grads):
            with jax.named_scope("optim"):  # clip + Adam + apply, the same for the three modules
                updates, opt_states[module] = optimizers[module].update(grads, opt_states[module], params[module])
                params[module] = optax.apply_updates(params[module], updates)
            return updates

        # --- target critic Polyak update (reference dreamer_v3.py:713-720) --
        with jax.named_scope("optim"):
            params["target_critic"] = jax.tree_util.tree_map(
                lambda c, t: tau * c + (1 - tau) * t, params["critic"], params["target_critic"]
            )

        # loss-side targets stay fp32; the compute path runs in `cdt` via the
        # JMP-style casts at each loss entry (params + inputs -> cdt, flax
        # promotes, distributions upcast back to fp32 at the loss boundary)
        target_obs = {k: batch[k] for k in set(cnn_dec_keys + mlp_dec_keys)}  # fp32 targets
        batch_obs = cast_floating(target_obs, cdt)  # network input
        # shift actions right by one: a_0 = 0 (reference dreamer_v3.py:104-105)
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0
        ).astype(cdt)
        is_first = batch["is_first"].at[0].set(1.0).astype(cdt)

        # ---------------- DYNAMIC LEARNING ---------------------------------
        def wm_loss_fn(wm_params):
            wm_params = cast_floating(wm_params, cdt)
            with jax.named_scope("encoder"):
                embedded = world_model_def.apply(wm_params, batch_obs, method="encode")

            with jax.named_scope("rssm_scan"):
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
            with jax.named_scope("decoder_heads"):
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
                "post_logits": ql,
                "prior_logits": pl,
            }
            return rec_loss, aux

        (rec_loss, aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["world_model"])
        wm_grads = pmean_tree(wm_grads, axis)
        wm_updates = apply_optimizer("world_model", wm_grads)

        # ---------------- BEHAVIOUR LEARNING -------------------------------
        # (uses the freshly updated world model, like the reference)
        wm_params = cast_floating(params["world_model"], cdt)
        posteriors = jax.lax.stop_gradient(aux["posteriors"]).reshape(T * B, stoch_flat)
        recurrents = jax.lax.stop_gradient(aux["recurrents"]).reshape(T * B, recurrent_size)
        true_continue = (1 - batch["terminated"]).reshape(T * B, 1)

        def actor_loss_fn(actor_params, moments_state):
            actor_params = cast_floating(actor_params, cdt)
            with jax.named_scope("imagination"):
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
                _, (latents_h, actions_h) = jax.lax.scan(img_body, (posteriors, recurrents, a0), keys_h, unroll=scan_unroll)
                imagined_trajectories = jnp.concatenate([latent0[None], latents_h], axis=0)  # [H+1, TB, L]
                imagined_actions = jnp.concatenate([a0[None], actions_h], axis=0)

            with jax.named_scope("behaviour_losses"):
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
                normed_lambda_values = (lambda_values - offset) / invscale
                normed_baseline = (baseline - offset) / invscale
                advantage = normed_lambda_values - normed_baseline
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
        actor_updates = apply_optimizer("actor", actor_grads)
        moments_state = aux2["moments"]

        # ---------------- CRITIC LEARNING ----------------------------------
        imagined_trajectories = aux2["imagined_trajectories"]
        lambda_values = aux2["lambda_values"]
        discount = aux2["discount"]

        def critic_loss_fn(critic_params):
            with jax.named_scope("behaviour_losses"):
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
        critic_updates = apply_optimizer("critic", critic_grads)

        metrics = jnp.stack(
            [
                rec_loss,
                aux["observation_loss"],
                aux["reward_loss"],
                aux["state_loss"],
                aux["continue_loss"],
                aux["kl"],
                policy_loss,
                value_loss,
                optax.global_norm(wm_grads),
                optax.global_norm(actor_grads),
                optax.global_norm(critic_grads),
            ]
        )
        metrics = pmean_tree(metrics, axis)
        # learn-health stats over the three module trees: the grads are
        # already pmean'd and updates/params are replicated, so the dict is
        # identical on every device and rides the metric drain's batched
        # fetch (zero extra syncs; {} when diagnostics.health is off)
        if health.enabled:
            hstats = health_stats(
                {"world_model": wm_grads, "actor": actor_grads, "critic": critic_grads},
                {"world_model": wm_updates, "actor": actor_updates, "critic": critic_updates},
                {"world_model": params["world_model"], "actor": params["actor"], "critic": params["critic"]},
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


METRIC_ORDER = [
    "Loss/world_model_loss",
    "Loss/observation_loss",
    "Loss/reward_loss",
    "Loss/state_loss",
    "Loss/continue_loss",
    "State/kl",
    "Loss/policy_loss",
    "Loss/value_loss",
    "Grads/world_model",
    "Grads/actor",
    "Grads/critic",
]


def _build_agent_from_state(runtime, actions_dim, is_continuous, cfg, obs_space, state):
    return build_agent(
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


@register_algorithm()
def main(runtime, cfg):
    return _dreamer_main(runtime, cfg, _build_agent_from_state, make_train_step)


def _default_make_optimizers(cfg, params, agent_state, extra_opt_setup=None):
    """DV3's three optimizers (world/actor/critic) with generic restore."""
    optimizers = {
        "world_model": optax.chain(
            optax.clip_by_global_norm(cfg.algo.world_model.clip_gradients),
            instantiate(cfg.algo.world_model.optimizer),
        ),
        "actor": optax.chain(
            optax.clip_by_global_norm(cfg.algo.actor.clip_gradients),
            instantiate(cfg.algo.actor.optimizer),
        ),
        "critic": optax.chain(
            optax.clip_by_global_norm(cfg.algo.critic.clip_gradients),
            instantiate(cfg.algo.critic.optimizer),
        ),
    }
    opt_states = {
        "world_model": optimizers["world_model"].init(params["world_model"]),
        "actor": optimizers["actor"].init(params["actor"]),
        "critic": optimizers["critic"].init(params["critic"]),
    }
    if extra_opt_setup is not None:
        opt_states = extra_opt_setup(optimizers, opt_states, params)
    if agent_state and "opt_states" in agent_state:
        opt_states = jax.tree_util.tree_map(
            lambda ref, saved: jnp.asarray(saved, dtype=getattr(ref, "dtype", None)),
            opt_states,
            agent_state["opt_states"],
        )
    return optimizers, opt_states


def _dreamer_main(
    runtime,
    cfg,
    build_agent_fn,
    make_train_step_fn,
    extra_opt_setup=None,
    *,
    make_optimizers_fn=None,
    init_moments_fn=None,
    player_actor_fn=None,
    metric_order=None,
    final_test_fn=None,
    load_agent_state_fn=None,
    player_cls=PlayerDV3,
):
    """Shared Dreamer-family training engine.

    The DV3/DV1-style loop (env interaction + sequential replay + jitted
    train step + checkpoint) parameterized by hooks so the JEPA variant and
    the Plan2Explore exploration/finetuning entrypoints reuse it:

    - ``build_agent_fn(runtime, actions_dim, is_continuous, cfg, obs_space,
      agent_state)`` -> ``(wm_def, actor_def, critic_def, params)`` — params
      may carry extra keys (JEPA heads, P2E ensembles/critics); every key is
      checkpointed.
    - ``make_optimizers_fn(cfg, params, agent_state)`` -> ``(optimizers,
      opt_states)``; default = DV3's world/actor/critic trio.
    - ``init_moments_fn(cfg, agent_state)`` -> Moments pytree (P2E: a dict of
      task + per-exploration-critic states).
    - ``player_actor_fn(params, has_trained)`` -> actor params for env
      interaction (P2E exploration plays with ``actor_exploration``;
      finetuning switches exploration -> task at the first gradient step,
      reference p2e_dv3_finetuning.py:350-354).
    - ``final_test_fn(player, params, runtime, cfg, log_dir)`` -> reward
      (P2E: zero-shot test with the task actor).
    - ``load_agent_state_fn(runtime, cfg)`` -> state used to *initialize*
      models when not resuming (finetuning loads the exploration checkpoint,
      reference cli.py:117-148); counters/buffers restore only from
      ``checkpoint.resume_from``.
    """
    world_size = runtime.world_size
    num_envs = cfg.env.num_envs

    state = runtime.load(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None
    agent_state = state
    if agent_state is None and load_agent_state_fn is not None:
        agent_state = load_agent_state_fn(runtime, cfg)

    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

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

    rng_key = runtime.seed_everything(cfg.seed)

    envs = pipelined_vector_env(cfg, make_env_fns(cfg, log_dir, "train"))
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    has_decoders = len(cfg.algo.cnn_keys.decoder) + len(cfg.algo.mlp_keys.decoder) > 0
    if has_decoders and (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)

    world_model_def, actor_def, critic_def, params = build_agent_fn(
        runtime, actions_dim, is_continuous, cfg, observation_space, agent_state
    )
    # bf16-true stores the weights themselves in bf16; *-mixed keeps fp32
    # master weights and casts per-loss inside the train step
    params = cast_floating(params, runtime.param_dtype)
    player = player_cls(world_model_def, actor_def, actions_dim, num_envs)

    if make_optimizers_fn is None:
        optimizers, opt_states = _default_make_optimizers(cfg, params, agent_state, extra_opt_setup)
    else:
        optimizers, opt_states = make_optimizers_fn(cfg, params, agent_state)
    if init_moments_fn is None:
        moments_state = init_moments_state()
        if agent_state and "moments" in agent_state:
            moments_state = jax.tree_util.tree_map(jnp.asarray, agent_state["moments"])
    else:
        moments_state = init_moments_fn(cfg, agent_state)
    if player_actor_fn is None:
        player_actor_fn = lambda p, has_trained: p["actor"]  # noqa: E731
    if metric_order is None:
        metric_order = METRIC_ORDER

    from sheeprl_tpu.parallel.dp import fsdp_min_shard_bytes
    from sheeprl_tpu.parallel.fsdp import fsdp_active, shard_map_summary, shard_tree
    from sheeprl_tpu.parallel.mesh import replicated_sharding

    if world_size > 1:
        if fsdp_active(runtime.mesh):
            # FSDP placement (howto/sharding.md): large leaves land sliced
            # over the "model" axis, small leaves replicated — the committed
            # shardings are what the global-view jit propagates from.  The
            # Moments state is a handful of scalars: always replicated.
            min_bytes = fsdp_min_shard_bytes(cfg)
            params = shard_tree(params, runtime.mesh, min_bytes)
            opt_states = shard_tree(opt_states, runtime.mesh, min_bytes)
            moments_state = jax.device_put(moments_state, replicated_sharding(runtime.mesh))
            diag.on_fsdp_shard_map(
                shard_map_summary(
                    {"params": params, "opt_state": opt_states}, runtime.mesh, min_bytes
                )
            )
        else:
            params = jax.device_put(params, replicated_sharding(runtime.mesh))
            opt_states = jax.device_put(opt_states, replicated_sharding(runtime.mesh))
            moments_state = jax.device_put(moments_state, replicated_sharding(runtime.mesh))

    # telemetry instrumentation (shared engine: dv3 / jepa / p2e inherit):
    # recompile watchdog + exact compiled-step FLOPs for the live MFU gauge.
    # The player forward stays uninstrumented — its compiles are still counted
    # by the process-wide jax.monitoring listener.
    loop_scan_unroll = int(cfg.algo.get("scan_unroll", 1) or 1)
    train_step = diag.instrument(
        "train_step",
        make_train_step_fn(
            world_model_def,
            actor_def,
            critic_def,
            optimizers,
            cfg,
            actions_dim,
            is_continuous,
            mesh=runtime.mesh if world_size > 1 else None,
        ),
        kind="train",
        donate_argnums=(0, 1, 2),  # params, opt_states, moments — audited at first dispatch
        # unrolled scans inflate cost_analysis() FLOPs (PERF.md §5), which
        # would silently inflate Telemetry/mfu too — the telemetry_cost
        # journal event carries this caveat so MFU readers know to compare
        # step_ms instead
        cost_note=(
            f"cost_analysis FLOPs inflate under scan unrolling (scan_unroll={loop_scan_unroll}); "
            "compare step_ms, not MFU"
            if loop_scan_unroll > 1
            else None
        ),
    )
    diag.register_footprint("params", params)
    diag.register_footprint("opt_state", opt_states)
    diag.register_footprint("moments", moments_state)
    # one staged h2d per vector step for the player's obs slab (see
    # envs/player.py); the action fetch below is the one blocking d2h
    stage_sharding = obs_sharding(runtime.mesh if world_size > 1 else None)

    buffer_size = cfg.buffer.size // num_envs if not cfg.dry_run else 2
    # HBM-resident replay when buffer.device=True: frames never leave the
    # device after collection (sheeprl_tpu/data/device_buffer.py) — removes
    # the ~B*T*H*W*C bytes of host->HBM traffic per gradient step
    rb, use_device_buffer = make_dreamer_replay_buffer(
        cfg, world_size, num_envs, obs_keys, log_dir, buffer_size, mesh=runtime.mesh
    )
    diag.track_buffer("replay", rb)
    buffer_state = state
    if buffer_state is None and cfg.buffer.get("load_from_exploration") and agent_state:
        # P2E finetuning may continue on the exploration replay buffer
        # (reference p2e_dv3_finetuning.py:188-195)
        buffer_state = agent_state
    if (
        buffer_state
        and (cfg.buffer.checkpoint or cfg.buffer.get("load_from_exploration"))
        and buffer_state.get("rb") is not None
    ):
        rb.load_state_dict(buffer_state["rb"])
        if rssm_scan_spec(cfg)[0] > 1:
            # a replay collected WITHOUT the chunked scan has no stored-state
            # rows — fail with the cause here instead of a generic
            # unknown-buffer-key error at the first add
            loaded = getattr(rb, "buffer", None)
            if isinstance(loaded, (list, tuple)) and loaded:
                loaded = loaded[0]
            loaded_keys = set(loaded.buffer if hasattr(loaded, "buffer") else loaded or {})
            if loaded_keys and "rssm_recurrent" not in loaded_keys:
                raise ValueError(
                    "algo.rssm_chunks > 1 needs replay rows carrying the player's RSSM "
                    "state (rssm_recurrent/rssm_posterior/rssm_valid), but the restored "
                    "buffer was collected without them — resume with rssm_chunks=1 or "
                    "start a fresh buffer"
                )

    train_step_count = 0
    last_train = 0
    start_iter = (state["iter_num"] if state else 0) + 1
    policy_step_count = state["iter_num"] * num_envs if state else 0
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

    # ---- first obs (reference dreamer_v3.py:578-589) ----------------------
    obs = envs.reset(seed=cfg.seed)[0]
    step_data: Dict[str, np.ndarray] = step_slab(num_envs, {k: obs[k] for k in obs_keys})
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    player.init_states(params["world_model"])

    # chunked-scan stored states (algo.rssm_chunks > 1): every replay row
    # additionally carries the player's post-step RSSM state so the train
    # step can seed chunk boundaries from it (rssm_valid=0 on rows written
    # without one — prefill, bookkeeping — falls back to the learned initial
    # state).  Costs H+Z floats per step per env in replay and rides the
    # iteration's ONE blocking d2h on the host-buffer path.
    store_rssm_state = rssm_scan_spec(cfg)[0] > 1
    if store_rssm_state:
        rssm_zero_recurrent = np.zeros(
            (num_envs, int(player.state["recurrent"].shape[-1])), np.float32
        )
        rssm_zero_stochastic = np.zeros(
            (num_envs, int(player.state["stochastic"].shape[-1])), np.float32
        )
        step_data.update(
            rssm_state_slab(num_envs, rssm_zero_recurrent, rssm_zero_stochastic, valid=False)
        )

    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cumulative_grad_steps = 0
    has_trained = bool(cfg.checkpoint.resume_from)

    def split_real_actions(actions: np.ndarray) -> np.ndarray:
        if is_continuous:
            return actions.reshape(num_envs, -1)
        idxs = []
        start = 0
        for d in actions_dim:
            idxs.append(np.argmax(actions[..., start : start + d], axis=-1))
            start += d
        return np.stack(idxs, axis=-1)

    metrics_drain = DeviceMetricsDrain()
    # which of its two orders an iteration runs in (loop_order.py): measured,
    # not configured.  Only the HBM ring has two: on the host the add needs
    # the fetched action, so the sample cannot precede the fetch without
    # seeing one row less.
    loop_order = LoopOrder(
        use_device_buffer and not cfg.dry_run, count=diag.note_loop_order, journal=diag.on_loop_order
    )

    def dispatch_gradient_steps() -> None:
        """Sample and dispatch this iteration's gradient steps, if any are due.

        The sample includes everything up to and including the current policy
        step (both buffer modes and both orders: the add always precedes the
        sampling); episode-end bookkeeping rows from *this* step (known only
        at `step_wait`) become sampleable one iteration later.  Likewise the
        restart_on_exception truncation surgery (below) lands only after these
        gradient steps have sampled, so a crashed-env discontinuity can be
        trained on once as a normal transition: rare and bounded to one
        iteration (the reference patches before training; we accept the lag
        as the price of the overlap)."""
        nonlocal params, opt_states, moments_state, rng_key
        nonlocal has_trained, cumulative_grad_steps, train_step_count
        if iter_num < learning_starts:
            return
        per_rank_gradient_steps = ratio(
            (policy_step_count - prefill_steps * policy_steps_per_iter)
        )
        if cfg.dry_run:
            per_rank_gradient_steps = 1
        if per_rank_gradient_steps <= 0:
            return
        has_trained = True
        with diag.span("buffer-sample"):
            local_data = rb.sample(
                local_sample_size(cfg.algo.per_rank_batch_size * world_size, use_device_buffer),
                sequence_length=cfg.algo.per_rank_sequence_length,
                n_samples=per_rank_gradient_steps,
            )
            batches = train_batches(
                local_data,
                per_rank_gradient_steps,
                runtime.mesh if world_size > 1 else None,
                cnn_keys,
                use_device_buffer,
            )

        with timer("Time/train_time"), diag.span("train"):
            for batch in batches:
                batch = diag.maybe_inject_nan(iter_num, batch)
                target_freq = cfg.algo.critic.get("per_rank_target_network_update_freq", 0)
                if target_freq and cumulative_grad_steps % target_freq == 0:
                    tau = 1.0 if cumulative_grad_steps == 0 else cfg.algo.critic.get("tau", 1.0)
                else:
                    tau = 0.0
                rng_key, train_key = jax.random.split(rng_key)
                out = train_step(
                    params, opt_states, moments_state, batch, train_key, jnp.float32(tau)
                )
                # P2E's step builders return 4 outputs (no health
                # tree); the DV3/JEPA steps return 5 ({} when
                # diagnostics.health is off)
                params, opt_states, moments_state, metrics = out[:4]
                step_health = out[4] if len(out) > 4 else None
                cumulative_grad_steps += 1
            train_step_count += 1
        metrics_drain.append(metrics, extra=step_health)

    for iter_num in range(start_iter, total_iters + 1):
        policy_step_count += policy_steps_per_iter
        diag.note_env_steps(num_envs)
        player_acts = iter_num > learning_starts or bool(cfg.checkpoint.resume_from)
        order = loop_order.begin(player_acts, train_step_count)

        # ---- policy forward + env dispatch + replay write -----------------
        # Split-phase iteration.  The device's work is enqueued in one
        # sequence whatever the order: player forward, ring add, ring sample,
        # batch staging, train step.  The player reads the parameters the
        # previous train step wrote, so the action fetch returns when that
        # step and the player have ended, and `step_wait` is the only other
        # place the host blocks.  What the order decides is where those two
        # waits stand against the host's sample and train dispatch:
        # - ENV_OVERLAP: fetch, `step_async`, then sample + dispatch while the
        #   env workers step.  Critical path ``fwd + fetch + max(sample +
        #   dispatch, env_step)``: the host's sample and dispatch hide behind
        #   the env step, and the device idles under them, from the previous
        #   step's end until the next is enqueued.
        # - TRAIN_FIRST (HBM ring only): sample + dispatch, then fetch and
        #   `step_async`.  The device runs player -> add -> sample -> step
        #   back to back while the host fetches, steps the envs and stages
        #   the next obs; the env step stands on the host's serial path.
        # A fast env wants the second, a slow simulator the first, and where
        # they meet depends on the model: `loop_order` times both and keeps
        # the faster (PERF.md §6, PR 32; the reference hot loop serializes all
        # of it, dreamer_v3.py:637-672).
        # Params, optimizer state and ring contents are the same bit for bit.
        with diag.span("rollout"):
            with timer("Time/env_interaction_time"):
                actions_jnp = None
                if not player_acts:
                    real_actions = actions = np.asarray(envs.action_space.sample())
                    if not is_continuous:
                        actions = np.concatenate(
                            [
                                np.eye(act_dim, dtype=np.float32)[act]
                                for act, act_dim in zip(actions.reshape(len(actions_dim), -1), actions_dim)
                            ],
                            axis=-1,
                        )
                    step_data["actions"] = actions.reshape(1, num_envs, -1)
                    if store_rssm_state:
                        # prefill rows: the player never ran, so no state exists —
                        # valid=0 makes chunk starts here reset to the learned
                        # initial state instead of training on zeros
                        step_data.update(
                            rssm_state_slab(
                                num_envs, rssm_zero_recurrent, rssm_zero_stochastic, valid=False
                            )
                        )
                else:
                    rng_key, step_key = jax.random.split(rng_key)
                    with diag.span("rollout/obs-stage"):
                        torch_obs = prepare_obs(
                            obs, cnn_keys=cnn_keys, mlp_keys=mlp_keys, num_envs=num_envs, sharding=stage_sharding
                        )
                    # mask_* observation keys feed MinedojoActor's hierarchical
                    # action masking (reference dreamer_v3.py:614-617)
                    mask = {k: v for k, v in torch_obs.items() if k.startswith("mask")} or None
                    with diag.span("rollout/player-forward"):
                        actions_jnp = player.get_actions(
                            params["world_model"], player_actor_fn(params, has_trained), torch_obs, step_key,
                            mask=mask,
                        )
                    if use_device_buffer:
                        # device-resident actions go straight into the HBM ring
                        # (no fetch needed for the write); the chunked-scan state
                        # record stays on device with them
                        step_data["actions"] = jnp.reshape(actions_jnp, (1, num_envs, -1))
                        if store_rssm_state:
                            step_data.update(
                                rssm_state_slab(
                                    num_envs,
                                    player.state["recurrent"],
                                    player.state["stochastic"],
                                    valid=True,
                                )
                            )
                        with diag.span("rollout/replay-add"):
                            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            if order == TRAIN_FIRST:
                # queued behind the player and the add, ahead of the host's
                # two waits: `buffer-sample` and `train` lie inside `rollout`
                # here, which keeps their seconds out of its self time
                dispatch_gradient_steps()

            with timer("Time/env_interaction_time"):
                if actions_jnp is not None:
                    diag.note_fetch()  # the iteration's ONE blocking d2h
                    # the host waits here for the device: the player forward and
                    # whatever was queued before it
                    with diag.span("rollout/action-fetch"):
                        if store_rssm_state and not use_device_buffer:
                            # the stored states ride the SAME blocking fetch as the
                            # action values — still one d2h round trip per vector step
                            actions, host_recurrent, host_stochastic = fetch_values(
                                actions_jnp, player.state["recurrent"], player.state["stochastic"]
                            )
                            step_data.update(
                                rssm_state_slab(num_envs, host_recurrent, host_stochastic, valid=True)
                            )
                        else:
                            actions = np.asarray(actions_jnp)  # blocking value fetch
                    real_actions = split_real_actions(actions)
                    if not use_device_buffer:
                        step_data["actions"] = actions.reshape(1, num_envs, -1)
                with diag.span("env_step_async"):
                    envs.step_async(real_actions.reshape(envs.action_space.shape))
                if actions_jnp is None or not use_device_buffer:
                    # prefill / host-buffer write — overlaps the env workers
                    with diag.span("rollout/replay-add"):
                        rb.add(step_data, validate_args=cfg.buffer.validate_args)

        # ---- dispatch this iteration's gradient steps ---------------------
        # ENV_OVERLAP: runs while the env workers are stepping.
        if order != TRAIN_FIRST:
            dispatch_gradient_steps()

        # ---- collect the env step results (device keeps training) --------
        with timer("Time/env_interaction_time"), diag.span("env_wait"):
            next_obs, rewards, terminated, truncated, infos = envs.step_wait()
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        # ---- bookkeeping: host work between the env's results and the
        # checkpoint test (obs copies, reset rows, aggregator, metric drain)
        with diag.span("bookkeeping"):
            step_data["is_first"] = np.zeros_like(step_data["terminated"])
            if "restart_on_exception" in infos:
                for i, agent_roe in enumerate(infos["restart_on_exception"]):
                    if agent_roe and not dones[i]:
                        if use_device_buffer:
                            rb.mark_last_truncated(i)
                        else:
                            sub = rb.buffer[i]
                            last_idx = (sub._pos - 1) % sub.buffer_size
                            sub["terminated"][last_idx] = np.zeros_like(sub["terminated"][last_idx])
                            sub["truncated"][last_idx] = np.ones_like(sub["truncated"][last_idx])
                            sub["is_first"][last_idx] = np.zeros_like(sub["is_first"][last_idx])
                        step_data["is_first"][0, i] = np.ones_like(step_data["is_first"][0, i])

            if "final_info" in infos and "episode" in infos["final_info"]:
                ep = infos["final_info"]["episode"]
                mask = ep.get("_r", infos["final_info"].get("_episode"))
                if mask is not None and np.any(mask):
                    for r, l in zip(ep["r"][mask], ep["l"][mask]):
                        aggregator.update("Rewards/rew_avg", float(r))
                        aggregator.update("Game/ep_len_avg", float(l))

            real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
            if "final_obs" in infos:
                for idx, final_obs in enumerate(infos["final_obs"]):
                    if final_obs is not None:
                        for k in obs_keys:
                            real_next_obs[k][idx] = np.asarray(final_obs[k])

            step_data.update(
                step_slab(
                    num_envs,
                    {
                        **{k: next_obs[k] for k in obs_keys},
                        "terminated": terminated,
                        "truncated": truncated,
                        "rewards": rewards,
                    },
                    dtypes={"terminated": np.float32, "truncated": np.float32, "rewards": np.float32},
                )
            )
            obs = next_obs
            if cfg.env.clip_rewards:
                step_data["rewards"] = np.tanh(step_data["rewards"])

            dones_idxes = dones.nonzero()[0].tolist()
            if dones_idxes:
                reset_data = {}
                for k in obs_keys:
                    reset_data[k] = real_next_obs[k][dones_idxes][np.newaxis]
                reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
                reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
                reset_data["actions"] = np.zeros((1, len(dones_idxes), int(sum(actions_dim))), np.float32)
                reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
                reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
                if store_rssm_state:
                    # episode-end bookkeeping rows carry no player state (the env
                    # just reset); valid=0 keeps chunk starts off them
                    reset_data.update(
                        rssm_state_slab(
                            len(dones_idxes),
                            rssm_zero_recurrent[: len(dones_idxes)],
                            rssm_zero_stochastic[: len(dones_idxes)],
                            valid=False,
                        )
                    )
                rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)

                step_data["rewards"][:, dones_idxes] = 0
                step_data["terminated"][:, dones_idxes] = 0
                step_data["truncated"][:, dones_idxes] = 0
                step_data["is_first"][:, dones_idxes] = 1
                reset_mask = np.zeros((num_envs, 1), np.float32)
                reset_mask[dones_idxes] = 1.0
                player.init_states(params["world_model"], reset_mask)

            # ---- log (reference dreamer_v3.py:747-793) ------------------------
            if policy_step_count - last_log >= cfg.metric.log_every or iter_num == total_iters or cfg.dry_run:
                # the sentinel sees the raw per-gradient-step rows before the
                # aggregator's NaN filtering drops them (warn/halt policies; the
                # skip_update selection already happened in-graph)
                metrics_drain.flush_into(
                    aggregator,
                    metric_order,
                    observer=lambda rows: diag.observe_rows(policy_step_count, metric_order, rows),
                    extra_observer=lambda extras: diag.on_health(
                        policy_step_count, mean_stats(extras)
                    ),
                )
                metrics_dict = aggregator.compute()
                timers = timer.compute()
                if timers.get("Time/train_time", 0) > 0:
                    metrics_dict["Time/sps_train"] = (train_step_count - last_train) / timers["Time/train_time"]
                if timers.get("Time/env_interaction_time", 0) > 0:
                    metrics_dict["Time/sps_env_interaction"] = (
                        (policy_step_count - last_log) * cfg.env.action_repeat
                    ) / timers["Time/env_interaction_time"]
                if policy_step_count > 0:
                    metrics_dict["Params/replay_ratio"] = cumulative_grad_steps / policy_step_count
                if runtime.is_global_zero:
                    logger.log_metrics(metrics_dict, policy_step_count)
                aggregator.reset()
                timer.reset()
                last_log = policy_step_count
                last_train = train_step_count

        # ---- checkpoint (reference dreamer_v3.py:795-826) -----------------
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
                **{k: jax.tree_util.tree_map(np.asarray, v) for k, v in params.items()},
                "opt_states": jax.tree_util.tree_map(np.asarray, opt_states),
                "moments": jax.tree_util.tree_map(np.asarray, moments_state),
                "ratio": ratio.state_dict(),
                "iter_num": iter_num,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
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
    cumulative_rew = None
    if runtime.is_global_zero and cfg.algo.run_test:
        if final_test_fn is None:
            cumulative_rew = test(
                player, params["world_model"], player_actor_fn(params, True), runtime, cfg, log_dir, greedy=False
            )
        else:
            cumulative_rew = final_test_fn(player, params, runtime, cfg, log_dir)
        logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, policy_step_count)
    if cfg.model_manager.disabled is False and runtime.is_global_zero:  # pragma: no cover
        from sheeprl_tpu.utils.mlflow import log_models

        log_models(cfg, params, log_dir)
    logger.finalize()
    diag.close("completed")
    return cumulative_rew
