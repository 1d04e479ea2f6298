"""DreamerV3 helpers (reference /root/reference/sheeprl/algos/dreamer_v3/utils.py).

``Moments`` is a pure-functional EMA of return percentiles: carried as a tiny
state pytree updated inside the jitted train step.  The reference gathers
values across ranks via ``fabric.all_gather`` before the quantile
(utils.py:56-64); inside the shard_map'd train step the same semantics is an
explicit ``lax.all_gather`` over the data axis before ``jnp.quantile``
(``axis_name`` below), so every device EMAs the *global* percentiles.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Replay keys carrying the player's post-step RSSM state when
#: ``algo.rssm_chunks > 1`` (SEED-RL/R2D2-style stored-state chunking):
#: ``rssm_recurrent``/``rssm_posterior`` are the state AFTER observing the
#: row's obs, ``rssm_valid`` is 1.0 only on rows the player actually wrote
#: (prefill and episode-end bookkeeping rows carry zeros + valid=0, and a
#: chunk starting there falls back to the learned initial state — exactly
#: what the unchunked scan does at every sampled-sequence start).
RSSM_STATE_KEYS = ("rssm_recurrent", "rssm_posterior", "rssm_valid")


def rssm_scan_spec(cfg) -> Tuple[int, int]:
    """``(chunks, burn_in)`` from ``algo.rssm_chunks`` /
    ``algo.rssm_chunk_burn_in`` — shared by the DV3/JEPA/P2E train-step
    builders so the three can never drift.  Configs without the keys (the
    DV1/DV2 family) resolve to ``(1, 0)`` = today's sequential scan."""
    chunks = int(cfg.algo.get("rssm_chunks", 1) or 1)
    burn_in = int(cfg.algo.get("rssm_chunk_burn_in", 0) or 0)
    if chunks < 1:
        raise ValueError(f"algo.rssm_chunks must be >= 1, got {chunks}")
    if burn_in < 0:
        raise ValueError(f"algo.rssm_chunk_burn_in must be >= 0, got {burn_in}")
    return chunks, burn_in


def chunked_dynamic_scan(
    scan_body,
    batch_actions: jax.Array,
    embedded: jax.Array,
    is_first: jax.Array,
    key: jax.Array,
    *,
    stoch_flat: int,
    recurrent_size: int,
    cdt,
    chunks: int = 1,
    burn_in: int = 0,
    stored_recurrent: jax.Array | None = None,
    stored_posterior: jax.Array | None = None,
    stored_valid: jax.Array | None = None,
    unroll: int = 1,
    step_inputs=lambda *xs: xs,
):
    """Run the T-step dynamic-learning scan, optionally split into ``chunks``
    independent chunks whose initial states come from replay-stored RSSM
    states — the chunk axis is folded into the batch axis, so the GRU GEMM
    runs at ``B * chunks`` rows instead of ``B`` (PERF.md §5: MFU rises
    exactly as the effective row count widens; the trade is strict recurrence
    across chunk boundaries for stored — possibly stale — states, the
    SEED-RL/R2D2 playbook).

    ``scan_body`` is the per-step body: ``((posterior, recurrent), x_t) ->
    ((posterior, recurrent), ys)``.  ``step_inputs`` maps one loop's
    ``(actions, embedded, is_first, keys)``, each with that loop's leading
    axis and folded rows, to the ``xs`` the body reads a step of: whatever of
    a step depends on neither carry is computed there, once for all the
    loop's rows (`dynamic_learning_scan` below; the default hands the body
    the four as they are).  ``batch_actions`` and ``embedded`` are any two
    ``[T, B, ...]`` leaves.  Returns the stacked ``ys`` pytree in the original
    ``[T, B, ...]`` layout.

    * ``chunks == 1`` reproduces today's sequential scan **bit-identically**
      (same zero init, same ``jax.random.split(key, T)`` per-step keys, same
      op order — golden-tested in ``tests/test_algos/test_rssm_chunks.py``).
    * ``chunks > 1``: row ``t`` of chunk ``k`` starts at ``t0 = k*T/K``; its
      initial carry is the stored state at row ``t0 - 1`` (chunk 0 keeps the
      zero init + forced ``is_first``).  A stored state marked invalid
      (``rssm_valid == 0``) turns the chunk start into a fresh-sequence start
      via the ``is_first`` reset path.
    * ``burn_in > 0``: before the gradient region, rows ``[t0 - burn_in, t0)``
      are re-run from the state stored at ``t0 - burn_in - 1`` and the
      resulting carry — gradients stopped — re-freshens each chunk's initial
      state (R2D2's burn-in, folded over chunks the same way).
    """
    T, B = batch_actions.shape[:2]
    if chunks <= 1:
        keys_t = jax.random.split(key, T)
        init = (jnp.zeros((B, stoch_flat), cdt), jnp.zeros((B, recurrent_size), cdt))
        _, ys = jax.lax.scan(
            scan_body, init, step_inputs(batch_actions, embedded, is_first, keys_t), unroll=unroll
        )
        return ys

    K = int(chunks)
    if T % K != 0:
        raise ValueError(f"algo.rssm_chunks ({K}) must divide the sequence length ({T})")
    C = T // K
    if not 0 <= burn_in < C:
        raise ValueError(
            f"algo.rssm_chunk_burn_in ({burn_in}) must be in [0, chunk_length) = [0, {C})"
        )
    if stored_recurrent is None or stored_posterior is None:
        raise ValueError(
            "algo.rssm_chunks > 1 needs the replay-stored RSSM state keys "
            f"{RSSM_STATE_KEYS[:2]} in the batch (enabled automatically by the "
            "training loop when the knob is set — old replay checkpoints "
            "collected without it cannot be chunk-trained)"
        )

    def fold(x):  # [T, B, ...] -> [C, K*B, ...] (row t = k*C + c -> (c, k*B+b))
        x = x.reshape((K, C) + x.shape[1:])
        x = jnp.moveaxis(x, 0, 1)
        return x.reshape((C, K * B) + x.shape[3:])

    def unfold(y):  # inverse of fold on the stacked outputs
        y = y.reshape((C, K, B) + y.shape[2:])
        y = jnp.moveaxis(y, 1, 0)
        return y.reshape((T, B) + y.shape[3:])

    stored_z = jax.lax.stop_gradient(stored_posterior).astype(cdt)
    stored_h = jax.lax.stop_gradient(stored_recurrent).astype(cdt)
    valid = (
        jax.lax.stop_gradient(stored_valid).astype(cdt)
        if stored_valid is not None
        else jnp.ones((T, B, 1), cdt)
    )
    k_main, k_burn = jax.random.split(key)
    boundary_rows = np.arange(1, K) * C  # first row of chunks 1..K-1 (static)

    if burn_in > 0:
        # burn-in: re-run the `burn_in` rows before each boundary from the
        # state stored just before them; only the final carry is used, and it
        # is gradient-stopped, so no gradient flows through the burn scan
        burn_rows = boundary_rows[:, None] - burn_in + np.arange(burn_in)[None, :]

        def gather_fold(x):  # rows [K-1, burn_in] of [T, B, ...] -> [burn_in, (K-1)*B, ...]
            g = x[burn_rows]
            g = jnp.moveaxis(g, 0, 1)
            return g.reshape((burn_in, (K - 1) * B) + g.shape[3:])

        init_rows = boundary_rows - burn_in - 1
        z0 = stored_z[init_rows].reshape(((K - 1) * B, stoch_flat))
        h0 = stored_h[init_rows].reshape(((K - 1) * B, recurrent_size))
        bf = gather_fold(is_first)
        invalid = 1.0 - valid[init_rows].reshape(((K - 1) * B, 1))
        bf = bf.at[0].set(jnp.maximum(bf[0], invalid))
        xs_burn = step_inputs(
            gather_fold(batch_actions),
            gather_fold(embedded),
            bf,
            jax.random.split(k_burn, burn_in),
        )
        (z_fresh, h_fresh), _ = jax.lax.scan(scan_body, (z0, h0), xs_burn, unroll=unroll)
        z_rest = jax.lax.stop_gradient(z_fresh).reshape((K - 1, B, stoch_flat))
        h_rest = jax.lax.stop_gradient(h_fresh).reshape((K - 1, B, recurrent_size))
        is_first_adj = is_first
    else:
        init_rows = boundary_rows - 1
        z_rest = stored_z[init_rows]
        h_rest = stored_h[init_rows]
        # a chunk starting on a row whose predecessor was never written by
        # the player (prefill / bookkeeping) resets like a sequence start
        invalid = 1.0 - valid[init_rows]  # [K-1, B, 1]
        is_first_adj = is_first.at[boundary_rows].set(
            jnp.maximum(is_first[boundary_rows], invalid)
        )

    z_init = jnp.concatenate([jnp.zeros((1, B, stoch_flat), cdt), z_rest], axis=0)
    h_init = jnp.concatenate([jnp.zeros((1, B, recurrent_size), cdt), h_rest], axis=0)
    init = (z_init.reshape((K * B, stoch_flat)), h_init.reshape((K * B, recurrent_size)))
    xs = step_inputs(fold(batch_actions), fold(embedded), fold(is_first_adj), jax.random.split(k_main, C))
    _, ys = jax.lax.scan(scan_body, init, xs, unroll=unroll)
    return jax.tree_util.tree_map(unfold, ys)


def dynamic_learning_scan(world_model_def, wm_params, batch_actions, embedded, is_first, key, *, cdt, **scan_spec):
    """The dynamic-learning pass of the DV3 family's train steps
    (DV3/JEPA/P2E): ``(recurrents, posteriors, post_logits, prior_logits)``,
    each ``[T, B, ...]``, by `RSSM.dynamic`'s mathematics and draws.

    The loop's body (`RSSM.scan_step`) keeps what depends on its carry: the
    reset, the state's half of the recurrent and representation models' input
    products, the LayerNorm-GRU, the representation head and the argmax of
    the draw.  Outside it, once on all rows: the actions' and the embedded
    observations' halves of those products (before `chunked_dynamic_scan`
    folds them like any ``[T, B, ...]`` leaf), the learned initial state, each
    loop's Gumbel noise, and — after it, on the stacked recurrent states — the
    prior head, which nothing carries and the burn-in loop never needs.
    ``scan_spec`` is `chunked_dynamic_scan`'s keyword arguments."""

    def rssm(method, *args):
        return world_model_def.apply(wm_params, *args, method=lambda wm, *a: getattr(wm.rssm, method)(*a))

    action_rows, obs_rows = rssm("scan_projections", batch_actions, embedded)
    initial_states = rssm("get_initial_states", ())

    def step_inputs(action_rows, obs_rows, is_first, keys):
        noise = rssm("scan_noise", keys, is_first.shape[1], cdt)
        return (1 - is_first) * action_rows, obs_rows, is_first, noise

    def scan_body(carry, x):
        recurrent, posterior, post_logits = rssm("scan_step", *carry, *x, initial_states)
        return (posterior, recurrent), (recurrent, posterior, post_logits)

    recurrents, posteriors, post_logits = chunked_dynamic_scan(
        scan_body, action_rows, obs_rows, is_first, key, cdt=cdt, step_inputs=step_inputs, **scan_spec
    )
    return recurrents, posteriors, post_logits, rssm("prior_logits", recurrents)


AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/world_model_loss",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/observation_loss",
    "Loss/reward_loss",
    "Loss/state_loss",
    "Loss/continue_loss",
    "State/kl",
    "State/post_entropy",
    "State/prior_entropy",
    "Grads/world_model",
    "Grads/actor",
    "Grads/critic",
}
MODELS_TO_REGISTER = {"world_model", "actor", "critic", "target_critic", "moments"}


def init_moments_state() -> Dict[str, jax.Array]:
    return {"low": jnp.zeros(()), "high": jnp.zeros(())}


def update_moments(
    state: Dict[str, jax.Array],
    x: jax.Array,
    decay: float = 0.99,
    max_: float = 1.0,
    percentile_low: float = 0.05,
    percentile_high: float = 0.95,
    axis_name: str | None = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Return (offset, invscale, new_state) (reference Moments.forward,
    utils.py:56-64).  With ``axis_name`` set (inside shard_map) the quantile
    is computed over the all-gathered values from every device."""
    from sheeprl_tpu.parallel.dp import all_gather_cat

    x = all_gather_cat(jax.lax.stop_gradient(x).astype(jnp.float32), axis_name)
    low = jnp.quantile(x, percentile_low)
    high = jnp.quantile(x, percentile_high)
    new_low = decay * state["low"] + (1 - decay) * low
    new_high = decay * state["high"] + (1 - decay) * high
    invscale = jnp.maximum(1.0 / max_, new_high - new_low)
    return new_low, invscale, {"low": new_low, "high": new_high}


def prepare_obs(
    obs: Dict[str, np.ndarray],
    *,
    cnn_keys: Sequence[str] = (),
    mlp_keys: Sequence[str] = (),
    num_envs: int = 1,
    sharding: Any = None,
) -> Dict[str, jax.Array]:
    """Host obs → device arrays ``[num_envs, ...]``; pixels scaled to
    [-0.5, 0.5] (reference utils.py:80-92).  The whole slab is staged in ONE
    ``jax.device_put`` (pass a reused ``sharding`` from the hot loops —
    ``envs/player.py::obs_sharding``); pixels transfer uint8 and are cast +
    scaled on device (4x less host→HBM traffic, identical float32 values —
    same policy as the ppo path)."""
    host: Dict[str, np.ndarray] = {}
    for k in cnn_keys:
        v = np.asarray(obs[k])
        host[k] = v.reshape(num_envs, -1, *v.shape[-2:])
    for k in mlp_keys:
        host[k] = np.asarray(obs[k], np.float32).reshape(num_envs, -1)
    dev = jax.device_put(host, sharding) if sharding is not None else jax.device_put(host)
    cnn = set(cnn_keys)
    return {k: (v.astype(jnp.float32) / 255.0 - 0.5 if k in cnn else v) for k, v in dev.items()}


def test(player, wm_params, actor_params, runtime, cfg, log_dir: str, test_name: str = "", greedy: bool = True):
    """One test episode (reference utils.py:95-140)."""
    from sheeprl_tpu.envs.env import make_env

    env = make_env(cfg, cfg.seed, 0, log_dir, "test" + (f"_{test_name}" if test_name else ""))()
    done = False
    cumulative_rew = 0.0
    obs = env.reset(seed=cfg.seed)[0]
    saved_num_envs = player.num_envs
    player.num_envs = 1
    player.state = None
    player.init_states(wm_params)
    key = jax.random.PRNGKey(cfg.seed or 0)
    step = 0
    while not done:
        key, sub = jax.random.split(key)
        torch_obs = prepare_obs(obs, cnn_keys=cfg.algo.cnn_keys.encoder, mlp_keys=cfg.algo.mlp_keys.encoder)
        mask = {k: v for k, v in torch_obs.items() if k.startswith("mask")} or None
        actions = np.asarray(
            player.get_actions(wm_params, actor_params, torch_obs, sub, greedy=greedy, mask=mask)
        )
        if player.actor_def.is_continuous:
            real_actions = actions.reshape(env.action_space.shape)
        else:
            # one-hot concat -> per-head argmax indices
            idxs = []
            start = 0
            for d in player.actions_dim:
                idxs.append(np.argmax(actions[..., start : start + d], axis=-1))
                start += d
            real_actions = np.stack(idxs, axis=-1).reshape(env.action_space.shape)
        obs, reward, terminated, truncated, _ = env.step(real_actions)
        done = bool(terminated or truncated or cfg.dry_run)
        cumulative_rew += float(reward)
        step += 1
    env.close()
    player.num_envs = saved_num_envs
    player.state = None
    return cumulative_rew
