"""DreamerV3 helpers (reference /root/reference/sheeprl/algos/dreamer_v3/utils.py).

``Moments`` is a pure-functional EMA of return percentiles: carried as a tiny
state pytree updated inside the jitted train step.  The reference gathers
values across ranks via ``fabric.all_gather`` before the quantile
(utils.py:56-64); inside the shard_map'd train step the same semantics is an
explicit ``lax.all_gather`` over the data axis before ``jnp.quantile``
(``axis_name`` below), so every device EMAs the *global* percentiles.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict

from sheeprl_tpu.models.blocks import PRODUCT_INPUTS, PRODUCT_PROBES, PRODUCT_SUFFIX

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
    scan,
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
    step_inputs=lambda *xs: xs,
):
    """Run the T-step dynamic-learning scan, optionally split into ``chunks``
    independent chunks whose initial states come from replay-stored RSSM
    states — the chunk axis is folded into the batch axis, so the GRU GEMM
    runs at ``B * chunks`` rows instead of ``B`` (PERF.md §5: MFU rises
    exactly as the effective row count widens; the trade is strict recurrence
    across chunk boundaries for stored — possibly stale — states, the
    SEED-RL/R2D2 playbook).

    ``scan`` runs one loop, ``(init, xs) -> (carry, ys)`` over ``xs``' leading
    axis with the carry ``(posterior, recurrent)``: for a per-step body
    ``(carry, x_t) -> (carry, ys_t)`` it is ``functools.partial(jax.lax.scan,
    body)``.  Whoever builds it chooses its unrolling and what its backward
    keeps in the loop (`dynamic_learning_scan`'s takes the kernels' gradients
    out of it); it is called for the burn-in rows, whose carry's gradient is
    stopped here so that their backward never runs, and for the chunks.
    ``step_inputs`` maps one loop's ``(actions, embedded, is_first, keys)``,
    each with that loop's leading axis and folded rows, to the ``xs`` the body
    reads a step of: whatever of a step depends on neither carry is computed
    there, once for all the loop's rows (`dynamic_learning_scan` below; the
    default hands the body the four as they are).  ``batch_actions`` and
    ``embedded`` are any two ``[T, B, ...]`` leaves.  Returns the stacked
    ``ys`` pytree in the original ``[T, B, ...]`` layout.

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
        _, ys = scan(init, step_inputs(batch_actions, embedded, is_first, keys_t))
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
        (z_fresh, h_fresh), _ = scan((z0, h0), xs_burn)
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
    _, ys = scan(init, xs)
    return jax.tree_util.tree_map(unfold, ys)


def scan_kernel_gradients_after(step, unroll: int = 1):
    """``loop(variables, consts, init, xs) -> (carry, ys)``: `lax.scan` of the
    flax apply ``step(variables, consts, carry, x_t, mutable) -> ((carry,
    ys_t), mutated)`` over ``xs``' leading axis, with a backward of its own.

    `lax.scan`'s transpose accumulates the cotangent of everything its body
    closes over inside the loop: for a kernel that is an outer product of one
    step's few rows, read-modified-written at the kernel's size every step,
    though nothing the chain of the backward needs reads it.  Here the kernels
    of the products that ``step`` marks (`models/blocks.py::tap_product`) are
    held constant through the loop, whose backward keeps what the carry's
    cotangent needs (the products' input gradients, the vectors of LayerNorm
    scales and biases) and stacks, as the cotangent of a zero probe added to
    each product, the cotangent of its output; the product's inputs are
    stacked going forward, and each kernel's gradient is then one product over
    all the loop's rows, ``sum_t x_t^T dy_t = X^T dY``, with the operand types
    and the precision of the step's own: the result differs from
    `lax.scan`'s by summation order only.  A product that is not marked (the
    fused GRU's) keeps its kernel's gradient in the loop.  The forward loop
    runs once: its linearisation is made in the forward rule and kept."""

    def run(variables, consts, init, xs, probes):
        params = flatten_dict(variables["params"])
        for tap in flatten_dict(probes):
            params[_tapped_kernel(tap)] = jax.lax.stop_gradient(params[_tapped_kernel(tap)])
        held = {**variables, "params": unflatten_dict(params)}

        def body(carry, x):
            x, probe = x
            (carry, ys), tapped = step({**held, PRODUCT_PROBES: probe}, consts, carry, x, [PRODUCT_INPUTS])
            return carry, (ys, tapped.get(PRODUCT_INPUTS, {}))

        carry, (ys, inputs) = jax.lax.scan(body, init, (xs, probes), unroll=unroll)
        return (carry, ys), inputs

    @jax.custom_vjp
    def loop(variables, consts, init, xs):
        return jax.lax.scan(lambda carry, x: step(variables, consts, carry, x, [])[0], init, xs, unroll=unroll)

    def forward(variables, consts, init, xs):
        x0 = jax.tree_util.tree_map(lambda x: x[0], xs)
        length = jax.tree_util.tree_leaves(xs)[0].shape[0]
        # one abstract trace of a step names its marked products and gives their shapes
        marked = jax.eval_shape(lambda: step(variables, consts, init, x0, [PRODUCT_PROBES])[1])
        probes = jax.tree_util.tree_map(
            lambda y: jnp.zeros((length, *y.shape), y.dtype), marked.get(PRODUCT_PROBES, {})
        )
        out, transpose, inputs = jax.vjp(run, variables, consts, init, xs, probes, has_aux=True)
        return out, (transpose, inputs)

    def backward(residuals, cotangents):
        transpose, inputs = residuals
        d_variables, d_consts, d_init, d_xs, d_outputs = transpose(cotangents)
        d_params, d_outputs = flatten_dict(d_variables["params"]), flatten_dict(d_outputs)
        for tap, x in flatten_dict(inputs).items():
            held = d_params[_tapped_kernel(tap)]  # zeros: the loop held the kernel constant
            product = jnp.einsum("...i,...o->io", x, d_outputs[tap]).astype(held.dtype)
            # the step's product reads the kernel's leading rows only
            d_params[_tapped_kernel(tap)] = jnp.pad(product, ((0, held.shape[0] - product.shape[0]), (0, 0)))
        return {**d_variables, "params": unflatten_dict(d_params)}, d_consts, d_init, d_xs

    loop.defvjp(forward, backward)
    return loop


def _tapped_kernel(tap: Tuple[str, ...]) -> Tuple[str, ...]:
    """A marked product's path among the probes -> its kernel's among the parameters."""
    return (*tap[:-1], tap[-1].removesuffix(PRODUCT_SUFFIX), "kernel")


def dynamic_learning_scan(
    world_model_def, wm_params, batch_actions, embedded, is_first, key, *, cdt, unroll: int = 1, **scan_spec
):
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

    The backward loop likewise keeps what the carry's cotangent depends on:
    the input gradients of those four products, the transposes of the
    LayerNorms, the gates and the reset, and the vector-sized gradients of the
    LayerNorm scales and biases.  Outside it, once on all the loop's rows
    after it: the gradients of the four products' kernels, from the products'
    stacked inputs and the stacked cotangents of their outputs
    (`scan_kernel_gradients_after`); nothing the loop carries has a kernel's
    shape.  ``unroll`` is the loops' `lax.scan` unrolling; ``scan_spec`` is
    `chunked_dynamic_scan`'s keyword arguments."""

    def rssm(method, *args, variables=wm_params, **apply_kwargs):
        return world_model_def.apply(
            variables, *args, method=lambda wm, *a: getattr(wm.rssm, method)(*a), **apply_kwargs
        )

    action_rows, obs_rows = rssm("scan_projections", batch_actions, embedded)
    initial_states = rssm("get_initial_states", ())

    def step_inputs(action_rows, obs_rows, is_first, keys):
        noise = rssm("scan_noise", keys, is_first.shape[1], cdt)
        return (1 - is_first) * action_rows, obs_rows, is_first, noise

    def step(variables, initial_states, carry, x, mutable):
        (recurrent, posterior, post_logits), mutated = rssm(
            "scan_step", *carry, *x, initial_states, variables=variables, mutable=mutable
        )
        return ((posterior, recurrent), (recurrent, posterior, post_logits)), mutated

    loop = scan_kernel_gradients_after(step, unroll)
    # the loop is differentiated with respect to what it is handed: of the parameters, the RSSM's
    scan = functools.partial(loop, {"params": {"rssm": wm_params["params"]["rssm"]}}, initial_states)
    recurrents, posteriors, post_logits = chunked_dynamic_scan(
        scan, action_rows, obs_rows, is_first, key, cdt=cdt, step_inputs=step_inputs, **scan_spec
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
