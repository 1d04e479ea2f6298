"""A plain DreamerV3 gradient step: the reference the timed path is held to.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
flax, no optax, no scan tricks beyond ``lax.scan`` over time, nothing of the
program imported.  It follows Hafner et al. 2023 (DreamerV3) as the sheeprl
reference implements it: conv encoder / transposed-conv decoder with
LayerNorm + SiLU, the RSSM with a LayerNorm GRU, 1% unimix and
straight-through categorical latents, symlog two-hot reward and value heads,
a Bernoulli continue head, KL balancing with free nats, imagination with the
actor, lambda returns, percentile return normalisation, and three Adam
optimizers behind a global-norm clip.

It reads the weights by the names of the program's parameter tree (that tree
is the interface, as a checkpoint's would be) and shares the program's random
stream: the same keys are split the same way.  Two departures, both noted
where they happen: the Gumbel noise of every categorical draw is generated in
the precision the configuration states (``noise_dtype``), because the bits
drawn for another type are other bits, and it is added in float32.

``quant`` rounds both operands of every matrix multiplication and
convolution; the control puts the nearest lower precision there.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32


def quantizer(name: str) -> Callable[[Array], Array]:
    if name == "float32":
        return lambda x: x
    if name == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(F32)
    raise ValueError(f"no such precision: {name}")


def silu(x: Array) -> Array:
    return x * jax.nn.sigmoid(x)


def symlog(x: Array) -> Array:
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x: Array) -> Array:
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1)


def layer_norm(p: Mapping[str, Array], x: Array, eps: float) -> Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


class Model:
    """The networks, as functions of a parameter tree."""

    def __init__(self, shapes: Mapping[str, Any], quant: str = "float32", noise_dtype: Any = jnp.bfloat16):
        self.s = dict(shapes)
        self.q = quantizer(quant)
        self.noise_dtype = noise_dtype
        self.eps = float(shapes.get("layer_norm_eps", 1e-3))
        self.unimix = float(shapes.get("unimix", 0.01))
        self.S, self.D = shapes["stochastic_size"], shapes["discrete_size"]

    # -- building blocks ----------------------------------------------------
    def dense(self, p, x):
        y = jnp.matmul(self.q(x), self.q(p["kernel"]), precision="highest")
        return y + p["bias"] if "bias" in p else y

    def stack(self, p, x, layers: int):
        for i in range(layers):
            x = silu(layer_norm(p[f"LayerNorm_{i}"], self.dense(p[f"Dense_{i}"], x), self.eps))
        return x

    def head(self, p, x, layers: int):
        return self.dense(p["Dense_0"], self.stack(p["DenseStack_0"], x, layers))

    def encode(self, wm, rgb):
        """``rgb`` [N, C, H, W] in [-0.5, 0.5] -> [N, embed]."""
        p = wm["params"]["cnn_encoder"]
        x = jnp.transpose(rgb, (0, 2, 3, 1))
        for i in range(self.s["cnn_stages"]):
            x = jax.lax.conv_general_dilated(
                self.q(x), self.q(p[f"Conv_{i}"]["kernel"]), (2, 2), ((1, 1), (1, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest",
            )
            x = silu(layer_norm(p[f"LayerNorm_{i}"], x, self.eps))
        return x.reshape(x.shape[0], -1)

    def decode(self, wm, latent):
        """[N, latent] -> [N, C, H, W]."""
        p = wm["params"]["cnn_decoder"]
        stages, m = self.s["cnn_stages"], self.s["cnn_channels_multiplier"]
        start = self.s["image_size"] // 2**stages
        x = self.dense(p["Dense_0"], latent).reshape(-1, start, start, 2 ** (stages - 1) * m)
        for i in range(stages):
            x = jax.lax.conv_transpose(
                self.q(x), self.q(p[f"ConvTranspose_{i}"]["kernel"]), (2, 2), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest",
            )
            if i < stages - 1:
                x = silu(layer_norm(p[f"LayerNorm_{i}"], x, self.eps))
            else:
                x = x + p[f"ConvTranspose_{i}"]["bias"]
        return jnp.transpose(x, (0, 3, 1, 2))

    def recurrent(self, wm, x, h):
        p = wm["params"]["rssm"]["recurrent_model"]
        feat = self.stack(p["DenseStack_0"], x, 1)
        g = p["LayerNormGRUCell_0"]
        z = layer_norm(g["LayerNorm_0"], self.dense(g["Dense_0"], jnp.concatenate([h, feat], -1)), self.eps)
        reset, cand, update = jnp.split(z, 3, axis=-1)
        cand = jnp.tanh(jax.nn.sigmoid(reset) * cand)
        update = jax.nn.sigmoid(update - 1)
        return update * cand + (1 - update) * h

    def mix(self, logits):
        """1% uniform mix; [..., S*D] -> [..., S, D] log-probabilities."""
        logits = logits.reshape(logits.shape[:-1] + (-1, self.D))
        probs = (1 - self.unimix) * jax.nn.softmax(logits, -1) + self.unimix / self.D
        return jnp.log(probs)

    def draw(self, key, logits):
        """Straight-through one-hot sample of [..., n] logits.  Departure: the
        Gumbel noise is drawn in ``noise_dtype`` (the program's bits) and added in float32."""
        noise = jax.random.gumbel(key, logits.shape, self.noise_dtype).astype(F32)
        hard = jax.nn.one_hot(jnp.argmax(logits + noise, -1), logits.shape[-1], dtype=F32)
        probs = jax.nn.softmax(logits, -1)
        return hard + probs - jax.lax.stop_gradient(probs)

    def prior_logits(self, wm, h):
        return self.mix(self.head(wm["params"]["rssm"]["transition_model"], h, 1))

    def posterior_logits(self, wm, h, embed):
        return self.mix(self.head(wm["params"]["rssm"]["representation_model"], jnp.concatenate([h, embed], -1), 1))

    def initial(self, wm, n: int) -> Tuple[Array, Array]:
        h0 = jnp.broadcast_to(jnp.tanh(wm["params"]["rssm"]["initial_recurrent_state"]), (n, self.s["recurrent_state_size"]))
        logits = self.prior_logits(wm, h0)
        z0 = jax.nn.one_hot(jnp.argmax(logits, -1), self.D, dtype=F32)
        return h0, z0.reshape(n, -1)

    def flat(self, z):
        return z.reshape(z.shape[:-2] + (-1,))

    def reward_logits(self, wm, latent):
        return self.head(wm["params"]["reward_model"], latent, self.s["mlp_layers"])

    def continue_logits(self, wm, latent):
        return self.head(wm["params"]["continue_model"], latent, self.s["mlp_layers"])

    def critic(self, p, latent):
        return self.head(p["params"], latent, self.s["mlp_layers"])

    def actor_logits(self, p, latent):
        x = self.stack(p["params"]["model"], latent, self.s["mlp_layers"])
        logits = self.dense(p["params"]["heads_0"], x)
        return jnp.log((1 - self.unimix) * jax.nn.softmax(logits, -1) + self.unimix / logits.shape[-1])

    def act(self, p, latent, key):
        return self.draw(jax.random.fold_in(key, 0), self.actor_logits(p, jax.lax.stop_gradient(latent)))


# -- the two-hot head ----------------------------------------------------------
def twohot_mean(logits: Array) -> Array:
    bins = jnp.linspace(-20.0, 20.0, logits.shape[-1], dtype=F32)
    return symexp(jnp.sum(jax.nn.softmax(logits, -1) * bins, -1, keepdims=True))


def twohot_log_prob(logits: Array, x: Array) -> Array:
    """``x`` [..., 1] -> [...]: cross-entropy against the two neighbouring bins of symlog(x)."""
    n = logits.shape[-1]
    bins = jnp.linspace(-20.0, 20.0, n, dtype=F32)
    x = symlog(x)
    below = jnp.clip(jnp.sum((bins <= x).astype(jnp.int32), -1) - 1, 0, n - 1)
    above = jnp.clip(below + 1, 0, n - 1)
    equal = below == above
    to_below = jnp.where(equal, 1.0, jnp.abs(bins[below] - x[..., 0]))
    to_above = jnp.where(equal, 1.0, jnp.abs(bins[above] - x[..., 0]))
    total = to_below + to_above
    target = (
        jax.nn.one_hot(below, n, dtype=F32) * (to_above / total)[..., None]
        + jax.nn.one_hot(above, n, dtype=F32) * (to_below / total)[..., None]
    )
    return jnp.sum(target * jax.nn.log_softmax(logits, -1), -1)


def categorical_kl(p_logits: Array, q_logits: Array) -> Array:
    """KL(p || q) over the last axis, summed over the axis before it."""
    p_logits = jax.nn.log_softmax(p_logits, -1)
    q_logits = jax.nn.log_softmax(q_logits, -1)
    return jnp.sum(jnp.sum(jnp.exp(p_logits) * (p_logits - q_logits), -1), -1)


# -- the optimizer -------------------------------------------------------------
def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros, "count": jnp.zeros((), jnp.int32)}


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g**2) for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree_util.tree_map(lambda g: g * scale, grads), norm


def adam_update(params, grads, state, lr: float, eps: float, b1: float = 0.9, b2: float = 0.999):
    count = state["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g**2, state["nu"], grads)
    c1, c2 = 1 - b1 ** count.astype(F32), 1 - b2 ** count.astype(F32)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), params, mu, nu
    )
    return params, {"mu": mu, "nu": nu, "count": count}


# -- one gradient step ---------------------------------------------------------
def make_step(shapes: Mapping[str, Any], hyper: Mapping[str, Any], quant: str = "float32",
              noise_dtype: Any = jnp.bfloat16) -> Callable:
    """``step(params, opt, moments, batch, key, tau) -> (params, opt, moments, out)``.

    ``out`` has the three losses, the three gradient norms before the clip and
    the gradients as the optimizers get them.
    """
    m = Model(shapes, quant, noise_dtype)
    H, gamma, lmbda = shapes["horizon"], hyper["gamma"], hyper["lmbda"]
    rec = shapes["recurrent_state_size"]
    stoch = m.S * m.D

    def world_model_loss(wm, batch, key):
        T, B = batch["actions"].shape[:2]
        rgb = batch["rgb"]
        embed = m.encode(wm, rgb.reshape((T * B,) + rgb.shape[2:])).reshape(T, B, -1)
        actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], 0)
        is_first = batch["is_first"].at[0].set(1.0)
        h0, z0 = m.initial(wm, B)

        def body(carry, x):
            z, h = carry
            action, emb, first, k = x
            _, k_post = jax.random.split(k)
            action = (1 - first) * action
            h = (1 - first) * h + first * h0
            z = (1 - first) * z + first * z0
            h = m.recurrent(wm, jnp.concatenate([z, action], -1), h)
            prior = m.prior_logits(wm, h)
            post = m.posterior_logits(wm, h, emb)
            z = m.flat(m.draw(k_post, post))
            return (z, h), (h, z, post, prior)

        init = (jnp.zeros((B, stoch), F32), jnp.zeros((B, rec), F32))
        _, (hs, zs, post, prior) = jax.lax.scan(body, init, (actions, embed, is_first, jax.random.split(key, T)))
        latent = jnp.concatenate([zs, hs], -1)
        flat = latent.reshape(T * B, -1)
        recon = m.decode(wm, flat).reshape(rgb.shape)
        observation = jnp.sum((recon - rgb) ** 2, axis=(-3, -2, -1))
        reward = -twohot_log_prob(m.reward_logits(wm, flat).reshape(T, B, -1), batch["rewards"])
        sg = jax.lax.stop_gradient
        free = hyper["kl_free_nats"]
        kl = hyper["kl_dynamic"] * jnp.maximum(categorical_kl(sg(post), prior), free) + hyper[
            "kl_representation"
        ] * jnp.maximum(categorical_kl(post, sg(prior)), free)
        logit = m.continue_logits(wm, flat).reshape(T, B, 1)
        target = 1 - batch["terminated"]
        cont = jnp.sum(jax.nn.softplus(-logit) * target + jax.nn.softplus(logit) * (1 - target), -1)
        loss = jnp.mean(hyper["kl_regularizer"] * kl + observation + reward + hyper["continue_scale_factor"] * cont)
        return loss, (zs, hs)

    def actor_loss(actor, wm, critic, moments, zs, hs, true_continue, k_img, k_act0):
        sg = jax.lax.stop_gradient
        latent0 = jnp.concatenate([zs, hs], -1)
        a0 = m.act(actor, latent0, k_act0)

        def body(carry, k):
            z, h, action = carry
            k_dyn, k_act = jax.random.split(k)
            h = m.recurrent(wm, jnp.concatenate([z, action], -1), h)
            z = m.flat(m.draw(k_dyn, m.prior_logits(wm, h)))
            latent = jnp.concatenate([z, h], -1)
            action = m.act(actor, latent, k_act)
            return (z, h, action), (latent, action)

        _, (latents, actions) = jax.lax.scan(body, (zs, hs, a0), jax.random.split(k_img, H))
        traj = jnp.concatenate([latent0[None], latents], 0)
        acts = jnp.concatenate([a0[None], actions], 0)
        values = twohot_mean(m.critic(critic, traj))
        rewards = twohot_mean(m.reward_logits(wm, traj))
        continues = (jax.nn.sigmoid(m.continue_logits(wm, traj)) > 0.5).astype(F32)
        continues = jnp.concatenate([true_continue[None], continues[1:]], 0)

        cont = continues[1:] * gamma
        interm = rewards[1:] + cont * values[1:] * (1 - lmbda)

        def back(nxt, x):
            val = x[0] + x[1] * lmbda * nxt
            return val, val

        _, lambdas = jax.lax.scan(back, values[-1], (interm, cont), reverse=True)
        discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)
        mom = hyper["moments"]
        flat = sg(lambdas).reshape(-1)
        low = mom["decay"] * moments["low"] + (1 - mom["decay"]) * jnp.quantile(flat, mom["percentile_low"])
        high = mom["decay"] * moments["high"] + (1 - mom["decay"]) * jnp.quantile(flat, mom["percentile_high"])
        invscale = jnp.maximum(1.0 / mom["max"], high - low)
        advantage = (lambdas - low) / invscale - (values[:-1] - low) / invscale
        logp = m.actor_logits(actor, sg(traj))
        log_prob = jnp.sum(sg(acts) * logp, -1, keepdims=True)
        entropy = -jnp.sum(jnp.exp(logp) * logp, -1, keepdims=True)
        loss = -jnp.mean(discount[:-1] * (log_prob[:-1] * sg(advantage) + hyper["ent_coef"] * entropy[:-1]))
        return loss, (sg(traj), sg(lambdas), discount, {"low": low, "high": high})

    def critic_loss(critic, target_critic, traj, lambdas, discount):
        logits = m.critic(critic, traj[:-1])
        target_values = jax.lax.stop_gradient(twohot_mean(m.critic(target_critic, traj[:-1])))
        loss = -twohot_log_prob(logits, lambdas) - twohot_log_prob(logits, target_values)
        return jnp.mean(loss * discount[:-1, ..., 0])

    def step(params, opt, moments, batch, key, tau):
        params, opt = dict(params), dict(opt)
        k_wm, k_img, k_act0 = jax.random.split(key, 3)
        params["target_critic"] = jax.tree_util.tree_map(
            lambda c, t: tau * c + (1 - tau) * t, params["critic"], params["target_critic"]
        )
        (wm_loss, (zs, hs)), g_wm = jax.value_and_grad(world_model_loss, has_aux=True)(params["world_model"], batch, k_wm)
        g_wm, n_wm = clip_by_global_norm(g_wm, hyper["world_model"]["clip"])
        params["world_model"], opt["world_model"] = adam_update(
            params["world_model"], g_wm, opt["world_model"], hyper["world_model"]["lr"], hyper["world_model"]["eps"]
        )
        T, B = batch["actions"].shape[:2]
        zs = jax.lax.stop_gradient(zs).reshape(T * B, -1)
        hs = jax.lax.stop_gradient(hs).reshape(T * B, -1)
        true_continue = (1 - batch["terminated"]).reshape(T * B, 1)
        (a_loss, (traj, lambdas, discount, moments)), g_actor = jax.value_and_grad(actor_loss, has_aux=True)(
            params["actor"], params["world_model"], params["critic"], moments, zs, hs, true_continue, k_img, k_act0
        )
        g_actor, n_actor = clip_by_global_norm(g_actor, hyper["actor"]["clip"])
        params["actor"], opt["actor"] = adam_update(
            params["actor"], g_actor, opt["actor"], hyper["actor"]["lr"], hyper["actor"]["eps"]
        )
        c_loss, g_critic = jax.value_and_grad(critic_loss)(params["critic"], params["target_critic"], traj, lambdas, discount)
        g_critic, n_critic = clip_by_global_norm(g_critic, hyper["critic"]["clip"])
        params["critic"], opt["critic"] = adam_update(
            params["critic"], g_critic, opt["critic"], hyper["critic"]["lr"], hyper["critic"]["eps"]
        )
        out = {
            "losses": jnp.stack([wm_loss, a_loss, c_loss]),
            "grad_norms": jnp.stack([n_wm, n_actor, n_critic]),
            "grads": {"world_model": g_wm, "actor": g_actor, "critic": g_critic},
        }
        return params, opt, moments, out

    return step


def first_steps(shapes, hyper, params, moments, inputs, quant: str = "float32",
                noise_dtype: Any = jnp.bfloat16) -> Dict[str, Any]:
    """Follow the recorded steps from ``params``: the losses and gradient norms
    of each, the first step's gradients, and the parameters after the last."""
    step = jax.jit(make_step(shapes, hyper, quant, noise_dtype))
    params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, F32), params)
    opt = {k: adam_init(params[k]) for k in ("world_model", "actor", "critic")}
    moments = jax.tree_util.tree_map(lambda x: jnp.asarray(x, F32), moments)
    losses, norms, first_grads = [], [], None
    for item in inputs:
        batch = {k: jnp.asarray(v, F32) for k, v in item["batch"].items()}
        params, opt, moments, out = step(params, opt, moments, batch, jnp.asarray(item["key"]), jnp.float32(item["tau"]))
        losses.append(jax.device_get(out["losses"]))
        norms.append(jax.device_get(out["grad_norms"]))
        if first_grads is None:
            first_grads = jax.device_get(out["grads"])
    return {"losses": losses, "grad_norms": norms, "first_grads": first_grads, "params_after": jax.device_get(params)}


def player_step(shapes, params, state, rgb, key, quant: str = "float32") -> Dict[str, Any]:
    """The forward pass that chooses an action: encode the frame, step the
    GRU from the player's state, draw the posterior and the action.  The
    program keeps the player in float32, so the noise is float32 here."""
    m = Model(shapes, quant, F32)
    wm, actor = params["world_model"], params["actor"]
    k_post, k_act = jax.random.split(jnp.asarray(key))
    embed = m.encode(wm, jnp.asarray(rgb, F32))
    h = m.recurrent(
        wm, jnp.concatenate([jnp.asarray(state["stochastic"], F32), jnp.asarray(state["actions"], F32)], -1),
        jnp.asarray(state["recurrent"], F32),
    )
    z = m.flat(m.draw(k_post, m.posterior_logits(wm, h, embed)))
    action = m.act(actor, jnp.concatenate([z, h], -1), k_act)
    return jax.device_get({"recurrent": h, "stochastic": z, "actions": action})
