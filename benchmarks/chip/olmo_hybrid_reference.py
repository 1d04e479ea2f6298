"""A plain Olmo-Hybrid policy under PPO: the reference the timed path is held to.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
flax, no optax, no cache, no chunking, no kernel, nothing of the program
imported.  A sequence is computed whole: the gated delta rule
(arXiv:2412.06464) as its recurrence, one token after the other under
``lax.scan``; attention as one masked softmax over every key of the episode.
What the program carries between rollouts (the linear layers' state and
convolution tail, the full layer's keys and values of the running episode)
comes in as plain arrays, the *snapshot*: the reference starts a sequence
from it as from a prefix it was handed, and keeps nothing.

It reads the weights by the names of the program's parameter tree (that tree
is the interface, as a checkpoint's would be).  The layer equations are those
of ISSUE 31, from ``config.json`` of ``allenai/Olmo-Hybrid-7B``; what the
config does not say is listed under ``assumed`` in the configuration's file:

- pre-norm blocks (``x + mixer(norm(x))``, ``x + mlp(norm(x))``), no norm on
  a sublayer's output and none on the full layer's ``q``/``k``;
- a value head: one linear read-out of the final normed state;
- a chip's share: the layer computes the heads it holds (``heads_held`` of
  ``heads_total``) and its partial sum goes on; logits are over the ids held.

``quant`` rounds both operands of every matrix multiplication; the control
puts the nearest lower precision there.

The update is followed too (:func:`follow_update`): the whole gradient of each
minibatch in turn, a layer at a time (:class:`Gradient`), and AdamW behind its
global-norm clip written out (:func:`adamw`), from the parameters the program
began with to where its 8 gradient steps should have left them.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def quantizer(name: str) -> Callable[[Array], Array]:
    if name == "float32":
        return lambda x: x
    if name == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(F32)
    raise ValueError(f"no such precision: {name}")


def silu(x: Array) -> Array:
    return x * jax.nn.sigmoid(x)


def rms_norm(scale: Array, x: Array, eps: float) -> Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


class Model:
    """The forward pass of one chip's share, a sequence at a time.

    ``shapes`` is the ``shapes`` object of the configuration's file."""

    def __init__(self, shapes: Mapping[str, Any], quant: str = "float32"):
        self.s = dict(shapes)
        self.q = quantizer(quant)
        self.eps = float(shapes["rms_norm_eps"])

    def mm(self, x: Array, w: Array) -> Array:
        return jnp.matmul(self.q(x), self.q(w), precision=_HI)

    # -- the linear layer -----------------------------------------------------
    def conv(self, w: Array, x: Array, tail: Array, seg: Array) -> Array:
        """Depthwise causal convolution of 4 taps, then SiLU.  ``x`` is
        ``[T, C]``, ``tail`` the 3 inputs before it, ``seg`` the episode each
        position belongs to (0: the one the tail belongs to): a tap reaches
        back only inside its own episode."""
        taps = w.shape[0]
        padded = jnp.concatenate([tail, x], axis=0)  # [taps - 1 + T, C]
        seg_padded = jnp.concatenate([jnp.zeros(taps - 1, seg.dtype), seg])
        out = jnp.zeros_like(x)
        T = x.shape[0]
        for j in range(taps):  # tap j multiplies the input taps - 1 - j steps back
            same = (seg_padded[j:j + T] == seg)[:, None]
            out = out + jnp.where(same, padded[j:j + T] * w[j], 0.0)
        return silu(out)

    def delta_rule(self, S: Array, q: Array, k: Array, v: Array, log_a: Array, b: Array, resets: Array) -> Array:
        """The recurrence, a token at a time.  One head: ``S`` ``[dv, dk]``,
        ``q``/``k`` ``[T, dk]``, ``v`` ``[T, dv]``, ``log_a``/``b``/``resets`` ``[T]``."""

        def step(S, xs):
            q, k, v, log_a, b, reset = xs
            S = jnp.where(reset > 0, 0.0, S) * jnp.exp(log_a)
            S = S - b * jnp.outer(jnp.matmul(S, k, precision=_HI), k) + b * jnp.outer(v, k)
            return S, jnp.matmul(S, q, precision=_HI)

        return jax.lax.scan(step, S, (q, k, v, log_a, b, resets))[1]

    def linear_layer(self, p: Mapping[str, Any], x: Array, resets: Array, state: Mapping[str, Array]) -> Array:
        """``x`` ``[T, D]`` of one sequence; ``state``: ``S`` ``[H, dv, dk]``, ``conv`` ``[3, H (2 dk + dv)]``."""
        s = self.s
        H, dk, dv = s["heads_held"], s["linear_key_head_dim"], s["linear_value_head_dim"]
        T = x.shape[0]
        seg = jnp.cumsum(resets.astype(jnp.int32))
        widths = (H * dk, H * dk, H * dv)
        tails = jnp.split(state["conv"], (widths[0], widths[0] + widths[1]), axis=-1)
        q, k, v = (
            self.conv(p[f"{n}_conv"]["kernel"], self.mm(x, p[f"{n}_proj"]["kernel"]), tail, seg)
            for n, tail in zip("qkv", tails)
        )
        q, k, v = q.reshape(T, H, dk), k.reshape(T, H, dk), v.reshape(T, H, dv)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        log_a = -jnp.exp(p["A_log"]) * jax.nn.softplus(self.mm(x, p["a_proj"]["kernel"]) + p["dt_bias"])  # [T, H]
        b = jax.nn.sigmoid(self.mm(x, p["b_proj"]["kernel"]))
        if s["linear_allow_neg_eigval"]:
            b = 2.0 * b
        per_head = jax.vmap(self.delta_rule, in_axes=(0, 1, 1, 1, 1, 1, None), out_axes=1)
        o = per_head(state["S"], q, k, v, log_a, b, resets)  # [T, H, dv]
        gate = silu(self.mm(x, p["g_proj"]["kernel"])).reshape(T, H, dv)
        y = rms_norm(p["o_norm"]["scale"], o, self.eps) * gate
        return self.mm(y.reshape(T, H * dv), p["o_proj"]["kernel"])

    # -- the full layer -------------------------------------------------------
    def full_layer(self, p: Mapping[str, Any], x: Array, resets: Array, cache: Mapping[str, Array], held: Array) -> Array:
        """Causal softmax attention over the keys of the same episode, no
        rotary phases.  ``cache``: ``k``/``v`` ``[H, L, dh]`` of the running
        episode, of which the first ``held`` positions are its own."""
        s = self.s
        H, dh = s["heads_held"], s["hidden_size"] // s["heads_total"]
        T, L = x.shape[0], cache["k"].shape[1]
        q, k, v = (self.mm(x, p[f"{n}_proj"]["kernel"]).reshape(T, H, dh) for n in "qkv")
        seg = jnp.cumsum(resets.astype(jnp.int32))
        keys = jnp.concatenate([jnp.swapaxes(cache["k"].astype(F32), 0, 1), k], axis=0)  # [L + T, H, dh]
        values = jnp.concatenate([jnp.swapaxes(cache["v"].astype(F32), 0, 1), v], axis=0)
        carried = (jnp.arange(L)[None, :] < held) & (seg[:, None] == 0)  # [T, L]
        own = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]) & (seg[None, :] == seg[:, None])
        mask = jnp.concatenate([carried, own], axis=1)
        scores = jnp.einsum("thd,shd->hts", self.q(q), self.q(keys), precision=_HI) * dh ** -0.5
        weights = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", self.q(weights), self.q(values), precision=_HI)
        return self.mm(o.reshape(T, H * dh), p["o_proj"]["kernel"])

    # -- the whole model ------------------------------------------------------
    def mlp(self, p: Mapping[str, Any], x: Array) -> Array:
        return self.mm(silu(self.mm(x, p["gate_proj"]["kernel"])) * self.mm(x, p["up_proj"]["kernel"]), p["down_proj"]["kernel"])

    def block(self, kind: str, layer: Mapping[str, Any], x: Array, resets: Array, state: Mapping[str, Array], held: Array) -> Array:
        """One pre-norm residual block over one sequence: the mixer of its kind, then the MLP."""
        h = rms_norm(layer["mixer_norm"]["scale"], x, self.eps)
        if kind == "linear_attention":
            x = x + self.linear_layer(layer["mixer"], h, resets, state)
        else:
            x = x + self.full_layer(layer["mixer"], h, resets, state, held)
        return x + self.mlp(layer["mlp"], rms_norm(layer["mlp_norm"]["scale"], x, self.eps))

    def head(self, p: Mapping[str, Any], x: Array) -> Tuple[Array, Array]:
        """The final norm, the logits over the held ids and the value; ``p`` holds ``final_norm``, ``lm_head``, ``value_head``."""
        x = rms_norm(p["final_norm"]["scale"], x, self.eps)
        return self.mm(x, p["lm_head"]["kernel"]), self.mm(x, p["value_head"]["kernel"])[..., 0]

    def sequence(self, params: Mapping[str, Any], tokens: Array, resets: Array, snapshot: Mapping[str, Any]) -> Tuple[Array, Array]:
        """One sequence from its snapshot: ``tokens``/``resets`` ``[T]``;
        returns logits ``[T, ids held]`` and values ``[T]``."""
        p = params["params"]
        x = p["embed_tokens"]["kernel"][tokens]
        for i, kind in enumerate(self.s["layer_types"]):
            x = self.block(kind, p[f"layers_{i}"], x, resets, snapshot["layers"][i], snapshot["pos"])
        return self.head(p, x)

    def batch(self, params: Mapping[str, Any], tokens: Array, resets: Array, snapshot: Mapping[str, Any]) -> Tuple[Array, Array]:
        """``[S, T]`` sequences, each from its row of the snapshot."""
        return jax.vmap(self.sequence, in_axes=(None, 0, 0, 0))(params, tokens, resets, snapshot)


def ppo_terms(logits: Array, values: Array, batch: Mapping[str, Array], clip_coef: float) -> Tuple[Array, Array, Array]:
    """Clipped-surrogate policy loss, value loss and entropy loss, each a mean
    over every token of the minibatch; advantages as the rollout gave them."""
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], axis=-1)[..., 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    ratio = jnp.exp(logp - batch["logprobs"])
    adv = batch["advantages"]
    policy = jnp.mean(jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 1 - clip_coef, 1 + clip_coef)))
    value = jnp.mean(0.5 * (values - batch["returns"]) ** 2)
    return policy, value, -jnp.mean(entropy)


def player_readings(shapes, params, batch, snapshot, quant: str = "float32", rows: int = 8) -> Dict[str, Array]:
    """The full-sequence forward of ``batch`` (``tokens``, ``resets``,
    ``actions`` of ``[S, T]``) from ``snapshot``, ``rows`` sequences at a
    time: the log-probability of every stored action and every value."""
    model = Model(shapes, quant)

    @jax.jit
    def some(params, tokens, resets, actions, snapshot):
        logits, values = model.batch(params, tokens, resets, snapshot)
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), actions[..., None], -1)[..., 0]
        return logp, values

    S = batch["tokens"].shape[0]
    out = []
    for lo in range(0, S, rows):
        cut = lambda x: x[lo:lo + rows]  # noqa: E731
        out.append(some(params, cut(batch["tokens"]), cut(batch["resets"]), cut(batch["actions"]),
                        jax.tree_util.tree_map(cut, snapshot)))
    return {"logprobs": jnp.concatenate([o[0] for o in out]), "values": jnp.concatenate([o[1] for o in out])}


HEAD = ("final_norm", "lm_head", "value_head")


class Gradient:
    """The three losses of a minibatch and the gradient of their weighted sum,
    whole.  Computed a layer at a time, so that it fits beside the optimizer's
    state at the published widths: forward keeping each layer's input; then
    from the head down, the layer's vector-Jacobian product ``rows`` sequences
    at a time (the recurrence's saved states of a few sequences of one layer
    are all that is held beside the parameters and the gradient), the layer's
    gradient summed over the minibatch.  The same numbers as ``jax.grad`` of
    the whole loss (``tests/test_benchmark`` holds them to the program's).
    Made once for every minibatch of its shape: the three programs compile once."""

    def __init__(self, shapes, hyper, quant: str = "float32", clip_coef: Optional[float] = None):
        model = Model(shapes, quant)
        clip = float(hyper["clip_coef"] if clip_coef is None else clip_coef)
        self.kinds = list(shapes["layer_types"])

        def blocks(kind, layer, x, resets, state, held):
            return jax.vmap(lambda x, r, s, h: model.block(kind, layer, x, r, s, h))(x, resets, state, held)

        def backward(kind, layer, x, resets, state, held, dy):
            return jax.vjp(lambda layer, x: blocks(kind, layer, x, resets, state, held), layer, x)[1](dy)

        def head_and_loss(head, x, batch):
            def total(head, x):
                logits, values = model.head(head, x)
                policy, value, entropy = ppo_terms(logits, values, batch, clip)
                return policy + hyper["vf_coef"] * value + hyper["ent_coef"] * entropy, jnp.stack([policy, value, entropy])

            _, vjp, losses = jax.vjp(total, head, x, has_aux=True)
            g_head, dx = vjp(jnp.ones((), F32))
            return losses, g_head, dx

        self.forward = jax.jit(blocks, static_argnums=0)
        self.backward = jax.jit(backward, static_argnums=0)
        self.head_and_loss = jax.jit(head_and_loss)

    def __call__(self, params, batch, snapshot, rows: int = 2) -> Dict[str, Any]:
        """``batch`` leaves are ``[S, T]``, ``snapshot`` each sequence's."""
        p, kinds = params["params"], self.kinds
        x = p["embed_tokens"]["kernel"][batch["tokens"]]
        inputs = []
        for i, kind in enumerate(kinds):
            inputs.append(x)
            x = self.forward(kind, p[f"layers_{i}"], x, batch["resets"], snapshot["layers"][i], snapshot["pos"])
        losses, g_head, dx = self.head_and_loss({k: p[k] for k in HEAD}, x, batch)
        grads: Dict[str, Any] = dict(g_head)
        del g_head, x
        for i in reversed(range(len(kinds))):
            total, parts = None, []
            for lo in range(0, batch["tokens"].shape[0], rows):
                cut = lambda v: v[lo:lo + rows]  # noqa: E731
                g, dx_part = self.backward(kinds[i], p[f"layers_{i}"], cut(inputs[i]), cut(batch["resets"]),
                                           jax.tree_util.tree_map(cut, snapshot["layers"][i]), cut(snapshot["pos"]), cut(dx))
                total = g if total is None else jax.tree_util.tree_map(jnp.add, total, g)
                parts.append(dx_part)
            grads[f"layers_{i}"] = total
            dx = jnp.concatenate(parts, axis=0)
            inputs[i] = None
            del total, g
        grads["embed_tokens"] = {"kernel": jnp.zeros_like(p["embed_tokens"]["kernel"]).at[batch["tokens"]].add(dx)}
        return {"losses": losses, "grads": {"params": grads}}


def leaf_norms(tree: Any) -> list:
    """The norm of every leaf, in the order ``jax.tree_util.tree_leaves`` gives the program's tree too."""
    return [float(jnp.linalg.norm(g.astype(F32))) for g in jax.tree_util.tree_leaves(tree)]


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 3, 4))
def _adamw_entry(hyper: Tuple[float, ...], p, g, m, v, norm, count):
    b1, b2, eps, lr, decay, max_norm = hyper
    tm = jax.tree_util.tree_map
    g = tm(lambda g: jnp.where(norm < max_norm, g, g / norm * max_norm), g)
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    p = tm(lambda p, m, v: p - lr * (m / (1 - b1 ** count) / (jnp.sqrt(v / (1 - b2 ** count)) + eps) + decay * p), p, m, v)
    return p, m, v


def adamw(hyper: Mapping[str, float], params, grads, moments, count: int):
    """One step of AdamW behind a clip of the gradient's global norm, written
    out: ``g <- g max_grad_norm / |g|`` where ``|g|`` over the whole tree is
    not under it; ``m <- b1 m + (1 - b1) g``, ``v <- b2 v + (1 - b2) g^2``;
    ``p <- p - lr (m^ / (sqrt(v^) + eps) + weight_decay p)`` with ``m^``, ``v^``
    the moments over ``1 - b^count``.  One top-level entry of the tree at a
    time, the old parameters and moments given up to the new; ``moments`` is
    ``None`` before the first step."""
    h = tuple(float(hyper[k]) for k in ("adam_b1", "adam_b2", "adam_eps", "lr", "weight_decay", "max_grad_norm"))
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads)))
    p, g = dict(params["params"]), grads["params"]
    m, v = ({}, {}) if moments is None else (dict(moments[0]), dict(moments[1]))
    for name in list(p):
        if moments is None:
            m[name] = jax.tree_util.tree_map(jnp.zeros_like, p[name])
            v[name] = jax.tree_util.tree_map(jnp.zeros_like, p[name])
        p[name], m[name], v[name] = _adamw_entry(h, p[name], g[name], m[name], v[name], norm, jnp.asarray(count, F32))
    return {"params": p}, (m, v)


def follow_update(shapes, hyper, params, batch, snapshot, minibatches, quant: str = "float32", rows: int = 1,
                  clip_coef: Optional[float] = None) -> Dict[str, Any]:
    """One update as the loop makes it, from ``params`` (which it uses up) and
    Adam's moments at nought: for each row of ``minibatches`` (sequence
    numbers) in turn the losses and the gradient on those sequences, then one
    step of :func:`adamw`.  ``batch`` and ``snapshot`` hold every sequence of
    the rollout and may lie on the host: a minibatch's share is cut from them.
    Returns every gradient step's losses and the leaf norms of its gradient as
    the optimizer got it, and the parameters after the last step."""
    gradient = Gradient(shapes, hyper, quant, clip_coef)
    losses, norms, moments = [], [], None
    for count, picked in enumerate(minibatches, start=1):
        cut = lambda tree: jax.tree_util.tree_map(lambda x: jnp.asarray(x[picked]), tree)  # noqa: E731
        got = gradient(params, cut(batch), cut(snapshot), rows=rows)
        losses.append(got["losses"])
        norms.append(leaf_norms(got["grads"]))
        params, moments = adamw(hyper, params, got.pop("grads"), moments, count)
    return {"losses": jnp.stack(losses), "grad_norms": norms, "params": params}
