"""A plain sparse-attention, routed-expert policy under PPO: the reference the timed path is held to.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
flax, no optax, no cache, no blocks, no kernel, nothing of the program
imported.  A sequence is computed whole: the index score of every query
against every key, ``jax.lax.top_k`` of it, one masked softmax over every key
of the episode, every held expert for every token.  What the program carries
between rollouts (the keys, values and index keys of the running episode)
comes in as plain arrays, the *snapshot*: the reference starts a sequence from
it as from a prefix it was handed, and keeps nothing.

It reads the weights by the names of the program's parameter tree.  The layer
equations are ISSUE 38's, from ``config.json`` of
``Kwai-Keye/Keye-VL-2.0-30B-A3B``; where they depart from the published
description, or the description is silent, the configuration's file lists it
under ``assumed``:

- RMSNorm over each head of ``q`` and ``k`` (the Qwen3-MoE family's, whose keys the config has);
- rotary pairs are dimensions ``i`` and ``i + d / 2`` (the ``rotate_half`` convention);
- the indexer (DeepSeek-V3.2-Exp's): rotary phases on all 64 dimensions of
  its queries and key by the temporal stream at the model's base, a LayerNorm
  (eps 1e-6) on its key, the scales ``16^-1/2 64^-1/2`` on its weights, its
  input under ``stop_gradient``; its loss the sum over the layers of the mean
  over the queries of ``KL(p || softmax(I))`` over the selection, coefficient 1;
- no shared expert, no load-balancing term; the router's product, like the
  index score, float32 at ``highest`` whatever ``quant`` says (both feed a discrete choice);
- a value head: one linear read-out of the final normed state;
- a chip's share: the router picks over all ``experts_total``, the layer
  computes the picks on the ``experts_held`` it holds and that partial sum goes
  on; logits are over the ids held.  Every other chip's tokens are absent.

``quant`` rounds both operands of every other matrix multiplication; the
control puts the nearest lower precision there.  The update is followed as
``olmo_hybrid_reference.py`` follows it (:func:`follow_update`), a layer at a
time, with its ``adamw``.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.chip.olmo_hybrid_reference import F32, _HI, adamw, leaf_norms, ppo_terms, quantizer, rms_norm, silu

Array = jax.Array
HEAD = ("final_norm", "lm_head", "value_head")
INDEXER = "indexer"


def layer_norm(p: Mapping[str, Array], x: Array, eps: float = 1e-6) -> Array:
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"] + p["bias"]


def rotate(x: Array, angles: Array) -> Array:
    """``x`` ``[T, H, d]`` turned by ``angles`` ``[T, d / 2]``: pair ``i`` is dimensions ``i`` and ``i + d / 2``."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def episode_index(resets: Array, held: Array) -> Array:
    """``[T]``: a token's index in its episode, the first episode ``held`` tokens in."""
    t = jnp.arange(resets.shape[0])
    start = jax.lax.cummax(jnp.where(resets > 0, t, -1))
    return jnp.where(start < 0, held + t, t - start)


class Model:
    """The forward pass of one chip's share, a sequence at a time.

    ``shapes`` is the ``shapes`` object of the configuration's file."""

    def __init__(self, shapes: Mapping[str, Any], quant: str = "float32"):
        self.s = dict(shapes)
        self.q = quantizer(quant)
        self.eps = float(shapes["rms_norm_eps"])

    def mm(self, x: Array, w: Array) -> Array:
        return jnp.matmul(self.q(x), self.q(w), precision=_HI)

    # -- attention over the indexer's selection ----------------------------------
    def attention(self, p: Mapping[str, Any], x: Array, resets: Array, cache: Mapping[str, Array], held: Array,
                  positions: Array) -> Tuple[Array, Array, Array, Array]:
        """``x`` ``[T, D]`` of one sequence; ``cache``: ``k``/``v`` ``[1, L, Hkv dh]``
        and ``ki`` ``[1, L, di]`` of the running episode, of which the first
        ``held`` positions are its own; ``positions`` ``[3, T]``.  Returns the
        layer's output, the indexer's KL summed over the queries, and the
        attended and visible positions counted over them."""
        s = self.s
        Hq, G, dh, Hi, di = s["num_heads"], s["num_kv_heads"], s["head_dim"], s["indexer_heads"], s["indexer_head_dim"]
        T, L = x.shape[0], cache["ki"].shape[1]
        theta = float(s["rope_theta"])
        # the main heads: pair i turns by the stream its section names
        stream = jnp.repeat(jnp.arange(3), jnp.asarray(s["mrope_section"]), total_repeat_length=dh // 2)
        angles = positions.astype(F32).T[:, stream] * theta ** (-jnp.arange(dh // 2, dtype=F32) / (dh // 2))
        q = rotate(rms_norm(p["q_norm"]["scale"], self.mm(x, p["q_proj"]["kernel"]).reshape(T, Hq, dh), self.eps), angles)
        k = rotate(rms_norm(p["k_norm"]["scale"], self.mm(x, p["k_proj"]["kernel"]).reshape(T, G, dh), self.eps), angles)
        v = self.mm(x, p["v_proj"]["kernel"]).reshape(T, G, dh)
        # the indexer: float32 at highest whatever ``quant`` says, its input a constant
        xi, pi = jax.lax.stop_gradient(x), p[INDEXER]
        angles_i = positions[0].astype(F32)[:, None] * theta ** (-jnp.arange(di // 2, dtype=F32) / (di // 2))
        qi = rotate(jnp.matmul(xi, pi["q_proj"]["kernel"], precision=_HI).reshape(T, Hi, di), angles_i)
        ki = rotate(layer_norm(pi["k_norm"], jnp.matmul(xi, pi["k_proj"]["kernel"], precision=_HI))[:, None], angles_i)[:, 0]
        w = jnp.matmul(xi, pi["w_proj"]["kernel"], precision=_HI) * (Hi ** -0.5 * di ** -0.5)

        keys = jnp.concatenate([cache["k"][0].astype(F32).reshape(L, G, dh), k], axis=0)  # [L + T, G, dh]
        values = jnp.concatenate([cache["v"][0].astype(F32).reshape(L, G, dh), v], axis=0)
        index_keys = jnp.concatenate([cache["ki"][0].astype(F32), ki], axis=0)
        seg = jnp.cumsum(resets.astype(jnp.int32))
        carried = (jnp.arange(L)[None, :] < held) & (seg[:, None] == 0)  # [T, L]
        own = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]) & (seg[None, :] == seg[:, None])
        visible = jnp.concatenate([carried, own], axis=1)

        per_head = jax.nn.relu(jnp.einsum("thd,sd->ths", qi, index_keys, precision=_HI))
        scores = jnp.sum(per_head * w[..., None], axis=1) + 0.0  # [T, L + T]
        scores = jnp.where(visible, scores, -jnp.inf)
        _, picked = jax.lax.top_k(jax.lax.stop_gradient(scores), min(int(s["topk"]), L + T))  # ties to the lower position
        selected = jnp.zeros(visible.shape, bool).at[jnp.arange(T)[:, None], picked].set(True) & visible

        qg = q.reshape(T, G, Hq // G, dh)  # query head h reads key/value head h // (Hq / G)
        att = jnp.einsum("tgrd,sgd->grts", self.q(qg), self.q(keys), precision=_HI) * dh ** -0.5
        weights = jax.nn.softmax(jnp.where(selected, att, -jnp.inf), axis=-1)
        o = jnp.einsum("grts,sgd->tgrd", self.q(weights), self.q(values), precision=_HI).reshape(T, Hq * dh)

        target = jax.lax.stop_gradient(jnp.sum(weights, axis=(0, 1)) / Hq)  # [T, L + T], sums to one over the selection
        log_q = jax.nn.log_softmax(jnp.where(selected, scores, -jnp.inf), axis=-1)
        kl = jnp.where(selected, target * (jnp.log(jnp.where(target > 0, target, 1.0)) - jnp.where(selected, log_q, 0.0)), 0.0)
        return self.mm(o, p["o_proj"]["kernel"]), jnp.sum(kl), jnp.sum(selected), jnp.sum(visible)

    # -- the experts held ------------------------------------------------------------
    def experts(self, p: Mapping[str, Any], x: Array) -> Tuple[Array, Array]:
        """``sum_{e in top(x), e held} g_e Expert_e(x)`` with the gates of the full top: ``x`` ``[T, D]``."""
        s = self.s
        held, first = int(s["experts_held"]), int(s["expert_share"]) * int(s["experts_held"])
        gates, picked = jax.lax.top_k(jax.nn.softmax(jnp.matmul(x, p["router"]["kernel"], precision=_HI), axis=-1), int(s["experts_per_token"]))
        if s["norm_topk_prob"]:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        # the gate of every held expert for every token, nought where the token did not pick it; every held expert for every token
        held_gates = jnp.sum(jnp.where(picked[..., None] == first + jnp.arange(held), gates[..., None], 0.0), axis=1)  # [T, held]
        expert = lambda w1, w3, w2: self.mm(silu(self.mm(x, w1)) * self.mm(x, w3), w2)  # noqa: E731
        y = jnp.einsum("te,etd->td", held_gates, jax.vmap(expert)(p["w1"], p["w3"], p["w2"]), precision=_HI)
        return y, jnp.sum((picked >= first) & (picked < first + held))

    # -- the whole model ---------------------------------------------------------------
    def block(self, layer: Mapping[str, Any], x: Array, resets: Array, cache: Mapping[str, Array], held: Array,
              positions: Array) -> Tuple[Array, Array, Array]:
        """One pre-norm residual block over one sequence; also the indexer's KL summed over
        the queries and ``[attended, visible, picks held]``."""
        y, kl, attended, visible = self.attention(layer["attn"], rms_norm(layer["attn_norm"]["scale"], x, self.eps), resets, cache, held, positions)
        x = x + y
        y, picks_held = self.experts(layer["moe"], rms_norm(layer["moe_norm"]["scale"], x, self.eps))
        return x + y, kl, jnp.stack([attended, visible, picks_held]).astype(F32)

    def head(self, p: Mapping[str, Any], x: Array) -> Tuple[Array, Array]:
        x = rms_norm(p["final_norm"]["scale"], x, self.eps)
        return self.mm(x, p["lm_head"]["kernel"]), self.mm(x, p["value_head"]["kernel"])[..., 0]

    def positions_of(self, resets: Array, held: Array, positions: Optional[Array]) -> Array:
        return jnp.broadcast_to(episode_index(resets, held)[None], (3,) + resets.shape) if positions is None else positions

    def sequence(self, params: Mapping[str, Any], tokens: Array, resets: Array, snapshot: Mapping[str, Any],
                 positions: Optional[Array] = None) -> Tuple[Array, Array, Array, Array]:
        """One sequence from its snapshot: ``tokens``/``resets`` ``[T]``; returns
        logits ``[T, ids held]``, values ``[T]``, the indexers' KL summed over layers
        and queries, and the three counts summed over the layers."""
        p = params["params"]
        positions = self.positions_of(resets, snapshot["pos"], positions)
        x = p["embed_tokens"]["kernel"][tokens]
        kl, counts = jnp.zeros((), F32), jnp.zeros((3,), F32)
        for i in range(int(self.s["num_layers"])):
            x, layer_kl, layer_counts = self.block(p[f"layers_{i}"], x, resets, snapshot["layers"][i], snapshot["pos"], positions)
            kl, counts = kl + layer_kl, counts + layer_counts
        return self.head(p, x) + (kl, counts)

    def batch(self, params, tokens, resets, snapshot):
        """``[S, T]`` sequences, each from its row of the snapshot."""
        return jax.vmap(self.sequence, in_axes=(None, 0, 0, 0))(params, tokens, resets, snapshot)


def shares(shapes: Mapping[str, Any], counts: Array, queries: int) -> Dict[str, Array]:
    """What the update reports of its counts: attended over visible positions, held over all picks."""
    picks = queries * int(shapes["num_layers"]) * int(shapes["experts_per_token"])
    return {"attended_share": counts[0] / jnp.maximum(counts[1], 1), "picks_held_share": counts[2] / picks}


def player_readings(shapes, params, batch, snapshot, quant: str = "float32", rows: int = 1) -> Dict[str, Array]:
    """The full-sequence forward of ``batch`` (``tokens``, ``resets``,
    ``actions`` of ``[S, T]``) from ``snapshot``, ``rows`` sequences at a
    time: the log-probability of every stored action and every value."""
    model = Model(shapes, quant)

    @jax.jit
    def some(params, tokens, resets, actions, snapshot):
        logits, values, _, _ = model.batch(params, tokens, resets, snapshot)
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), actions[..., None], -1)[..., 0]
        return logp, values

    out = []
    for lo in range(0, batch["tokens"].shape[0], rows):
        cut = lambda x: jnp.asarray(x[lo:lo + rows])  # noqa: E731
        out.append(some(params, cut(batch["tokens"]), cut(batch["resets"]), cut(batch["actions"]), jax.tree_util.tree_map(cut, snapshot)))
    return {"logprobs": jnp.concatenate([o[0] for o in out]), "values": jnp.concatenate([o[1] for o in out])}


class Gradient:
    """The losses of a minibatch (the three of PPO and the indexers') and the
    gradient of their weighted sum, whole, a layer at a time as
    ``olmo_hybrid_reference.Gradient`` computes it: forward keeping each
    layer's input; then from the head down the layer's vector-Jacobian product
    ``rows`` sequences at a time, with the cotangent of the layer's own KL
    beside that of its output.  The same numbers as ``jax.grad`` of the whole
    loss (``tests/test_benchmark`` holds them to the program's)."""

    def __init__(self, shapes, hyper, quant: str = "float32", clip_coef: Optional[float] = None):
        model = Model(shapes, quant)
        clip = float(hyper["clip_coef"] if clip_coef is None else clip_coef)
        self.layers, self.model = int(shapes["num_layers"]), model
        self.index_coef = float(shapes.get("index_loss_coef", 1.0))

        def blocks(layer, x, resets, cache, held, positions):
            y, kl, counts = jax.vmap(lambda x, r, c, h, p: model.block(layer, x, r, c, h, p))(x, resets, cache, held, positions)
            return y, jnp.sum(kl), jnp.sum(counts, axis=0)

        def backward(layer, x, resets, cache, held, positions, dy, dkl):
            _, vjp, _ = jax.vjp(lambda layer, x: (blocks(layer, x, resets, cache, held, positions)[:2], None), layer, x, has_aux=True)
            return vjp((dy, dkl))

        def head_and_loss(head, x, batch):
            def total(head, x):
                logits, values = model.head(head, x)
                policy, value, entropy = ppo_terms(logits, values, batch, clip)
                return policy + hyper["vf_coef"] * value + hyper["ent_coef"] * entropy, jnp.stack([policy, value, entropy])

            _, vjp, losses = jax.vjp(total, head, x, has_aux=True)
            g_head, dx = vjp(jnp.ones((), F32))
            return losses, g_head, dx

        self.forward, self.backward, self.head_and_loss = jax.jit(blocks), jax.jit(backward), jax.jit(head_and_loss)

    def __call__(self, params, batch, snapshot, rows: int = 1) -> Dict[str, Any]:
        """``batch`` leaves are ``[S, T]``, ``snapshot`` each sequence's."""
        p = params["params"]
        queries = batch["tokens"].size
        positions = jax.vmap(lambda r, h: self.model.positions_of(r, h, None))(batch["resets"], snapshot["pos"])
        x = p["embed_tokens"]["kernel"][batch["tokens"]]
        inputs, kl, counts = [], jnp.zeros((), F32), jnp.zeros((3,), F32)
        for i in range(self.layers):
            inputs.append(x)
            x, layer_kl, layer_counts = self.forward(p[f"layers_{i}"], x, batch["resets"], snapshot["layers"][i], snapshot["pos"], positions)
            kl, counts = kl + layer_kl, counts + layer_counts
        losses, g_head, dx = self.head_and_loss({k: p[k] for k in HEAD}, x, batch)
        grads: Dict[str, Any] = dict(g_head)
        del g_head, x
        dkl = jnp.asarray(self.index_coef / queries, F32)
        for i in reversed(range(self.layers)):
            total, parts = None, []
            for lo in range(0, batch["tokens"].shape[0], rows):
                cut = lambda v: v[lo:lo + rows]  # noqa: E731
                g, dx_part = self.backward(p[f"layers_{i}"], cut(inputs[i]), cut(batch["resets"]),
                                           jax.tree_util.tree_map(cut, snapshot["layers"][i]), cut(snapshot["pos"]), cut(positions), cut(dx), dkl)
                total = g if total is None else jax.tree_util.tree_map(jnp.add, total, g)
                parts.append(dx_part)
            grads[f"layers_{i}"] = total
            dx = jnp.concatenate(parts, axis=0)
            inputs[i] = None
            del total, g
        grads["embed_tokens"] = {"kernel": jnp.zeros_like(p["embed_tokens"]["kernel"]).at[batch["tokens"]].add(dx)}
        report = shares(self.model.s, counts, queries)
        return {"losses": jnp.concatenate([losses, jnp.stack([kl / queries, report["attended_share"], report["picks_held_share"]])]),
                "grads": {"params": grads}}


def follow_update(shapes, hyper, params, batch, snapshot, minibatches, quant: str = "float32", rows: int = 1,
                  clip_coef: Optional[float] = None) -> Dict[str, Any]:
    """One update as the loop makes it, from ``params`` (which it uses up) and
    Adam's moments at nought: for each row of ``minibatches`` (sequence
    numbers) in turn the losses and the gradient on those sequences, then one
    step of ``adamw``.  Returns every gradient step's six reported numbers
    (the three PPO losses, the indexers' loss, the attended and the held-pick
    shares) and the leaf norms of its gradient, and the parameters after the last step."""
    gradient = Gradient(shapes, hyper, quant, clip_coef)
    losses, norms, moments = [], [], None
    for count, picked in enumerate(minibatches, start=1):
        cut = lambda tree: jax.tree_util.tree_map(lambda x: jnp.asarray(x[picked]), tree)  # noqa: E731
        got = gradient(params, cut(batch), cut(snapshot), rows=rows)
        losses.append(got["losses"])
        norms.append(leaf_norms(got["grads"]))
        params, moments = adamw(hyper, params, got.pop("grads"), moments, count)
    return {"losses": jnp.stack(losses), "grad_norms": norms, "params": params}
