"""A sparse-attention, routed-expert language model as a token-action policy (flax.linen).

Pre-norm residual blocks ``x + Attn(RMSNorm(x))``, ``x + MoE(RMSNorm(x))``:

- **attention**: grouped queries (``num_heads`` query heads read ``num_kv_heads``
  key/value heads, head ``h`` the head ``h // (num_heads / num_kv_heads)``),
  RMSNorm over each head of ``q`` and ``k``, multimodal rotary phases (the
  frequency pairs split by ``mrope_section`` over three position streams,
  which a text token sets equal);
- **a lightning indexer** (``ops/sparse_index.py``) with a key cache of its
  own: a query attends the ``topk`` cached positions of largest index score,
  every visible one while an episode is shorter.  Its input is
  ``stop_gradient(x)`` and it learns from its own loss alone, the KL from the
  main attention's weights over the selection (summed over the heads,
  normalised, a constant) to the softmax of its scores there;
- **routed experts** (``ops/moe.py``): the router picks over all
  ``experts_total``, the layer computes the picks that fell on the
  ``experts_held`` this chip holds, and that partial sum goes on.

**Carried state.**  A layer carries the keys and values of the running
episode ``[B, 1, cache_len, num_kv_heads * head_dim]`` and its index keys
``[B, 1, cache_len, indexer_head_dim]`` (float32 whatever the model's
precision: the score is), rotated when written.  ``decode=True`` decodes one
token an env: score against the index cache, select, gather the selected
rows, attend, write the token's own three rows.  Otherwise a training
sequence is computed from a snapshot of that state taken as a constant, a
sequence and a block of ``query_block`` queries at a time, through one Pallas
kernel over the key tiles of cache and sequence that the block selected from
(``ops/sparse_attention.py``: the scores stay on chip and a tile no query
selected is skipped), each block recomputed in the backward pass.  Episode ends
(``resets``) restart the cache and start a new block of the mask, as in
``hybrid_lm.py``, whose protocol this keeps (``TokenPlayer`` drives both).

**A chip's share.**  ``experts_held`` of ``experts_total`` (share
``expert_share``) and ``vocab_held`` of ``vocab_total`` ids; attention and the
indexer are whole on every chip (the index score sums over all its heads).
On one chip the layer runs without its exchange.  :func:`take_share` cuts an
uncut tree to a share.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from sheeprl_tpu.models.hybrid_lm import F32, RMSNorm, _dense, _Kernel, _segments, _write_rows
from sheeprl_tpu.ops.moe import held_experts, route
from sheeprl_tpu.ops.sparse_attention import selected_attention
from sheeprl_tpu.ops.sparse_index import index_scores, select_indices, select_mask

_HI = jax.lax.Precision.HIGHEST
# the jax.named_scopes a profile of the update is split by
SCOPES = ("embed", "attn_proj", "index_score", "select", "sparse_attention", "index_loss", "moe_route", "moe_experts",
          "vocab_head", "ppo_loss", "optim")
# what the update reports beside the three PPO losses, in this order
AUX = ("index_loss", "attended_share", "picks_held_share", "attention_tiles_share")


@dataclasses.dataclass(frozen=True)
class SparseMoEConfig:
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    mrope_section: Tuple[int, ...]
    indexer_heads: int
    indexer_head_dim: int
    topk: int
    experts_total: int
    experts_held: int
    expert_share: int
    experts_per_token: int
    expert_width: int
    norm_topk_prob: bool
    rms_norm_eps: float
    vocab_total: int
    vocab_held: int
    vocab_share: int
    cache_len: int
    query_block: int = 128
    index_loss_coef: float = 1.0

    @classmethod
    def from_cfg(cls, cfg: Mapping[str, Any]) -> "SparseMoEConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        values = {k: cfg[k] for k in fields if k in cfg}
        values["mrope_section"] = tuple(int(n) for n in cfg["mrope_section"])
        return cls(**values)

    def problems(self) -> Sequence[str]:
        """What cannot work, as sentences (``cli.check_configs`` raises the first)."""
        out = []
        if self.experts_held < 1 or self.experts_total % self.experts_held:
            out.append(f"experts_held ({self.experts_held}) must divide experts_total ({self.experts_total})")
        elif not 0 <= self.expert_share < self.experts_total // self.experts_held:
            out.append(f"expert_share ({self.expert_share}) must be one of the {self.experts_total // self.experts_held} shares")
        if not 1 <= self.experts_per_token <= self.experts_total:
            out.append(f"experts_per_token ({self.experts_per_token}) must be between 1 and experts_total ({self.experts_total})")
        if self.vocab_held < 1 or self.vocab_total % self.vocab_held:
            out.append(f"vocab_held ({self.vocab_held}) must divide vocab_total ({self.vocab_total})")
        elif not 0 <= self.vocab_share < self.vocab_total // self.vocab_held:
            out.append(f"vocab_share ({self.vocab_share}) must be one of the {self.vocab_total // self.vocab_held} shares")
        if self.num_kv_heads < 1 or self.num_heads % self.num_kv_heads:
            out.append(f"num_kv_heads ({self.num_kv_heads}) must divide num_heads ({self.num_heads})")
        if self.head_dim % 2 or sum(self.mrope_section) != self.head_dim // 2 or len(self.mrope_section) != 3:
            out.append(f"mrope_section {list(self.mrope_section)} must be three counts that sum to head_dim / 2 ({self.head_dim // 2})")
        if self.indexer_head_dim % 2:
            out.append(f"indexer_head_dim ({self.indexer_head_dim}) must be even: its rotary phases turn pairs")
        if not 1 <= self.topk <= self.cache_len:
            out.append(f"topk ({self.topk}) must be between 1 and cache_len ({self.cache_len})")
        return out


# -- rotary phases -----------------------------------------------------------------
def rope_angles(positions: jax.Array, theta: float, pairs: int, sections: Optional[Sequence[int]] = None) -> jax.Array:
    """``positions`` ``[3, B, T]`` -> angles ``[B, T, pairs]``: pair ``i`` turns by
    ``theta^(-i / pairs)`` a position of the stream its section names (all by the
    first, temporal stream where ``sections`` is None)."""
    freqs = jnp.asarray(theta, F32) ** (-jnp.arange(pairs, dtype=F32) / pairs)
    if sections is None:
        return positions[0].astype(F32)[..., None] * freqs
    stream = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections), total_repeat_length=pairs)
    return jnp.moveaxis(positions.astype(F32), 0, -1)[..., stream] * freqs


def rotate(x: jax.Array, angles: jax.Array) -> jax.Array:
    """``x`` ``[B, T, H, d]`` turned by ``angles`` ``[B, T, d / 2]``: pair ``i`` is dimensions ``i`` and ``i + d / 2``."""
    a, b = jnp.split(x.astype(F32), 2, axis=-1)
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def episode_positions(resets: jax.Array, pos: jax.Array) -> jax.Array:
    """A text token's three equal streams ``[3, B, T]``: its index in its episode, the sequence's first episode ``pos`` tokens in."""
    t = jnp.arange(resets.shape[1])[None]
    start = jax.lax.cummax(jnp.where(resets > 0, t, -1), axis=1)  # where the position's episode began; -1: before the sequence
    index = jnp.where(start < 0, pos[:, None] + t, t - start)
    return jnp.broadcast_to(index[None], (3,) + index.shape)


# -- the two forms of attention over the selection ------------------------------------
def decode_attention(c: SparseMoEConfig, q, k, v, qi, ki, w, cache, held):
    """One token an env.  ``q`` ``[B, Hq, dh]``, ``k``/``v`` ``[B, Hkv dh]``, ``qi``
    ``[B, Hi, di]``, ``ki`` ``[B, di]``, ``w`` ``[B, Hi]``; ``cache`` the layer's
    state, of which the first ``held`` ``[B]`` positions are the episode's.  The
    token's own rows are one more candidate, not yet in the cache."""
    B, L = q.shape[0], cache["ki"].shape[2]
    G, R = c.num_kv_heads, c.num_heads // c.num_kv_heads
    with jax.named_scope("index_score"):
        scores = jnp.concatenate([
            index_scores(qi[:, None], cache["ki"][:, 0], w[:, None])[:, 0], index_scores(qi[:, None], ki[:, None], w[:, None])[:, 0],
        ], axis=-1)
        visible = jnp.concatenate([jnp.arange(L)[None] < held[:, None], jnp.ones((B, 1), bool)], axis=-1)
    with jax.named_scope("select"):
        idx, valid = select_indices(scores, visible, min(c.topk, L + 1))
    with jax.named_scope("sparse_attention"):
        own, at = (idx == L)[..., None], jnp.minimum(idx, L - 1)[..., None]
        rows_k = jnp.where(own, k[:, None], jnp.take_along_axis(cache["k"][:, 0], at, axis=1)).reshape(B, -1, G, c.head_dim)
        rows_v = jnp.where(own, v[:, None], jnp.take_along_axis(cache["v"][:, 0], at, axis=1)).reshape(B, -1, G, c.head_dim)
        s = jnp.einsum("bgrd,bkgd->bgrk", q.reshape(B, G, R, c.head_dim), rows_k.astype(q.dtype)).astype(F32) * c.head_dim ** -0.5
        weights = jax.nn.softmax(jnp.where(valid[:, None, None], s, -jnp.inf), axis=-1).astype(q.dtype)
        return jnp.einsum("bgrk,bkgd->bgrd", weights, rows_v.astype(q.dtype)).reshape(B, c.num_heads * c.head_dim)


def sequence_attention(c: SparseMoEConfig, q, k, v, qi, ki, w, cache, pos, seg):
    """One training sequence from its snapshot, ``query_block`` queries at a
    time.  ``q`` ``[T, Hq, dh]``, ``k``/``v`` ``[T, Hkv, dh]``, ``qi`` ``[T, Hi, di]``,
    ``ki`` ``[T, di]``, ``w`` ``[T, Hi]``; ``cache`` leaves ``[1, L, .]``, ``pos`` how
    many of its positions are the first episode's, ``seg`` ``[T]``.  Returns the
    heads' outputs ``[T, Hq dh]``, the indexer's KL summed over the queries, the
    attended and the visible positions counted over them, and the key tiles
    attention computed and that exist, counted over the blocks."""
    T, L = q.shape[0], cache["ki"].shape[1]
    block = min(c.query_block, T)
    cache_k, cache_v = cache["k"][0].astype(k.dtype), cache["v"][0].astype(v.dtype)
    index_keys = jnp.concatenate([cache["ki"][0], ki], axis=0)

    @jax.checkpoint
    def queries(args):
        qb, qib, wb, tb, segb = args
        # the cache is the first episode's: seen until the sequence's first reset; then the sequence's own, causally, inside an episode
        carried = (jnp.arange(L)[None] < pos) & (segb[:, None] == 0)
        visible = jnp.concatenate([carried, (jnp.arange(T)[None] <= tb[:, None]) & (seg[None] == segb[:, None])], axis=1)
        with jax.named_scope("index_score"):
            scores = index_scores(qib, index_keys, wb)
        with jax.named_scope("select"):
            selected = select_mask(scores, visible, c.topk)
        with jax.named_scope("sparse_attention"):
            o, p, live = selected_attention(qb, cache_k, cache_v, k, v, selected)  # p: the weights' mean over the heads, a constant
        with jax.named_scope("index_loss"):
            log_q = jax.nn.log_softmax(jnp.where(selected, scores, -jnp.inf), axis=-1)
            kl = jnp.where(selected, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - jnp.where(selected, log_q, 0.0)), 0.0)
        counts = (jnp.sum(selected.astype(jnp.int32)), jnp.sum(visible.astype(jnp.int32)), jnp.sum(live), jnp.int32(live.size))
        return (o.reshape(o.shape[0], -1), jnp.sum(kl)) + counts

    cut = lambda x: x.reshape((T // block, block) + x.shape[1:])  # noqa: E731
    o, kl, *counts = jax.lax.map(queries, (cut(q), cut(qi), cut(w), cut(jnp.arange(T)), cut(seg)))
    return (o.reshape(T, -1), jnp.sum(kl)) + tuple(jnp.sum(n) for n in counts)


# -- the modules -----------------------------------------------------------------------
class LayerNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), F32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), F32)
        y = x.astype(F32)
        y = y - jnp.mean(y, axis=-1, keepdims=True)
        return y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + self.eps) * scale + bias


def _dense_hi(features: int, name: str) -> nn.Dense:
    """A product that feeds a discrete choice: float32 at ``highest``, and float32 in the player's view."""
    return nn.Dense(features, use_bias=False, name=name, dtype=F32, param_dtype=F32, precision=_HI)


class Indexer(nn.Module):
    config: SparseMoEConfig

    @nn.compact
    def __call__(self, x, positions):
        """``x`` ``[B, T, D]`` -> ``qi`` ``[B, T, Hi, di]``, ``ki`` ``[B, T, di]``, ``w`` ``[B, T, Hi]``, float32."""
        c = self.config
        B, T = x.shape[:2]
        x = jax.lax.stop_gradient(x).astype(F32)
        angles = rope_angles(positions, c.rope_theta, c.indexer_head_dim // 2)
        qi = rotate(_dense_hi(c.indexer_heads * c.indexer_head_dim, "q_proj")(x).reshape(B, T, c.indexer_heads, -1), angles)
        ki = rotate(LayerNorm(name="k_norm")(_dense_hi(c.indexer_head_dim, "k_proj")(x))[:, :, None], angles)[:, :, 0]
        w = _dense_hi(c.indexer_heads, "w_proj")(x) * (c.indexer_heads ** -0.5 * c.indexer_head_dim ** -0.5)
        return qi, ki, w


class SparseAttention(nn.Module):
    config: SparseMoEConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, x, resets, state, pos, positions, decode: bool, write: bool):
        """``state`` ``{"k", "v", "ki"}``; returns ``(y, state, aux)``, ``aux`` the
        indexer's KL summed over the queries, the attended and visible
        positions counted and the key tiles computed and in all (nothing when decoding)."""
        c = self.config
        B, T = x.shape[:2]
        with jax.named_scope("attn_proj"):
            q = _dense(c.num_heads * c.head_dim, "q_proj", self.dtype)(x).reshape(B, T, c.num_heads, c.head_dim)
            k = _dense(c.num_kv_heads * c.head_dim, "k_proj", self.dtype)(x).reshape(B, T, c.num_kv_heads, c.head_dim)
            v = _dense(c.num_kv_heads * c.head_dim, "v_proj", self.dtype)(x).reshape(B, T, c.num_kv_heads, c.head_dim)
            angles = rope_angles(positions, c.rope_theta, c.head_dim // 2, c.mrope_section)
            q = rotate(RMSNorm(c.rms_norm_eps, name="q_norm")(q), angles)
            k = rotate(RMSNorm(c.rms_norm_eps, name="k_norm")(k), angles)
        with jax.named_scope("index_score"):
            qi, ki, w = Indexer(c, name="indexer")(x, positions)
        if decode:
            held = jnp.where(resets[:, 0] > 0, 0, pos)
            rows = {"k": k.reshape(B, 1, -1), "v": v.reshape(B, 1, -1), "ki": ki}
            o = decode_attention(c, q[:, 0], rows["k"][:, 0], rows["v"][:, 0], qi[:, 0], ki[:, 0], w[:, 0], state, held)[:, None]
            if write:
                with jax.named_scope("sparse_attention"):
                    state = {name: _write_rows(state[name], rows[name], held) for name in ("k", "v", "ki")}
            aux = {}
        else:
            per_sequence = lambda a: sequence_attention(c, *a)  # noqa: E731
            o, kl, attended, seen, tiles, tiles_total = jax.lax.map(
                per_sequence, (q, k, v, qi, ki, w, {n: state[n] for n in ("k", "v", "ki")}, pos, _segments(resets)))
            aux = {"index_kl": jnp.sum(kl), "attended": jnp.sum(attended), "visible": jnp.sum(seen), "tiles": jnp.sum(tiles),
                   "tiles_total": jnp.sum(tiles_total)}
        with jax.named_scope("attn_proj"):
            y = _dense(c.hidden_size, "o_proj", self.dtype)(o.astype(self.dtype))
        return y, state, aux


class Experts(nn.Module):
    """The router over every expert of the model and the products of the experts held."""

    config: SparseMoEConfig
    dtype: Any = F32
    mxu_operands = ("w1", "w3", "w2")  # the leaves that are operands of an MXU product, for the player's view (players.py)

    @nn.compact
    def __call__(self, x):
        c = self.config
        flat = x.reshape(-1, c.hidden_size)
        with jax.named_scope("moe_route"):
            logits = _dense_hi(c.experts_total, "router")(flat.astype(F32))
            gates, picks_held = route(logits, c.experts_per_token, c.norm_topk_prob, c.expert_share * c.experts_held, c.experts_held)
        with jax.named_scope("moe_experts"):
            init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
            w1 = self.param("w1", init, (c.experts_held, c.hidden_size, c.expert_width), F32)
            w3 = self.param("w3", init, (c.experts_held, c.hidden_size, c.expert_width), F32)
            w2 = self.param("w2", init, (c.experts_held, c.expert_width, c.hidden_size), F32)
            experts = lambda a: held_experts(a[0], a[1], w1, w3, w2)  # noqa: E731
            tokens, gates = x.astype(self.dtype), gates.reshape(x.shape[:2] + (-1,))
            # a training sequence at a time, recomputed in the backward pass: the products' [experts, tokens, width] stay a sequence's
            y = jax.lax.map(jax.checkpoint(experts), (tokens, gates)) if x.shape[1] > 1 else experts((flat.astype(self.dtype), gates[:, 0]))
        return y.reshape(x.shape).astype(self.dtype), picks_held


class Block(nn.Module):
    config: SparseMoEConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, x, resets, state, pos, positions, decode: bool, write: bool):
        c = self.config
        with jax.named_scope("attn_proj"):
            h = RMSNorm(c.rms_norm_eps, name="attn_norm")(x)
        y, state, aux = SparseAttention(c, self.dtype, name="attn")(h, resets, state, pos, positions, decode, write)
        x = x + y
        with jax.named_scope("moe_route"):
            h = RMSNorm(c.rms_norm_eps, name="moe_norm")(x)
        y, picks_held = Experts(c, self.dtype, name="moe")(h)
        if not decode:
            aux["picks_held"] = picks_held
        return x + y, state, aux


class SparseMoELM(nn.Module):
    """Embedding -> blocks -> final norm -> logits over the held ids and a value."""

    config: SparseMoEConfig
    dtype: Any = F32

    def init_state(self, batch: int) -> Dict[str, Any]:
        """The carried state of ``batch`` envs at the start of an episode."""
        c = self.config
        rows = (batch, 1, c.cache_len)
        layer = lambda: {  # noqa: E731
            "k": jnp.zeros(rows + (c.num_kv_heads * c.head_dim,), self.dtype), "v": jnp.zeros(rows + (c.num_kv_heads * c.head_dim,), self.dtype),
            "ki": jnp.zeros(rows + (c.indexer_head_dim,), F32)}
        return {"pos": jnp.zeros((batch,), jnp.int32), "layers": [layer() for _ in range(c.num_layers)]}

    @nn.compact
    def __call__(self, tokens, resets, state, decode: bool = False, write: bool = True, positions=None, aux: bool = False):
        """``tokens``/``resets`` ``[B, T]`` (``T`` 1 when decoding), ``positions``
        ``[3, B, T]`` (a text token's: its index in its episode, thrice); returns
        logits ``[B, T, vocab_held]``, values ``[B, T]``, the state after and,
        with ``aux``, what a training sequence's update reports (:data:`AUX`)."""
        c = self.config
        pos = state["pos"]
        if positions is None:
            start = jnp.where(resets[:, 0] > 0, 0, pos) if decode else pos
            positions = episode_positions(jnp.zeros_like(resets) if decode else resets, start)
        with jax.named_scope("embed"):
            table = _Kernel((c.vocab_held, c.hidden_size), nn.initializers.normal(1.0), name="embed_tokens")()
            x = jnp.take(table, tokens, axis=0).astype(self.dtype)
        block = Block
        if not decode:  # gradient recomputation a layer, so that the update fits: what is saved is a layer's input
            block = nn.remat(Block, static_argnums=(6, 7))
        layers, found = [], []
        for i in range(c.num_layers):
            x, layer_state, layer_aux = block(c, self.dtype, name=f"layers_{i}")(x, resets, state["layers"][i], pos, positions, decode, write)
            layers.append(layer_state)
            found.append(layer_aux)
        with jax.named_scope("vocab_head"):
            x = RMSNorm(c.rms_norm_eps, name="final_norm")(x)
            logits = _dense(c.vocab_held, "lm_head", self.dtype)(x).astype(F32)
            values = _dense(1, "value_head", self.dtype)(x).astype(F32)[..., 0]
        if decode:
            pos = jnp.where(resets[:, 0] > 0, 0, pos) + (1 if write else 0)
        out = (logits, values, {"pos": pos, "layers": layers})
        if not aux:
            return out
        with jax.named_scope("index_loss"):
            total = {k: sum(layer[k] for layer in found) for k in found[0]}
            queries = tokens.size
            report = {
                # every layer's indexer has its own loss, the mean over the queries; the update adds their sum
                "index_loss": total["index_kl"] / queries,
                "attended_share": total["attended"] / jnp.maximum(total["visible"], 1),
                "picks_held_share": total["picks_held"] / (queries * c.num_layers * c.experts_per_token),
                "attention_tiles_share": total["tiles"] / jnp.maximum(total["tiles_total"], 1),
            }
        return out + (report,)


# -- a share of an uncut parameter tree ------------------------------------------
def take_share(params: Mapping[str, Any], whole: SparseMoEConfig, held: SparseMoEConfig) -> Dict[str, Any]:
    """The parameters the chip of configuration ``held`` holds of the uncut
    tree ``params`` (made under ``whole``, which holds every expert and id):
    experts ``[expert_share * experts_held, (expert_share + 1) * experts_held)``
    of every layer and the ids of its ``vocab_share``; attention, the indexer
    and the router whole."""

    def experts(x):
        return jax.lax.slice_in_dim(x, held.expert_share * held.experts_held, (held.expert_share + 1) * held.experts_held, axis=0)

    def ids(x, axis):
        return jax.lax.slice_in_dim(x, held.vocab_share * held.vocab_held, (held.vocab_share + 1) * held.vocab_held, axis=axis)

    p = params["params"]
    out: Dict[str, Any] = {
        "embed_tokens": {"kernel": ids(p["embed_tokens"]["kernel"], 0)},
        "final_norm": p["final_norm"],
        "lm_head": {"kernel": ids(p["lm_head"]["kernel"], 1)},
        "value_head": p["value_head"],
    }
    for i in range(whole.num_layers):
        layer = dict(p[f"layers_{i}"])
        layer["moe"] = {"router": layer["moe"]["router"], **{n: experts(layer["moe"][n]) for n in ("w1", "w3", "w2")}}
        out[f"layers_{i}"] = layer
    return {"params": out}
