"""A hybrid language model as a token-action policy (flax.linen).

The blocks of ``olmo_hybrid`` (``allenai/Olmo-Hybrid-7B``, ``config.json``):
pre-norm residual blocks whose mixer is, by ``layer_types``, a gated
delta-rule layer (``linear_attention``: arXiv:2412.06464, ``beta = 2 sigmoid``
under ``linear_allow_neg_eigval``) or causal softmax attention without rotary
phases (``full_attention``), each followed by a SiLU-gated MLP; RMSNorm; an
embedding and a head over the vocabulary, untied; one linear value read-out
for the critic (the language model has none).

**Two kinds of carried state.**  A linear layer carries its state ``S``
(``[heads, dv, dk]``) and the last three inputs of its causal convolutions;
a full layer carries the keys and values of the running episode, a cache that
grows with it.  Both stay on the device as one pytree (:meth:`HybridLM.init_state`):

- ``decode=True`` decodes one token an env through state and cache (the delta
  rule in its one-step form, one row written into the cache);
- otherwise a whole training sequence is computed from a snapshot of that
  state taken as a constant (the delta rule in its chunked form, attention
  over the carried keys and the sequence's own);
- where ``resets[t]`` is 1 the episode before step ``t`` is over: ``S`` and
  the convolution tail are zeroed, the cache restarts, and inside a sequence
  the attention mask starts a new block.

**A chip's share.**  Every layer is told which of the model's heads it holds
(``heads_held`` of ``heads_total``, share ``head_share``) and the embedding and
head which ids (``vocab_held`` of ``vocab_total``, share ``vocab_share``).  A
layer computes its heads' part of the output (the sum over held heads through
its rows of ``o_proj``) and that partial sum goes on; logits, sampling,
entropy and loss are over the held ids.  On one chip the layer runs without
its exchange: nothing stands in for the absent chip.  :func:`take_share` cuts
an uncut parameter tree to a share (a checkpoint's loader, and the test that
the shares add up).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from sheeprl_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
# the jax.named_scopes a profile of the update is split by
SCOPES = ("embed", "delta_rule", "delta_rule_proj", "full_attention", "swiglu", "vocab_head", "ppo_loss", "optim")


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    hidden_size: int
    intermediate_size: int
    layer_types: Tuple[str, ...]
    heads_total: int
    heads_held: int
    head_share: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool
    rms_norm_eps: float
    vocab_total: int
    vocab_held: int
    vocab_share: int
    cache_len: int
    chunk_size: int = 64

    @classmethod
    def from_cfg(cls, cfg: Mapping[str, Any]) -> "HybridConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        values = {k: cfg[k] for k in fields if k in cfg}
        values["layer_types"] = tuple(str(t) for t in cfg["layer_types"])
        return cls(**values)

    @property
    def head_dim(self) -> int:  # ``head_dim`` is null in the config: hidden over heads
        return self.hidden_size // self.heads_total

    @property
    def conv_width(self) -> int:
        return self.heads_held * (2 * self.linear_key_head_dim + self.linear_value_head_dim)

    def problems(self) -> Sequence[str]:
        """What cannot work, as sentences (``cli.check_configs`` raises the first)."""
        out = []
        if self.heads_held < 1 or self.heads_total % self.heads_held:
            out.append(f"heads_held ({self.heads_held}) must divide heads_total ({self.heads_total})")
        elif not 0 <= self.head_share < self.heads_total // self.heads_held:
            out.append(f"head_share ({self.head_share}) must be one of the {self.heads_total // self.heads_held} shares")
        if self.hidden_size % self.heads_total:
            out.append(f"heads_total ({self.heads_total}) must divide hidden_size ({self.hidden_size})")
        if self.vocab_held < 1 or self.vocab_total % self.vocab_held:
            out.append(f"vocab_held ({self.vocab_held}) must divide vocab_total ({self.vocab_total})")
        elif not 0 <= self.vocab_share < self.vocab_total // self.vocab_held:
            out.append(f"vocab_share ({self.vocab_share}) must be one of the {self.vocab_total // self.vocab_held} shares")
        unknown = sorted(set(self.layer_types) - {LINEAR, FULL})
        if unknown or not self.layer_types:
            out.append(f"layer_types must be a list of {LINEAR!r} and {FULL!r}, got {list(self.layer_types)}")
        return out


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _dense(features: int, name: str, dtype) -> nn.Dense:
    return nn.Dense(features, use_bias=False, name=name, dtype=dtype, param_dtype=F32)


class _Kernel(nn.Module):
    """A bare float32 array under the name ``kernel`` (a convolution's taps, the embedding's rows)."""

    shape: Tuple[int, ...]
    init: Any

    @nn.compact
    def __call__(self):
        return self.param("kernel", self.init, self.shape, F32)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), F32)
        y = x.astype(F32)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + self.eps) * scale
        return y.astype(x.dtype)


class SwiGLU(nn.Module):
    """``(SiLU(x W_gate) * x W_up) W_down``: whole on every chip (a feed-forward width is never cut)."""

    config: HybridConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, x):
        c = self.config
        gate = _dense(c.intermediate_size, "gate_proj", self.dtype)(x)
        up = _dense(c.intermediate_size, "up_proj", self.dtype)(x)
        return _dense(c.hidden_size, "down_proj", self.dtype)(_silu(gate) * up)


def _segments(resets: jax.Array) -> jax.Array:
    """``[B, T]`` resets -> the episode each position belongs to, counted from the sequence's start."""
    return jnp.cumsum(resets.astype(jnp.int32), axis=1)


def causal_conv(kernel: jax.Array, x: jax.Array, tail: jax.Array, seg: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution, then SiLU.  ``x`` ``[B, T, C]``, ``tail``
    the ``taps - 1`` inputs before it (of episode 0), ``seg`` ``[B, T]``.  A
    tap reaches back only inside its own episode.  Returns the output and the
    new tail (the last inputs, zeroed where they belong to an earlier episode
    than the sequence's last position)."""
    taps, T = kernel.shape[0], x.shape[1]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    seg_padded = jnp.concatenate([jnp.zeros((x.shape[0], taps - 1), seg.dtype), seg], axis=1)
    out = jnp.zeros(x.shape, F32)
    for j in range(taps):  # tap j multiplies the input taps - 1 - j steps back
        same = (seg_padded[:, j:j + T] == seg)[..., None]
        out = out + jnp.where(same, padded[:, j:j + T].astype(F32) * kernel[j].astype(F32), 0.0)
    new_tail = jnp.where((seg_padded[:, T:] == seg[:, -1:])[..., None], padded[:, T:], 0).astype(tail.dtype)
    return _silu(out).astype(x.dtype), new_tail


class GatedDeltaNet(nn.Module):
    """The linear layer over the heads this chip holds."""

    config: HybridConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, x, resets, state, decode: bool):
        """``x`` ``[B, T, D]``, ``resets`` ``[B, T]``, ``state`` ``{"S", "conv"}``; returns ``(y, state)``."""
        c = self.config
        H, dk, dv = c.heads_held, c.linear_key_head_dim, c.linear_value_head_dim
        B, T = x.shape[:2]
        seg = _segments(resets)
        with jax.named_scope("delta_rule_proj"):
            widths = (H * dk, H * dk, H * dv)
            tails = jnp.split(state["conv"], (widths[0], widths[0] + widths[1]), axis=-1)
            mixed, new_tails = [], []
            for name, width, tail in zip("qkv", widths, tails):
                kernel = _Kernel((c.linear_conv_kernel_dim, width), nn.initializers.lecun_normal(), name=f"{name}_conv")()
                out, new_tail = causal_conv(kernel, _dense(width, f"{name}_proj", self.dtype)(x), tail, seg)
                mixed.append(out)
                new_tails.append(new_tail)
            q, k, v = mixed[0].reshape(B, T, H, dk), mixed[1].reshape(B, T, H, dk), mixed[2].reshape(B, T, H, dv)
            q, k = q.astype(F32), k.astype(F32)
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            # A in [1, 16], dt in [1e-3, 1e-1], log-uniform, as the family initialises them
            a_log = self.param("A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)), (H,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (H,))
            dt = jax.nn.softplus(_dense(H, "a_proj", self.dtype)(x).astype(F32) + dt_bias)
            log_a = -jnp.exp(a_log) * dt
            b = jax.nn.sigmoid(_dense(H, "b_proj", self.dtype)(x).astype(F32))
            if c.linear_allow_neg_eigval:
                b = 2.0 * b
            gate = _silu(_dense(H * dv, "g_proj", self.dtype)(x)).reshape(B, T, H, dv)
        with jax.named_scope("delta_rule"):
            if decode:
                S, o = delta_rule_step(state["S"], q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], b[:, 0], resets[:, 0])
                o = o[:, None]
            else:
                S, o = delta_rule_chunked(state["S"], q, k, v, log_a, b, resets, chunk=c.chunk_size)
        with jax.named_scope("delta_rule_proj"):
            y = RMSNorm(c.rms_norm_eps, name="o_norm")(o).astype(self.dtype) * gate
            y = _dense(c.hidden_size, "o_proj", self.dtype)(y.reshape(B, T, H * dv))
        return y, {"S": S, "conv": jnp.concatenate(new_tails, axis=-1)}


def _dt_bias_init(key, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1


def _write_rows(cache: jax.Array, rows: jax.Array, at: jax.Array) -> jax.Array:
    """``cache[b, :, at[b], :] = rows[b]`` for every env: one
    ``dynamic_update_slice`` an env, each in place.  As one scatter XLA:TPU
    re-lays the whole cache with the scattered axes major and back again, two
    copies of a gigabyte a token (compiled for a v5e; PERF.md section 6); a
    position past the cache's end is clamped, which ``cli.check_configs`` rules out."""
    for b in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(cache, rows[b][None, :, None, :].astype(cache.dtype), (b, 0, at[b], 0))
    return cache


class FullAttention(nn.Module):
    """Causal softmax attention over the keys of the same episode, no rotary
    phases (``rope_theta: null``), over the heads this chip holds."""

    config: HybridConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, x, resets, state, pos, decode: bool, write: bool = True):
        """``state`` ``{"k", "v"}`` of ``[B, H, cache_len, dh]`` (the layout the
        scores read it in: a decoded token's row is written in place and the
        cache is never re-laid); ``pos`` ``[B]``
        how many of its positions are the running episode's.  Decoding one
        token writes its key and value at ``pos`` (after a reset: at 0) unless
        ``write`` is off; a sequence reads the cache and leaves it alone."""
        c = self.config
        H, dh = c.heads_held, c.head_dim
        B, T = x.shape[:2]
        with jax.named_scope("full_attention"):
            q, k, v = (_dense(H * dh, f"{n}_proj", self.dtype)(x).reshape(B, T, H, dh) for n in "qkv")
            scale = dh ** -0.5
            seg = _segments(resets)
            L = state["k"].shape[2]
            # the cache is the running episode's: a decoded token after a reset sees none of it,
            # a sequence sees it until its first reset
            held = jnp.where(resets[:, 0] > 0, 0, pos) if decode else pos  # [B]
            first = jnp.ones_like(seg, bool) if decode else seg == 0
            carried = (jnp.arange(L)[None, None, :] < held[:, None, None]) & first[:, :, None]
            own = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None] & (seg[:, None, :] == seg[:, :, None])
            s_cache = jnp.einsum("bthd,bhld->bhtl", q, state["k"].astype(q.dtype)).astype(F32) * scale
            s_own = jnp.einsum("bthd,bshd->bhts", q, k).astype(F32) * scale
            scores = jnp.concatenate([
                jnp.where(carried[:, None], s_cache, -jnp.inf), jnp.where(own[:, None], s_own, -jnp.inf)], axis=-1)
            weights = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
            o = jnp.einsum("bhtl,bhld->bthd", weights[..., :L], state["v"].astype(weights.dtype))
            o = o + jnp.einsum("bhts,bshd->bthd", weights[..., L:], v)
            y = _dense(c.hidden_size, "o_proj", self.dtype)(o.reshape(B, T, H * dh))
            if decode and write:
                state = {"k": _write_rows(state["k"], k[:, 0], held), "v": _write_rows(state["v"], v[:, 0], held)}
        return y, state


class Block(nn.Module):
    config: HybridConfig
    kind: str
    dtype: Any = F32

    @nn.compact
    def __call__(self, x, resets, state, pos, decode: bool, write: bool):
        c = self.config
        if self.kind == LINEAR:
            with jax.named_scope("delta_rule_proj"):
                h = RMSNorm(c.rms_norm_eps, name="mixer_norm")(x)
            y, state = GatedDeltaNet(c, self.dtype, name="mixer")(h, resets, state, decode)
        else:
            with jax.named_scope("full_attention"):
                h = RMSNorm(c.rms_norm_eps, name="mixer_norm")(x)
            y, state = FullAttention(c, self.dtype, name="mixer")(h, resets, state, pos, decode, write)
        x = x + y
        with jax.named_scope("swiglu"):
            x = x + SwiGLU(c, self.dtype, name="mlp")(RMSNorm(c.rms_norm_eps, name="mlp_norm")(x))
        return x, state


class HybridLM(nn.Module):
    """Embedding -> blocks -> final norm -> logits over the held ids and a value."""

    config: HybridConfig
    dtype: Any = F32

    def init_state(self, batch: int) -> Dict[str, Any]:
        """The carried state of ``batch`` envs at the start of an episode."""
        c = self.config
        layers = []
        for kind in c.layer_types:
            if kind == LINEAR:
                layers.append({
                    "S": jnp.zeros((batch, c.heads_held, c.linear_value_head_dim, c.linear_key_head_dim), F32),
                    "conv": jnp.zeros((batch, c.linear_conv_kernel_dim - 1, c.conv_width), self.dtype),
                })
            else:
                shape = (batch, c.heads_held, c.cache_len, c.head_dim)
                layers.append({"k": jnp.zeros(shape, self.dtype), "v": jnp.zeros(shape, self.dtype)})
        return {"pos": jnp.zeros((batch,), jnp.int32), "layers": layers}

    @nn.compact
    def __call__(self, tokens, resets, state, decode: bool = False, write: bool = True):
        """``tokens``/``resets`` ``[B, T]`` (``T`` 1 when decoding); returns
        logits ``[B, T, vocab_held]``, values ``[B, T]`` and the state after."""
        c = self.config
        with jax.named_scope("embed"):
            table = _Kernel((c.vocab_held, c.hidden_size), nn.initializers.normal(1.0), name="embed_tokens")()
            x = jnp.take(table, tokens, axis=0).astype(self.dtype)
        block = Block
        if not decode:  # gradient recomputation a layer, so that the update fits: what is saved is a layer's input
            block = nn.remat(Block, static_argnums=(5, 6))
        layers = []
        for i, kind in enumerate(c.layer_types):
            x, layer_state = block(c, kind, self.dtype, name=f"layers_{i}")(
                x, resets, state["layers"][i], state["pos"], decode, write)
            layers.append(layer_state)
        with jax.named_scope("vocab_head"):
            x = RMSNorm(c.rms_norm_eps, name="final_norm")(x)
            logits = _dense(c.vocab_held, "lm_head", self.dtype)(x).astype(F32)
            values = _dense(1, "value_head", self.dtype)(x).astype(F32)[..., 0]
        pos = state["pos"]
        if decode:
            pos = jnp.where(resets[:, 0] > 0, 0, pos) + (1 if write else 0)
        return logits, values, {"pos": pos, "layers": layers}


# -- a share of an uncut parameter tree ------------------------------------------
def take_share(params: Mapping[str, Any], whole: HybridConfig, held: HybridConfig) -> Dict[str, Any]:
    """The parameters the chip of configuration ``held`` holds of the uncut
    tree ``params`` (made under ``whole``, which holds every head and id):
    heads ``[head_share * heads_held, (head_share + 1) * heads_held)`` of
    every layer and the ids of its ``vocab_share``."""
    heads_held, head_share, vocab_held, vocab_share = held.heads_held, held.head_share, held.vocab_held, held.vocab_share
    dk, dv, dh = whole.linear_key_head_dim, whole.linear_value_head_dim, whole.head_dim

    def heads(x, width, axis):
        lo = head_share * heads_held * width
        return jax.lax.slice_in_dim(x, lo, lo + heads_held * width, axis=axis)

    def ids(x, axis):
        return jax.lax.slice_in_dim(x, vocab_share * vocab_held, (vocab_share + 1) * vocab_held, axis=axis)

    p = params["params"]
    out: Dict[str, Any] = {
        "embed_tokens": {"kernel": ids(p["embed_tokens"]["kernel"], 0)},
        "final_norm": p["final_norm"],
        "lm_head": {"kernel": ids(p["lm_head"]["kernel"], 1)},
        "value_head": p["value_head"],
    }
    for i, kind in enumerate(whole.layer_types):
        layer = p[f"layers_{i}"]
        m = layer["mixer"]
        if kind == LINEAR:
            mixer = {f"{n}_{part}": {"kernel": heads(m[f"{n}_{part}"]["kernel"], w, 1)}
                     for n, w in (("q", dk), ("k", dk), ("v", dv)) for part in ("proj", "conv")}
            mixer.update(
                a_proj={"kernel": heads(m["a_proj"]["kernel"], 1, 1)}, b_proj={"kernel": heads(m["b_proj"]["kernel"], 1, 1)},
                g_proj={"kernel": heads(m["g_proj"]["kernel"], dv, 1)}, o_proj={"kernel": heads(m["o_proj"]["kernel"], dv, 0)},
                A_log=heads(m["A_log"], 1, 0), dt_bias=heads(m["dt_bias"], 1, 0), o_norm=m["o_norm"],
            )
        else:
            mixer = {f"{n}_proj": {"kernel": heads(m[f"{n}_proj"]["kernel"], dh, 1)} for n in "qkv"}
            mixer["o_proj"] = {"kernel": heads(m["o_proj"]["kernel"], dh, 0)}
        out[f"layers_{i}"] = {"mixer": mixer, "mixer_norm": layer["mixer_norm"], "mlp": layer["mlp"], "mlp_norm": layer["mlp_norm"]}
    return {"params": out}


def carry_bytes(state: Any) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state)))
