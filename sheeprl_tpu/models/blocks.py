"""Reusable NN blocks (flax.linen).

TPU-native re-design of /root/reference/sheeprl/models/models.py:16-524.
Differences from the reference that are deliberate TPU choices:

- Convolutions run in NHWC (XLA's native TPU layout).  Observations keep the
  reference's CHW uint8 convention on the host/buffer side; ``cnn_forward``
  transposes once inside the jitted graph.
- ``LayerNormGRUCell`` is written as a ``(carry, x) -> (carry, y)`` cell so it
  drops straight into ``jax.lax.scan`` — the reference steps it from a Python
  loop (algos/dreamer_v3/dreamer_v3.py:134-145); here the whole sequence is one
  XLA while-loop with the gate matmuls batched onto the MXU.
- Norm layers default to eps=1e-3 like Dreamer's (models.py:506-524 uses
  torch LN defaults overridden per-algo; DV3 configs set eps=1e-3).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any

#: the collections `tap_product` writes to and reads from, and its names' suffix
PRODUCT_INPUTS, PRODUCT_PROBES, PRODUCT_SUFFIX = "intermediates", "perturbations", "_product"


def tap_product(module: nn.Module, dense_name: str, x: jax.Array, y: jax.Array) -> jax.Array:
    """Mark ``y`` as the product of ``x`` with the leading ``x.shape[-1]`` rows
    of the kernel of ``module``'s Dense ``dense_name`` (plus whatever does not
    depend on that kernel).  Returns ``y``, and does nothing else unless the
    caller of ``apply`` asks: with ``PRODUCT_INPUTS`` mutable ``x`` is stored
    there, and with ``PRODUCT_PROBES`` given its entry (zeros of ``y``'s
    shape) is added to ``y``, so that its cotangent is ``y``'s.  From the two,
    stacked over a loop's steps, the kernel's gradient is one product after
    the loop (`algos/dreamer_v3/utils.py::scan_kernel_gradients_after`)."""
    if module.is_initializing():  # `init` makes every collection mutable: the tree stays the parameters alone
        return y
    name = dense_name + PRODUCT_SUFFIX
    module.sow(PRODUCT_INPUTS, name, x, reduce_fn=lambda _, new: new, init_fn=lambda: None)
    return module.perturb(name, y, PRODUCT_PROBES)


def get_activation(name: str | Callable | None) -> Callable:
    """Map reference activation names (e.g. ``torch.nn.SiLU``) to jax fns."""
    if name is None:
        return lambda x: x
    if callable(name):
        return name
    key = name.rsplit(".", 1)[-1].lower()
    table = {
        "relu": jax.nn.relu,
        "silu": jax.nn.silu,
        "swish": jax.nn.silu,
        "tanh": jnp.tanh,
        "elu": jax.nn.elu,
        "gelu": jax.nn.gelu,
        "leakyrelu": jax.nn.leaky_relu,
        "sigmoid": jax.nn.sigmoid,
        "identity": lambda x: x,
    }
    if key not in table:
        raise ValueError(f"Unknown activation '{name}'")
    return table[key]


class MLP(nn.Module):
    """Dense stack with per-layer norm/activation/dropout
    (reference models.py:16-119)."""

    hidden_sizes: Sequence[int]
    output_dim: Optional[int] = None
    activation: str | Callable = "tanh"
    layer_norm: bool = False
    norm_eps: float = 1e-3
    dropout: float = 0.0
    flatten_input: bool = False
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    output_kernel_init: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        act = get_activation(self.activation)
        if self.flatten_input:
            x = x.reshape(x.shape[0], -1)
        for size in self.hidden_sizes:
            x = nn.Dense(size, dtype=self.dtype, param_dtype=self.param_dtype, kernel_init=self.kernel_init)(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, param_dtype=self.param_dtype)(x)
            x = act(x)
            if self.dropout > 0.0:
                x = nn.Dropout(rate=self.dropout, deterministic=deterministic)(x)
        if self.output_dim is not None:
            kinit = self.output_kernel_init or self.kernel_init
            x = nn.Dense(self.output_dim, dtype=self.dtype, param_dtype=self.param_dtype, kernel_init=kinit)(x)
        return x


def cnn_forward(module: nn.Module, x: jax.Array, input_hwc: bool = False) -> jax.Array:
    """Apply a conv module to input with arbitrary leading dims, flattening
    them into a single batch (reference utils/model.py ``cnn_forward``).
    Input is CHW (buffer convention) unless ``input_hwc``; converted to NHWC."""
    lead = x.shape[:-3]
    x = x.reshape((-1,) + x.shape[-3:])
    if not input_hwc:
        x = jnp.transpose(x, (0, 2, 3, 1))
    y = module(x)
    return y.reshape(lead + y.shape[1:])


class CNN(nn.Module):
    """Conv stack (reference models.py:122-202).  NHWC on TPU."""

    channels: Sequence[int]
    kernel_sizes: Sequence[int]
    strides: Sequence[int]
    paddings: Sequence[Any] | None = None
    activation: str | Callable = "relu"
    layer_norm: bool = False
    norm_eps: float = 1e-3
    flatten_output: bool = True
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = get_activation(self.activation)
        paddings = self.paddings or ["SAME"] * len(self.channels)
        for ch, k, s, p in zip(self.channels, self.kernel_sizes, self.strides, paddings):
            pad = p if isinstance(p, str) else [(p, p), (p, p)]
            x = nn.Conv(
                ch, (k, k), strides=(s, s), padding=pad, dtype=self.dtype, param_dtype=self.param_dtype
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, param_dtype=self.param_dtype)(x)
            x = act(x)
        if self.flatten_output:
            x = x.reshape(x.shape[0], -1)
        return x


class DeCNN(nn.Module):
    """Transposed-conv stack (reference models.py:205-285)."""

    channels: Sequence[int]
    kernel_sizes: Sequence[int]
    strides: Sequence[int]
    paddings: Sequence[Any] | None = None
    activation: str | Callable = "relu"
    layer_norm: bool = False
    norm_eps: float = 1e-3
    final_activation: Optional[str] = None
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = get_activation(self.activation)
        n = len(self.channels)
        paddings = self.paddings or ["SAME"] * n
        for i, (ch, k, s, p) in enumerate(zip(self.channels, self.kernel_sizes, self.strides, paddings)):
            pad = p if isinstance(p, str) else [(p, p), (p, p)]
            x = nn.ConvTranspose(
                ch, (k, k), strides=(s, s), padding=pad, dtype=self.dtype, param_dtype=self.param_dtype
            )(x)
            last = i == n - 1
            if not last:
                if self.layer_norm:
                    x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, param_dtype=self.param_dtype)(x)
                x = act(x)
            elif self.final_activation is not None:
                x = get_activation(self.final_activation)(x)
        return x


class NatureCNN(nn.Module):
    """DQN-Nature conv backbone + dense head (reference models.py:288-328)."""

    features_dim: int = 512
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for ch, k, s in ((32, 8, 4), (64, 4, 2), (64, 3, 1)):
            x = nn.Conv(ch, (k, k), strides=(s, s), padding="VALID", dtype=self.dtype, param_dtype=self.param_dtype)(x)
            x = jax.nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        x = nn.Dense(self.features_dim, dtype=self.dtype, param_dtype=self.param_dtype)(x)
        return jax.nn.relu(x)


class LayerNormGRUCell(nn.Module):
    """GRU cell with LayerNorm on the joint projection and -1 update-gate bias
    (reference models.py:331-410, after danijar's dreamerv2 nets.py).

    Call as ``new_h = cell(h, x)`` — scan-ready: the concatenated
    ``[h, x] @ W`` projection is a single MXU matmul per step.

    ``fused=True`` runs the step through the Pallas TPU kernel
    (``sheeprl_tpu/ops/pallas_gru.py``): projection + LayerNorm + gates in one
    VMEM-resident ``pallas_call``, with the weight matrix pinned in VMEM
    across the batch grid.  The parameter tree is identical to the unfused
    path.  A shape or backend the kernel cannot serve raises
    ``FusedGRUUnavailable`` with the reason when the module is built — the
    unfused result is never substituted.  ``fused_interpret`` runs the kernel
    in interpreter mode (CPU tests).
    """

    hidden_size: int
    use_bias: bool = True
    layer_norm: bool = True
    norm_eps: float = 1e-3
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    fused: bool = False
    fused_interpret: bool = False

    @nn.compact
    def __call__(self, h: jax.Array, x: jax.Array) -> jax.Array:
        joint = jnp.concatenate([h, x], axis=-1)
        dense = nn.Dense(
            3 * self.hidden_size,
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="Dense_0",
        )
        ln = (
            nn.LayerNorm(
                epsilon=self.norm_eps, dtype=self.dtype, param_dtype=self.param_dtype, name="LayerNorm_0"
            )
            if self.layer_norm
            else None
        )

        if self.fused:
            from sheeprl_tpu.ops.pallas_gru import (
                FusedGRUUnavailable,
                fused_gru_ineligible,
                fused_layernorm_gru,
            )

            if not self.layer_norm or joint.ndim != 2:
                reason = "the kernel fuses the LayerNorm of a [batch, features] step"
            elif not self.fused_interpret and jax.default_backend() != "tpu":
                reason = f"it lowers through Mosaic and the backend is {jax.default_backend()!r}"
            else:
                reason = fused_gru_ineligible(joint.shape[-1], self.hidden_size, joint.dtype)
            if reason is not None:
                raise FusedGRUUnavailable(f"fused LayerNorm-GRU (algo.rssm_pallas) unavailable: {reason}")
            if not self.is_initializing():
                params = self.variables["params"]
                w = params["Dense_0"]["kernel"]
                b = (
                    params["Dense_0"]["bias"]
                    if self.use_bias
                    else jnp.zeros((3 * self.hidden_size,), w.dtype)
                )
                return fused_layernorm_gru(
                    joint,
                    w,
                    b,
                    params["LayerNorm_0"]["scale"],
                    params["LayerNorm_0"]["bias"],
                    h,
                    float(self.norm_eps),
                    self.fused_interpret,
                )

        z = tap_product(self, dense.name, joint, dense(joint))
        if ln is not None:
            z = ln(z)
        reset, cand, update = jnp.split(z, 3, axis=-1)
        reset = jax.nn.sigmoid(reset)
        cand = jnp.tanh(reset * cand)
        update = jax.nn.sigmoid(update - 1)
        return update * cand + (1 - update) * h


class MultiEncoder(nn.Module):
    """Fuse a CNN encoder over stacked pixel keys with an MLP encoder over
    stacked vector keys (reference models.py:413-460)."""

    cnn_encoder: Optional[nn.Module]
    mlp_encoder: Optional[nn.Module]
    cnn_keys: Sequence[str] = ()
    mlp_keys: Sequence[str] = ()

    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        feats = []
        if self.cnn_encoder is not None and self.cnn_keys:
            x = jnp.concatenate([obs[k] for k in self.cnn_keys], axis=-3)
            feats.append(cnn_forward(self.cnn_encoder, x))
        if self.mlp_encoder is not None and self.mlp_keys:
            x = jnp.concatenate([obs[k] for k in self.mlp_keys], axis=-1)
            feats.append(self.mlp_encoder(x))
        if not feats:
            raise ValueError("MultiEncoder needs at least one of cnn/mlp encoders")
        return jnp.concatenate(feats, axis=-1) if len(feats) > 1 else feats[0]


class MultiDecoder(nn.Module):
    """Fan a latent out to per-key reconstructions (reference models.py:478-503).
    Tolerates both decoders being ``None`` (JEPA world model)."""

    cnn_decoder: Optional[nn.Module]
    mlp_decoder: Optional[nn.Module]
    cnn_keys: Sequence[str] = ()
    cnn_channels: Sequence[int] = ()
    mlp_keys: Sequence[str] = ()
    mlp_dims: Sequence[int] = ()

    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None and self.cnn_keys:
            recon = self.cnn_decoder(latent)  # (..., C_total, H, W) CHW by decoder contract
            start = 0
            for k, c in zip(self.cnn_keys, self.cnn_channels):
                out[k] = recon[..., start : start + c, :, :]
                start += c
        if self.mlp_decoder is not None and self.mlp_keys:
            recon = self.mlp_decoder(latent)
            start = 0
            for k, d in zip(self.mlp_keys, self.mlp_dims):
                out[k] = recon[..., start : start + d]
                start += d
        return out
