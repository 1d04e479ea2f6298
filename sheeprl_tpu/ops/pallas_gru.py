"""Pallas TPU kernel: fused LayerNorm-GRU cell.

The RSSM's hot op (SURVEY §7.10's Pallas candidate) is the recurrent cell
stepped T times under ``lax.scan``: ``concat(h, x) @ W`` (one MXU matmul)
followed by LayerNorm over the joint ``3H`` projection and the gate
elementwise chain (reference models.py:331-410; our flax cell
``sheeprl_tpu/models/blocks.py:LayerNormGRUCell``).  This kernel runs the
whole step in one ``pallas_call``: the weight matrix stays resident in VMEM
across the batch grid, and the LN + sigmoid/tanh gate math happens on the VPU
without round-tripping the ``[B, 3H]`` projection through HBM.

Semantics are bit-compatible with the flax cell (gate order reset|cand|update,
``cand = tanh(reset * cand)``, ``update = sigmoid(update - 1)``), pinned by
``tests/test_ops/test_pallas_gru.py`` against the flax cell and the golden GRU
fixture.  Gradients: ``jax.custom_vjp`` whose backward recomputes the step
with plain jnp ops (rematerialization) and reuses XLA's autodiff — the
backward is a standard fused XLA graph, the forward (the op run T times per
scan in both dynamic learning and imagination) is the Pallas kernel.

Eligibility (``fused_gru_ineligible``): ``3H`` a lane multiple (all DV3 size
presets satisfy this) and the whole weight block plus one batch tile fitting
the VMEM the call may claim — DV3-S does in both precisions (compiled and
trained on a v5e), XL (W ~126 MB in bf16) does not.  The flax cell raises ``FusedGRUUnavailable``
with the reason when ``fused=True`` meets an ineligible shape or a non-TPU
backend; it never substitutes the unfused result.

Speed against the XLA-compiled flax cell: not measured on this chip (ROADMAP
S3 owns the lever sweep), so the fused path ships **off by default**
(``algo.rssm_pallas``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_LANE = 128
_BATCH_BLOCK = 256
# Mosaic's default scoped-VMEM limit (16 MiB on a v5e) is below what the
# resident f32 weight block of DV3-S needs, so the call asks for what its
# blocks take.  48 MiB is the most it will ask for: under half of a v5e
# core's 128 MiB (``pltpu.get_tpu_info().vmem_capacity_bytes``).
_VMEM_CEILING_BYTES = 48 * 1024 * 1024


class FusedGRUUnavailable(ValueError):
    """``fused=True`` (``algo.rssm_pallas``) on a shape or backend the Pallas
    kernel cannot serve; the message carries the reason."""


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _sublane(dtype) -> int:
    """Rows per VMEM tile: 8 for 4-byte types, 16 for bf16 (packed pairs)."""
    return 8 * (4 // np.dtype(dtype).itemsize)


def _vmem_bytes(joint_dim: int, hidden_size: int, dtype) -> int:
    """VMEM one grid step of a full batch block holds: W and the three
    [1, 3H] vectors once (their block index never changes, so they are
    single-buffered), the joint/h/out batch tiles double-buffered, and the
    f32 projection with its LayerNorm and gate temporaries."""
    itemsize = np.dtype(dtype).itemsize
    d_pad = _round_up(joint_dim, _LANE)
    three_h = 3 * hidden_size
    resident = (d_pad + 3 * _sublane(dtype)) * three_h * itemsize
    tiles = 2 * _BATCH_BLOCK * (d_pad + 2 * hidden_size) * itemsize
    temporaries = 4 * _BATCH_BLOCK * three_h * 4
    return resident + tiles + temporaries


def fused_gru_ineligible(joint_dim: int, hidden_size: int, dtype=jnp.float32) -> Optional[str]:
    """Why the kernel cannot take this shape (``None`` when it can)."""
    if (3 * hidden_size) % _LANE != 0:
        return f"3*hidden_size = {3 * hidden_size} is not a multiple of the {_LANE}-lane tile"
    need = _vmem_bytes(joint_dim, hidden_size, dtype)
    if need > _VMEM_CEILING_BYTES:
        return (
            f"the [{_round_up(joint_dim, _LANE)}, {3 * hidden_size}] {np.dtype(dtype).name} weight block "
            f"plus one batch tile needs {need / 2**20:.0f} MiB of VMEM, over the "
            f"{_VMEM_CEILING_BYTES / 2**20:.0f} MiB the kernel may claim (W is not tiled)"
        )
    return None


def _gru_kernel(joint_ref, w_ref, b_ref, g_ref, beta_ref, h_ref, out_ref, *, eps: float):
    """One batch tile: projection (MXU, native input dtype with fp32
    accumulation) + LayerNorm + gates (VPU, fp32).  f32 operands follow the
    ambient ``jax.default_matmul_precision`` like the flax cell does (the TPU
    default rounds them to bf16); bf16 operands pin DEFAULT — Mosaic rejects
    an fp32 contraction over bf16 vectors ("Bad lhs type"), which is what
    ``matmul_precision=highest`` would otherwise ask for."""
    precision = jax.lax.Precision.DEFAULT if joint_ref.dtype == jnp.bfloat16 else None
    a = jnp.dot(
        joint_ref[:], w_ref[:], precision=precision, preferred_element_type=jnp.float32
    ) + b_ref[:].astype(jnp.float32)
    # LayerNorm over the 3H projection
    mean = jnp.mean(a, axis=-1, keepdims=True)
    centered = a - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    n = centered * jax.lax.rsqrt(var + eps)
    n = n * g_ref[:].astype(jnp.float32) + beta_ref[:].astype(jnp.float32)
    hidden = out_ref.shape[-1]
    reset = jax.nn.sigmoid(n[:, :hidden])
    cand = jnp.tanh(reset * n[:, hidden : 2 * hidden])
    update = jax.nn.sigmoid(n[:, 2 * hidden :] - 1.0)
    h = h_ref[:].astype(jnp.float32)
    out_ref[:] = (update * cand + (1.0 - update) * h).astype(out_ref.dtype)


def _gru_pallas(joint: jax.Array, w: jax.Array, b: jax.Array, g: jax.Array, beta: jax.Array,
                h: jax.Array, *, eps: float, interpret: bool) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, joint_dim = joint.shape
    hidden = h.shape[-1]
    three_h = 3 * hidden

    # pad the contraction dim to lanes (zero rows of W contribute nothing) and
    # the batch dim to the tile grid (whole sublane tiles of the input dtype:
    # the player's batch of 1 becomes 16 rows in bf16, 8 in f32)
    d_pad = _round_up(joint_dim, _LANE)
    bm = min(_BATCH_BLOCK, _round_up(batch, max(_sublane(joint.dtype), _sublane(h.dtype))))
    b_pad = _round_up(batch, bm)
    if d_pad != joint_dim:
        joint = jnp.pad(joint, ((0, 0), (0, d_pad - joint_dim)))
        w = jnp.pad(w, ((0, d_pad - joint_dim), (0, 0)))
    if b_pad != batch:
        joint = jnp.pad(joint, ((0, b_pad - batch), (0, 0)))
        h = jnp.pad(h, ((0, b_pad - batch), (0, 0)))

    def resident(shape):
        # same block at every grid step: one buffer, fetched once
        return pl.BlockSpec(
            shape, lambda i: (0, 0), memory_space=pltpu.VMEM, pipeline_mode=pl.Buffered(1)
        )

    out = pl.pallas_call(
        functools.partial(_gru_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((b_pad, hidden), h.dtype),
        grid=(b_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, d_pad), lambda i: (i, 0), memory_space=pltpu.VMEM),
            resident((d_pad, three_h)),
            resident((1, three_h)),
            resident((1, three_h)),
            resident((1, three_h)),
            pl.BlockSpec((bm, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_CEILING_BYTES),
        interpret=interpret,
    )(joint, w, b.reshape(1, -1), g.reshape(1, -1), beta.reshape(1, -1), h)
    return out[:batch]


def _gru_reference(joint, w, b, g, beta, h, eps):
    """Plain-jnp step, numerically identical to the kernel — used for the
    custom-VJP backward (remat)."""
    a = jnp.dot(joint, w, preferred_element_type=jnp.float32) + b.astype(jnp.float32)
    mean = jnp.mean(a, axis=-1, keepdims=True)
    centered = a - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    n = centered * jax.lax.rsqrt(var + eps)
    n = n * g.astype(jnp.float32) + beta.astype(jnp.float32)
    hidden = h.shape[-1]
    reset = jax.nn.sigmoid(n[:, :hidden])
    cand = jnp.tanh(reset * n[:, hidden : 2 * hidden])
    update = jax.nn.sigmoid(n[:, 2 * hidden :] - 1.0)
    return (update * cand + (1.0 - update) * h.astype(jnp.float32)).astype(h.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def fused_layernorm_gru(joint, w, b, g, beta, h, eps: float = 1e-3, interpret: bool = False):
    """``new_h = GRU(LN(joint @ w + b; g, beta), h)`` as one Pallas kernel."""
    return _gru_pallas(joint, w, b, g, beta, h, eps=eps, interpret=interpret)


def _fused_fwd(joint, w, b, g, beta, h, eps, interpret):
    out = _gru_pallas(joint, w, b, g, beta, h, eps=eps, interpret=interpret)
    return out, (joint, w, b, g, beta, h)


def _fused_bwd(eps, interpret, residuals, cotangent):
    del interpret
    joint, w, b, g, beta, h = residuals
    _, vjp = jax.vjp(lambda *args: _gru_reference(*args, eps), joint, w, b, g, beta, h)
    return vjp(cotangent)


fused_layernorm_gru.defvjp(_fused_fwd, _fused_bwd)
