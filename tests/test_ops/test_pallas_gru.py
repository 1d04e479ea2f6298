"""Pallas fused LayerNorm-GRU cell (sheeprl_tpu/ops/pallas_gru.py): parity
with the flax cell in forward AND gradients, plus the golden GRU fixture.
Runs the kernel in interpreter mode on CPU.  The ``on_tpu`` tests lower the
same code through Mosaic at the DV3-S width; they skip here and run on the
chip with ``JAX_PLATFORMS=tpu python -m pytest tests/test_ops/test_pallas_gru.py``."""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models.blocks import LayerNormGRUCell
from sheeprl_tpu.ops.pallas_gru import (
    FusedGRUUnavailable,
    fused_gru_ineligible,
    fused_layernorm_gru,
    _gru_reference,
)

GOLDEN = Path(__file__).parent.parent / "golden" / "dv3_goldens.npz"


def _random_cell(hidden=128, in_dim=96, use_bias=True, seed=0):
    rng = np.random.default_rng(seed)
    joint_dim = hidden + in_dim
    w = jnp.asarray(rng.normal(size=(joint_dim, 3 * hidden)).astype(np.float32) * 0.2)
    b = jnp.asarray(rng.normal(size=(3 * hidden,)).astype(np.float32) * 0.1)
    g = jnp.asarray(1.0 + rng.normal(size=(3 * hidden,)).astype(np.float32) * 0.1)
    beta = jnp.asarray(rng.normal(size=(3 * hidden,)).astype(np.float32) * 0.1)
    if not use_bias:
        b = jnp.zeros_like(b)
    h = jnp.asarray(rng.normal(size=(32, hidden)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(32, in_dim)).astype(np.float32))
    return w, b, g, beta, h, x


def _flax_params(w, b, g, beta, use_bias):
    dense = {"kernel": w}
    if use_bias:
        dense["bias"] = b
    return {"params": {"Dense_0": dense, "LayerNorm_0": {"scale": g, "bias": beta}}}


def test_eligible_shapes():
    assert fused_gru_ineligible(1024, 512, jnp.float32) is None  # DV3-S: h 512 + dense 512
    assert fused_gru_ineligible(1024, 512, jnp.bfloat16) is None
    assert fused_gru_ineligible(200, 256) is None
    assert "lane" in fused_gru_ineligible(100, 100)  # 300 not a lane multiple
    assert "VMEM" in fused_gru_ineligible(5120, 4096, jnp.bfloat16)  # DV3-XL: W ~126 MB


def test_fused_on_ineligible_shape_or_backend_raises():
    """``fused=True`` never returns the unfused result: an ineligible shape
    (here DV3-XL's) or a non-TPU backend raises when the module is built."""
    h, x = jnp.zeros((2, 4096)), jnp.zeros((2, 1024))
    xl = LayerNormGRUCell(hidden_size=4096, use_bias=False, fused=True, fused_interpret=True)
    with pytest.raises(FusedGRUUnavailable, match="VMEM"):
        jax.eval_shape(xl.init, jax.random.PRNGKey(0), h, x)
    if jax.default_backend() != "tpu":
        small = LayerNormGRUCell(hidden_size=128, use_bias=False, fused=True)
        with pytest.raises(FusedGRUUnavailable, match="backend is 'cpu'"):
            jax.eval_shape(small.init, jax.random.PRNGKey(0), jnp.zeros((2, 128)), jnp.zeros((2, 96)))
    no_ln = LayerNormGRUCell(hidden_size=128, layer_norm=False, fused=True, fused_interpret=True)
    with pytest.raises(FusedGRUUnavailable, match="LayerNorm"):
        jax.eval_shape(no_ln.init, jax.random.PRNGKey(0), jnp.zeros((2, 128)), jnp.zeros((2, 96)))


@pytest.mark.parametrize("use_bias", [True, False])
def test_fused_matches_flax_forward(use_bias):
    w, b, g, beta, h, x = _random_cell(use_bias=use_bias)
    cell = LayerNormGRUCell(hidden_size=128, use_bias=use_bias, layer_norm=True, norm_eps=1e-3)
    want = cell.apply(_flax_params(w, b, g, beta, use_bias), h, x)
    joint = jnp.concatenate([h, x], axis=-1)
    got = fused_layernorm_gru(joint, w, b, g, beta, h, 1e-3, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_fused_matches_flax_gradients():
    w, b, g, beta, h, x = _random_cell()
    cell = LayerNormGRUCell(hidden_size=128, use_bias=True, layer_norm=True, norm_eps=1e-3)
    params = _flax_params(w, b, g, beta, True)

    def loss_flax(params, h, x):
        return jnp.sum(cell.apply(params, h, x) ** 2)

    def loss_fused(params, h, x):
        joint = jnp.concatenate([h, x], axis=-1)
        p = params["params"]
        return jnp.sum(
            fused_layernorm_gru(
                joint,
                p["Dense_0"]["kernel"],
                p["Dense_0"]["bias"],
                p["LayerNorm_0"]["scale"],
                p["LayerNorm_0"]["bias"],
                h,
                1e-3,
                True,
            )
            ** 2
        )

    g_flax = jax.grad(loss_flax)(params, h, x)
    g_fused = jax.grad(loss_fused)(params, h, x)
    flat_a, _ = jax.tree_util.tree_flatten(g_flax)
    flat_b, _ = jax.tree_util.tree_flatten(g_fused)
    for a, b_ in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4, rtol=2e-4)


def test_fused_cell_module_path():
    """The flax module's fused flag routes through the kernel with the SAME
    parameter tree (interpret mode on CPU)."""
    w, b, g, beta, h, x = _random_cell(use_bias=False)
    unfused = LayerNormGRUCell(hidden_size=128, use_bias=False, layer_norm=True, norm_eps=1e-3)
    fused = LayerNormGRUCell(
        hidden_size=128, use_bias=False, layer_norm=True, norm_eps=1e-3, fused=True, fused_interpret=True
    )
    params = unfused.init(jax.random.PRNGKey(0), h, x)
    # identical trees: fused init must produce the same structure
    params_fused = fused.init(jax.random.PRNGKey(0), h, x)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(params_fused)
    want = unfused.apply(params, h, x)
    got = fused.apply(params, h, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_fused_under_scan():
    """The kernel composes with lax.scan the way the RSSM uses it."""
    w, b, g, beta, h, x = _random_cell(use_bias=False)
    cell = LayerNormGRUCell(
        hidden_size=128, use_bias=False, layer_norm=True, norm_eps=1e-3, fused=True, fused_interpret=True
    )
    ref_cell = LayerNormGRUCell(hidden_size=128, use_bias=False, layer_norm=True, norm_eps=1e-3)
    params = ref_cell.init(jax.random.PRNGKey(0), h, x)
    xs = jnp.stack([x, x * 0.5, x * -0.25], axis=0)

    def run(cell_mod):
        def body(carry, x_t):
            new_h = cell_mod.apply(params, carry, x_t)
            return new_h, new_h

        return jax.lax.scan(body, h, xs)[1]

    np.testing.assert_allclose(np.asarray(run(cell)), np.asarray(run(ref_cell)), atol=1e-5, rtol=1e-5)


def test_reference_impl_matches_golden_gru():
    """_gru_reference (the custom-VJP backward's remat target) agrees with the
    reference-torch golden fixture."""
    assert GOLDEN.exists()
    gld = np.load(GOLDEN)
    joint = jnp.concatenate([jnp.asarray(gld["gru_h"]), jnp.asarray(gld["gru_x"])], axis=-1)
    with jax.default_matmul_precision("highest"):  # the TPU default rounds f32 operands to bf16
        out = _gru_reference(
            joint,
            jnp.asarray(gld["gru_linear_w"].T),
            jnp.asarray(gld["gru_linear_b"]),
            jnp.asarray(gld["gru_ln_scale"]),
            jnp.asarray(gld["gru_ln_bias"]),
            jnp.asarray(gld["gru_h"]),
            1e-3,
        )
    np.testing.assert_allclose(np.asarray(out), gld["gru_out"], atol=1e-4, rtol=1e-4)


on_tpu = pytest.mark.skipif(jax.default_backend() != "tpu", reason="lowers through Mosaic: needs the chip")


@on_tpu
@pytest.mark.parametrize("batch", [1, 16, 1024])  # player, dynamic-learning scan, imagination
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])  # 32-true, bf16-mixed
def test_mosaic_kernel_matches_flax_at_dv3_s_width(dtype, batch):
    """At the same matmul precision the kernel and the flax cell agree to
    1e-5 in f32 (measured <= 4e-6 on a v5e) and to one bf16 ulp of the output
    in bf16 (measured 2**-6 at magnitude 2-4), where the flax cell rounds the
    projection to bf16 before LayerNorm and the kernel keeps it in f32.  The
    comparison runs under ``highest`` because XLA's *default* differs with
    the batch: at batch 1 it skips the MXU and is exact while the kernel, like
    XLA at batch >= 16, rounds f32 operands to bf16 (7e-3 from exact at batch
    1024) — the second assert bounds that default-precision gap."""
    hidden, dense_units = 512, 512
    rng = np.random.default_rng(batch)
    h = jnp.asarray(rng.normal(size=(batch, hidden)), dtype)
    x = jnp.asarray(rng.normal(size=(batch, dense_units)), dtype)
    unfused = LayerNormGRUCell(hidden_size=hidden, use_bias=False)
    fused = LayerNormGRUCell(hidden_size=hidden, use_bias=False, fused=True)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(dtype), unfused.init(jax.random.PRNGKey(0), h, x)
    )
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(unfused.apply)(params, h, x), np.float32)
        got = jax.jit(fused.apply)(params, h, x)
    assert got.dtype == dtype and got.shape == (batch, hidden)
    tol = 1e-5 if dtype == jnp.float32 else 2**-6
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol, rtol=tol)
    at_default = np.asarray(jax.jit(fused.apply)(params, h, x), np.float32)
    np.testing.assert_allclose(at_default, want, atol=max(tol, 1e-2), rtol=0)


@on_tpu
def test_mosaic_kernel_gradients_under_scan():
    """custom_vjp backward + lax.scan, the way the train step uses the cell."""
    hidden = 512
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(16, hidden)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(4, 16, hidden)), jnp.float32)
    unfused = LayerNormGRUCell(hidden_size=hidden, use_bias=False)
    fused = LayerNormGRUCell(hidden_size=hidden, use_bias=False, fused=True)
    params = unfused.init(jax.random.PRNGKey(0), h, xs[0])

    def loss(cell, params):
        def body(carry, x_t):
            new_h = cell.apply(params, carry, x_t)
            return new_h, new_h

        return jnp.sum(jax.lax.scan(body, h, xs)[1] ** 2)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda p: loss(unfused, p)))(params)
        got = jax.jit(jax.grad(lambda p: loss(fused, p)))(params)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)
