"""Attention over the indexer's selection (``sheeprl_tpu/ops/sparse_attention.py``)
against the dense masked form it replaced, kept here as the oracle: the heads'
outputs, the log-sum-exp, the weights the indexers' loss reads, dQ and dK/dV,
and the tile table, at selections that hold a reset inside the block, whole
key tiles left empty, tiles partly selected, a query that selects itself alone
and a cache tail past the episode's positions.  On the CPU the kernels run in
interpret mode; the ``on_tpu`` cases lower them through Mosaic at the keye
cell's shapes and skip here (``JAX_PLATFORMS=tpu python -m pytest
tests/test_ops/test_sparse_attention.py`` on the chip)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops import sparse_attention as sa
from sheeprl_tpu.ops.sparse_index import select_mask

F32, HI = jnp.float32, jax.lax.Precision.HIGHEST
L, T, BQ, HQ, G, DH = 128, 32, 16, 4, 2, 8  # key tiles of 32: four of the cache, one of the sequence
on_tpu = pytest.mark.skipif(jax.default_backend() != "tpu", reason="lowers through Mosaic: needs the chip")


def dense(q, cache_k, cache_v, k, v, selected, operands=F32):
    """The masked dense form over cache and sequence; ``operands`` rounds the products' operands as the kernel's do."""
    bq, heads_q, dh = q.shape
    groups = k.shape[1]
    r = lambda x: x.astype(operands).astype(F32)  # noqa: E731
    keys = jnp.concatenate([cache_k.reshape(-1, groups, dh), k], axis=0).astype(F32)
    values = jnp.concatenate([cache_v.reshape(-1, groups, dh), v], axis=0).astype(F32)
    s = jnp.einsum("tgrd,sgd->grts", r(q.reshape(bq, groups, -1, dh)), r(keys), precision=HI) * dh ** -0.5
    masked = jnp.where(selected, s, -jnp.inf)
    weights = jax.nn.softmax(masked, axis=-1)
    o = jnp.einsum("grts,sgd->tgrd", r(weights), r(values), precision=HI).reshape(bq, heads_q, dh)
    return o, jnp.sum(weights, axis=(0, 1)) / heads_q, jax.nn.logsumexp(masked, axis=-1).reshape(heads_q, bq)


def selection(pos, resets, block, topk, seed, alone=None):
    """The model's visibility for the queries ``[block * BQ, (block + 1) * BQ)`` of a
    sequence that starts ``pos`` positions into its episode with ``resets`` at the
    given indices, the top ``topk`` of seeded scores, and optionally one query that
    selects itself alone."""
    seg = np.cumsum(np.isin(np.arange(T), resets))
    tb = np.arange(block * BQ, (block + 1) * BQ)
    carried = (np.arange(L)[None] < pos) & (seg[tb][:, None] == 0)
    own = (np.arange(T)[None] <= tb[:, None]) & (seg[None] == seg[tb][:, None])
    visible = jnp.asarray(np.concatenate([carried, own], axis=1))
    scores = jnp.asarray(np.random.RandomState(seed).randn(BQ, L + T), F32)
    selected = np.array(select_mask(scores, visible, topk))
    if alone is not None:
        selected[alone] = False
        selected[alone, L + tb[alone]] = True
    return jnp.asarray(selected)


def operands(seed, dtype=F32):
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)  # noqa: E731
    return draw(BQ, HQ, DH), draw(L, G * DH), draw(L, G * DH), draw(T, G, DH), draw(T, G, DH)


def tiles_of(selected):
    """A NumPy count of the tiles any query of the block selected from."""
    s = np.asarray(selected).reshape(BQ, -1, sa.key_tile(L, T))
    return s.any(axis=(0, 2)).astype(np.int32)


CASES = {
    # a reset inside the block: the queries after it see no cache; the cache past pos=40 (tiles 2 and 3) is visible to none
    "reset_inside_block": dict(pos=40, resets=[5], block=0, topk=12),
    # every query after the reset: no cache tile is live, only the sequence's own
    "after_a_reset": dict(pos=100, resets=[3], block=1, topk=6),
    # the whole cache visible and most of it selected: every tile live, the last only partly
    "whole_cache": dict(pos=L, resets=[], block=1, topk=120),
    # a query that selects itself alone beside others that select across tiles
    "itself_alone": dict(pos=70, resets=[], block=1, topk=9, alone=4),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_dense_masked_form(case):
    c = CASES[case]
    selected = selection(c["pos"], c["resets"], c["block"], c["topk"], seed=len(case), alone=c.get("alone"))
    q, kc, vc, k, v = operands(len(case))
    o, p, live = jax.jit(sa.selected_attention)(q, kc, vc, k, v, selected)
    want_o, want_p, want_lse = dense(q, kc, vc, k, v, selected)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(p, want_p, atol=1e-7, rtol=1e-5)
    assert float(jnp.max(jnp.where(selected, 0.0, jnp.abs(p)))) == 0.0  # nothing off the selection
    np.testing.assert_array_equal(live, tiles_of(selected))
    # the log-sum-exp the backward and the weights' pass read, as the forward kernel leaves it
    qg = q.reshape(BQ, G, HQ // G, DH).transpose(1, 2, 0, 3).reshape(G, -1, DH)
    order = sa._fetch_order(live, L // sa.key_tile(L, T))
    _, lse = sa._forward_call(qg, kc, vc, k.reshape(T, -1), v.reshape(T, -1), selected.astype(jnp.int8), order, jnp.dtype(F32))
    np.testing.assert_allclose(lse[..., 0].reshape(HQ, BQ), want_lse, rtol=1e-6)
    # dQ and dK/dV of the sequence's keys under a seeded cotangent; the cache is a constant
    do = jnp.asarray(np.random.RandomState(7).randn(BQ, HQ, DH), F32)
    loss = lambda fn: lambda q, kc, k, v: jnp.sum(fn(q, kc, vc, k, v, selected)[0] * do)  # noqa: E731
    got = jax.jit(jax.grad(loss(sa.selected_attention), argnums=(0, 1, 2, 3)))(q, kc, k, v)
    want = jax.grad(loss(dense), argnums=(0, 2, 3))(q, kc, k, v)
    assert float(jnp.max(jnp.abs(got[1]))) == 0.0
    for g, w in zip((got[0], got[2], got[3]), want):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))) + 1e-7)


def test_the_cases_skip_what_they_are_meant_to():
    live = {case: tiles_of(selection(c["pos"], c["resets"], c["block"], c["topk"], seed=len(case), alone=c.get("alone")))
            for case, c in CASES.items()}
    assert list(live["reset_inside_block"]) == [1, 1, 0, 0, 1]  # the tail past pos, whole tiles, skipped
    assert list(live["after_a_reset"]) == [0, 0, 0, 0, 1]
    assert list(live["whole_cache"]) == [1, 1, 1, 1, 1]
    partly = selection(**{k: v for k, v in CASES["whole_cache"].items() if k != "block"}, block=1, seed=len("whole_cache"))
    assert 0 < int(np.asarray(partly)[:, L:].sum()) < BQ * T  # the sequence's own tile is partly selected


def test_a_tile_no_query_selected_is_never_computed():
    """Keys and values that are not numbers in a skipped tile leave every output
    as it was: the tile is not read into any product (the dense form multiplies
    them by a zero weight and turns everything into NaN)."""
    c = CASES["reset_inside_block"]
    selected = selection(c["pos"], c["resets"], c["block"], c["topk"], seed=3)
    q, kc, vc, k, v = operands(3)
    poisoned_k, poisoned_v = kc.at[64:].set(jnp.nan), vc.at[64:].set(jnp.nan)  # tiles 2 and 3, past pos
    run = jax.jit(sa.selected_attention)
    o, p, _ = run(q, kc, vc, k, v, selected)
    o2, p2, _ = run(q, poisoned_k, poisoned_v, k, v, selected)
    np.testing.assert_array_equal(o, o2)
    np.testing.assert_array_equal(p, p2)
    assert bool(jnp.all(jnp.isnan(dense(q, poisoned_k, poisoned_v, k, v, selected)[0])))


def test_bfloat16_operands_are_the_dense_form_at_bfloat16_products():
    """Operands of bfloat16 (``bf16-mixed``, and the TPU's one pass at the default
    precision) are the dense form whose products round their operands so."""
    c = CASES["itself_alone"]
    selected = selection(c["pos"], c["resets"], c["block"], c["topk"], seed=11, alone=c["alone"])
    q, kc, vc, k, v = operands(11, jnp.bfloat16)
    assert sa.dot_dtype(q.dtype) == jnp.bfloat16
    o, p, _ = jax.jit(sa.selected_attention)(q, kc, vc, k, v, selected)
    want_o, want_p, _ = dense(q, kc, vc, k, v, selected, operands=jnp.bfloat16)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(o.astype(F32), want_o, atol=2e-2)
    np.testing.assert_allclose(p, want_p, atol=1e-6, rtol=1e-5)  # the scores' products are exact of rounded operands


def test_the_key_tile_and_the_products_precision_come_from_shapes_and_backend():
    assert sa.key_tile(8192, 1024) == 512  # the keye cell: 18 tiles of 512
    assert sa.key_tile(32, 8) == 8 and sa.key_tile(L, T) == 32
    assert sa.dot_dtype(F32) == (jnp.bfloat16 if jax.default_backend() == "tpu" else F32)
    with jax.default_matmul_precision("highest"):
        assert sa.dot_dtype(F32) == F32


# -- on the chip: Mosaic at the keye cell's shapes ---------------------------------------------
CELL = dict(length=8192, seq=1024, bq=128, heads_q=32, groups=4, dh=128)


def _cell_case(seed):
    c, rng = CELL, np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), F32)  # noqa: E731
    q = draw(c["bq"], c["heads_q"], c["dh"])
    kc, vc = draw(c["length"], c["groups"] * c["dh"]), draw(c["length"], c["groups"] * c["dh"])
    k, v = draw(c["seq"], c["groups"], c["dh"]), draw(c["seq"], c["groups"], c["dh"])
    # the third update's traffic: 2,500 cached positions of the first episode, a reset 300 queries into the block's sequence
    t = np.arange(384, 384 + c["bq"])
    visible = np.concatenate([(np.arange(c["length"])[None] < 2500) & (t[:, None] < 300),
                              (np.arange(c["seq"])[None] <= t[:, None]) & ((np.arange(c["seq"])[None] >= 300) == (t[:, None] >= 300))], 1)
    selected = select_mask(jnp.asarray(rng.randn(c["bq"], c["length"] + c["seq"]), F32), jnp.asarray(visible), 2048)
    return q, kc, vc, k, v, selected


@on_tpu
def test_on_the_chip_the_kernel_is_the_dense_form_at_the_cells_shapes():
    q, kc, vc, k, v, selected = _cell_case(0)
    o, p, live = jax.jit(sa.selected_attention)(q, kc, vc, k, v, selected)
    want_o, want_p, _ = jax.jit(lambda *a: dense(*a, operands=jnp.bfloat16))(q, kc, vc, k, v, selected)
    assert int(jnp.sum(live)) < live.size  # tiles are skipped
    np.testing.assert_allclose(o, want_o, atol=2e-2)
    np.testing.assert_allclose(p, want_p, atol=2e-3)
    do = jnp.asarray(np.random.RandomState(1).randn(*q.shape), F32)
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, kc, vc, k, v, selected)[0] * do)  # noqa: E731
    got = jax.jit(jax.grad(loss(sa.selected_attention), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(lambda *a: dense(*a, operands=jnp.bfloat16)), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-2 * float(jnp.max(jnp.abs(w))))


@on_tpu
def test_on_the_chip_every_kernel_lowers_through_mosaic_at_the_cells_shapes():
    q, kc, vc, k, v, selected = _cell_case(1)
    def loss(q, k, v):
        o, p, _ = sa.selected_attention(q, kc, vc, k, v, selected)
        return jnp.sum(o) + jnp.sum(p)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile().as_text()
    assert text.count("tpu_custom_call") >= 4  # forward, weights, dQ, dK/dV
