"""The learner's index score (``sheeprl_tpu/ops/sparse_index.py::block_index_scores``)
against the dense form it replaced, :func:`index_scores` over cache and
sequence concatenated, kept as the oracle: the scores on every visible
position, the selection drawn from them, dQ, dW and dK through a loss masked
to a selection, and the tile table, at visibilities that hold a reset inside
the block, a block past a reset, an empty cache and a whole one.  On the CPU
the kernels run in interpret mode; the ``on_tpu`` cases lower them through
Mosaic at the keye cell's shapes and skip here (``JAX_PLATFORMS=tpu python -m
pytest tests/test_ops/test_sparse_index.py`` on the chip)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops.sparse_attention import key_tile
from sheeprl_tpu.ops.sparse_index import block_index_scores, index_scores, select_mask

F32 = jnp.float32
L, T, BQ, H, D = 128, 32, 16, 4, 8  # key tiles of 32: four of the cache, one of the sequence
on_tpu = pytest.mark.skipif(jax.default_backend() != "tpu", reason="lowers through Mosaic: needs the chip")


def dense(q, w, cache_k, k):
    return index_scores(q, jnp.concatenate([cache_k, k], axis=0), w)


def visibility(pos, resets, block, seq=T, length=L, bq=BQ):
    """The model's visibility for the queries ``[block * bq, (block + 1) * bq)`` of a
    sequence that starts ``pos`` positions into its episode with ``resets`` at the
    given indices: the cache until the first reset, then the sequence's own, causally."""
    seg = np.cumsum(np.isin(np.arange(seq), resets))
    tb = np.arange(block * bq, (block + 1) * bq)
    carried = (np.arange(length)[None] < pos) & (seg[tb][:, None] == 0)
    own = (np.arange(seq)[None] <= tb[:, None]) & (seg[None] == seg[tb][:, None])
    return jnp.asarray(np.concatenate([carried, own], axis=1))


def operands(seed, bq=BQ, heads=H, dim=D, length=L, seq=T):
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), F32)  # noqa: E731
    return draw(bq, heads, dim), draw(bq, heads), draw(length, dim), draw(seq, dim)


def tiles_of(visible, tile):
    """A NumPy count of the tiles any query of the block can see."""
    v = np.asarray(visible)
    return v.reshape(v.shape[0], -1, tile).any(axis=(0, 2)).astype(np.int32)


CASES = {
    # a reset inside the block: the queries after it see no cache; the cache past pos=40 (tiles 2 and 3) is visible to none
    "reset_inside_block": dict(pos=40, resets=[5], block=0, topk=12),
    # every query of the block after a reset: no cache tile is live, only the sequence's own
    "past_a_reset": dict(pos=100, resets=[3], block=1, topk=6),
    # a sequence that starts its episode: the cache holds nothing of it
    "empty_cache": dict(pos=0, resets=[], block=1, topk=9),
    # the whole cache visible: every tile live
    "whole_cache": dict(pos=L, resets=[], block=1, topk=120),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_dense_score_where_a_query_can_see(case):
    c = CASES[case]
    visible = visibility(c["pos"], c["resets"], c["block"])
    q, w, kc, k = operands(len(case))
    scores, live = jax.jit(block_index_scores)(q, w, kc, k, visible)
    want = dense(q, w, kc, k)
    # the kernel sums the heads in its own order: float32 rounding of a sum of four terms
    np.testing.assert_allclose(np.where(visible, scores, 0.0), np.where(visible, want, 0.0), rtol=1e-6, atol=1e-6)
    tile = key_tile(L, T)
    np.testing.assert_array_equal(live, tiles_of(visible, tile))
    dead = np.repeat(np.asarray(live) == 0, tile)[None]
    assert float(jnp.max(jnp.where(dead, jnp.abs(scores), 0.0))) == 0.0
    np.testing.assert_array_equal(select_mask(scores, visible, c["topk"]), select_mask(want, visible, c["topk"]))


@pytest.mark.parametrize("case", sorted(CASES))
def test_dq_dw_and_dk_are_the_dense_forms_through_a_loss_on_the_selection(case):
    """The indexer's loss reads the scores on its selection alone: through such
    a loss the kernel's cotangents are ``jax.grad`` of the dense form's; the
    cache's index keys, a snapshot, take none."""
    c = CASES[case]
    visible = visibility(c["pos"], c["resets"], c["block"])
    q, w, kc, k = operands(len(case) + 1)
    selected = select_mask(dense(q, w, kc, k), visible, c["topk"])
    target = jnp.asarray(np.random.RandomState(7).dirichlet(np.ones(L + T), BQ), F32)

    def loss(fn):
        def of(q, w, kc, k):
            log_q = jax.nn.log_softmax(jnp.where(selected, fn(q, w, kc, k), -jnp.inf), axis=-1)
            return -jnp.sum(jnp.where(selected, target * log_q, 0.0))
        return of

    got = jax.jit(jax.grad(loss(lambda *a: block_index_scores(*a, visible)[0]), argnums=(0, 1, 2, 3)))(q, w, kc, k)
    want = jax.grad(loss(dense), argnums=(0, 1, 3))(q, w, kc, k)
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0
    for g, expected in zip((got[0], got[1], got[3]), want):
        np.testing.assert_allclose(g, expected, atol=1e-5 * float(jnp.max(jnp.abs(expected))) + 1e-7)


def test_the_cases_see_what_they_are_meant_to():
    live = {case: list(tiles_of(visibility(c["pos"], c["resets"], c["block"]), key_tile(L, T))) for case, c in CASES.items()}
    assert live["reset_inside_block"] == [1, 1, 0, 0, 1]  # the tail past pos, whole tiles, unseen
    assert live["past_a_reset"] == [0, 0, 0, 0, 1]
    assert live["empty_cache"] == [0, 0, 0, 0, 1]
    assert live["whole_cache"] == [1, 1, 1, 1, 1]


def test_a_tile_no_query_can_see_is_never_computed():
    """Index keys that are not numbers in the unseen tiles leave the scores and
    every cotangent as they were: those tiles are read into no product (the
    dense form turns their scores into NaN)."""
    c = CASES["reset_inside_block"]
    visible = visibility(c["pos"], c["resets"], c["block"])
    q, w, kc, k = operands(3)
    poisoned = kc.at[64:].set(jnp.nan)  # tiles 2 and 3, past pos
    run = jax.jit(lambda kc: block_index_scores(q, w, kc, k, visible))
    (scores, live), (again, live2) = run(kc), run(poisoned)
    np.testing.assert_array_equal(scores, again)
    np.testing.assert_array_equal(live, live2)
    assert int(jnp.sum(live)) == 3 and bool(jnp.all(jnp.isnan(dense(q, w, poisoned, k)[:, 64:L])))
    selected = select_mask(scores, visible, c["topk"])
    grad = jax.jit(jax.grad(lambda q, w, k, kc: jnp.sum(jnp.where(selected, block_index_scores(q, w, kc, k, visible)[0], 0.0)),
                            argnums=(0, 1, 2)))
    for a, b in zip(grad(q, w, k, kc), grad(q, w, k, poisoned)):
        np.testing.assert_array_equal(a, b)


def test_no_score_is_minus_zero():
    """A position whose every head's product is at most 0 under weights that are
    all negative sums ``-0.0`` terms: the score is ``0.0``, one key with every
    other zero score for the selection's order."""
    q, w, kc, k = operands(5)
    w = -jnp.abs(w)
    kc = kc.at[:40].set(0.0)
    visible = visibility(L, [], 0)
    scores, _ = jax.jit(block_index_scores)(q, w, kc, k, visible)
    zeros = np.asarray(scores) == 0.0
    assert zeros[:, :40].all()
    assert not np.signbit(np.asarray(scores)[zeros]).any()


# -- on the chip: Mosaic at the keye cell's shapes ---------------------------------------------
CELL = dict(length=8192, seq=1024, bq=128, heads=16, dim=64, topk=2048)


def _cell_case(seed):
    c = CELL
    q, w, kc, k = operands(seed, c["bq"], c["heads"], c["dim"], c["length"], c["seq"])
    # the third update's traffic: 2,500 cached positions of the first episode, a reset 300 queries into the block's sequence
    seg = np.arange(c["seq"]) >= 300
    t = np.arange(256, 256 + c["bq"])
    visible = np.concatenate([(np.arange(c["length"])[None] < 2500) & (t[:, None] < 300),
                              (np.arange(c["seq"])[None] <= t[:, None]) & (seg[None] == seg[t][:, None])], 1)
    return q, w, kc, k, jnp.asarray(visible)


@on_tpu
def test_on_the_chip_the_kernel_is_the_dense_score_at_the_cells_shapes():
    q, w, kc, k, visible = _cell_case(0)
    scores, live = jax.jit(block_index_scores)(q, w, kc, k, visible)
    want = jax.jit(dense)(q, w, kc, k)
    assert 0 < int(jnp.sum(live)) < live.size  # tiles are skipped
    row_max = jnp.max(jnp.where(visible, jnp.abs(want), 0.0), axis=1, keepdims=True)
    assert float(jnp.max(jnp.where(visible, jnp.abs(scores - want), 0.0) / row_max)) < 1e-6
    topk = CELL["topk"]
    np.testing.assert_array_equal(select_mask(scores, visible, topk), select_mask(want, visible, topk))
    selected = select_mask(want, visible, topk)
    loss = lambda fn: lambda q, w, k: jnp.sum(jnp.where(selected, fn(q, w, kc, k), 0.0) ** 2)  # noqa: E731
    got = jax.jit(jax.grad(loss(lambda *a: block_index_scores(*a, visible)[0]), argnums=(0, 1, 2)))(q, w, k)
    expected = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, w, k)
    for g, e in zip(got, expected):
        np.testing.assert_allclose(g, e, atol=1e-5 * float(jnp.max(jnp.abs(e))))


@on_tpu
def test_on_the_chip_every_index_kernel_lowers_through_mosaic_at_the_cells_shapes():
    q, w, kc, k, visible = _cell_case(1)
    loss = lambda q, w, k: jnp.sum(block_index_scores(q, w, kc, k, visible)[0])  # noqa: E731
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(q, w, k).compile().as_text()
    assert text.count("tpu_custom_call") == 3  # forward, dQ and dW, dK
