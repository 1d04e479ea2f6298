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

import hashlib
import json
import os
import sys

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


# -- a decoded token an env: the kernel over each env's cache in place --------------------------
DL, DB = 2048, 6  # the decode kernel's tiles of 512: four an env
HELD = [0, 1, 300, 512, DL - 1, 1500]  # none, one, under one tile, at a tile's edge, all but the last, three tiles less a bit
STALE = 1e4  # what the rows past an env's positions hold: left from an earlier episode, never seen


def gathered(q, k, v, cache_k, cache_v, scores, held, topk, operands=F32):
    """The form the kernel replaced: ``top_k`` of the candidates (the cache's
    positions held, then the token's own row), the selected rows gathered,
    attention over them; ``operands`` rounds the products' operands."""
    batch, heads_q, dh = q.shape
    groups, length = k.shape[-1] // dh, cache_k.shape[2]
    r = lambda x: x.astype(operands).astype(F32)  # noqa: E731
    visible = jnp.concatenate([jnp.arange(length)[None] < held[:, None], jnp.ones((batch, 1), bool)], axis=-1)
    top, idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), min(topk, length + 1))
    own, at = (idx == length)[..., None], jnp.minimum(idx, length - 1)[..., None]
    rows_k = jnp.where(own, k[:, None], jnp.take_along_axis(cache_k[:, 0], at, axis=1)).reshape(batch, -1, groups, dh)
    rows_v = jnp.where(own, v[:, None], jnp.take_along_axis(cache_v[:, 0], at, axis=1)).reshape(batch, -1, groups, dh)
    s = jnp.einsum("bgrd,bkgd->bgrk", r(q.reshape(batch, groups, -1, dh)), r(rows_k), precision=HI) * dh ** -0.5
    weights = jax.nn.softmax(jnp.where((top > -jnp.inf)[:, None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bgrk,bkgd->bgrd", r(weights), r(rows_v), precision=HI).reshape(batch, heads_q, dh)


def decode_case(seed, dtype=F32, topk=200):
    """Envs at :data:`HELD` positions, scores with ties everywhere, the own row
    planted first in some envs and last in others, a tile of env 4 that holds
    nothing it selects, and stale rows past every env's positions."""
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)  # noqa: E731
    q, k, v = draw(DB, HQ, DH), draw(DB, G * DH), draw(DB, G * DH)
    held = np.asarray(HELD)
    stale = (np.arange(DL)[None, :, None] >= held[:, None, None])[:, None]
    cache_k = jnp.asarray(np.where(stale, STALE, rng.randn(DB, 1, DL, G * DH)), dtype)
    cache_v = jnp.asarray(np.where(stale, STALE, rng.randn(DB, 1, DL, G * DH)), dtype)
    scores = rng.randint(-3, 4, (DB, DL + 1)).astype(np.float32)  # seven values over 2,049 candidates
    scores[2, DL], scores[3, DL], scores[4, DL] = 9.0, -9.0, -9.0  # the own row selected, and left out where others outscore it
    scores[4, 512:1024] = -9.0  # env 4's second tile: under every score it could select
    visible = jnp.asarray(np.concatenate([np.arange(DL)[None] < held[:, None], np.ones((DB, 1), bool)], axis=1))
    selected = select_mask(jnp.asarray(scores), visible, topk)
    return q, k, v, cache_k, cache_v, jnp.asarray(scores), jnp.asarray(held, jnp.int32), selected


def test_the_decode_cases_select_what_they_are_meant_to():
    *_, held, selected = decode_case(0)
    s = np.asarray(selected)
    assert list(s.sum(1)) == [1, 2, 200, 200, 200, 200]  # held 0 and 1: every candidate
    assert s[2, DL] and not s[3, DL] and not s[4, DL] and s[0, DL]
    assert not s[4, 512:1024].any() and s[4, :512].any() and s[4, 1024:].any()
    assert not (s[:, :DL] & (np.arange(DL)[None] >= np.asarray(HELD)[:, None])).any()


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_decode_kernel_and_the_own_row_are_the_gathered_form(dtype):
    """The kernel over the cache, merged with the token's own row, against the
    gathered form at the same operands' rounding; the kernel's own log-sum-exp
    against the cache's masked scores, and the floor for the env that holds none."""
    q, k, v, cache_k, cache_v, scores, held, selected = decode_case(1, dtype)
    o = jax.jit(sa.decode_selected_attention)(q, k, v, cache_k, cache_v, selected, held)
    want = gathered(q, k, v, cache_k, cache_v, scores, held, 200, operands=dtype)
    assert o.dtype == dtype
    if dtype == F32:
        np.testing.assert_allclose(o, want, atol=2e-6, rtol=1e-5)
    else:
        np.testing.assert_allclose(o.astype(F32), want, atol=2e-2)
    _, lse = jax.jit(sa.decode_attention_cached)(q, cache_k, cache_v, selected[:, :DL], held)
    r = lambda x: x.astype(sa.dot_dtype(dtype)).astype(F32)  # noqa: E731
    s = jnp.einsum("bgrd,bsgd->bgrs", r(q.reshape(DB, G, -1, DH)), r(cache_k[:, 0].reshape(DB, DL, G, DH)), precision=HI) * DH ** -0.5
    want_lse = jax.nn.logsumexp(jnp.where(selected[:, None, None, :DL], s, -jnp.inf), axis=-1).reshape(DB, HQ)
    np.testing.assert_allclose(lse[1:], want_lse[1:], rtol=1e-5)
    assert bool(jnp.all(lse[0] == sa._FLOOR))  # held 0: no tile visited


def test_a_decode_tile_past_held_or_unselected_is_never_fetched_or_computed():
    """The tables: a dead step holds the block of the step before it, so no copy
    is issued, and every live tile is held at its own step.  The kernel: keys and
    values that are not numbers in every dead tile (past an env's positions, and
    env 4's tile that holds nothing it selected) leave the output as it was."""
    q, k, v, cache_k, cache_v, _, held, selected = decode_case(2)
    mask = selected[:, :DL]
    live, blocks = (np.asarray(x).reshape(DB, -1) for x in sa.decode_order(mask, sa.decode_tile(DL)))
    assert live.tolist() == [[0, 0, 0, 0], [1, 0, 0, 0], [1, 0, 0, 0], [1, 0, 0, 0], [1, 0, 1, 1], [1, 1, 1, 0]]
    for env in range(DB):
        for j in range(live.shape[1]):
            assert blocks[env, j] == (j if live[env, j] else blocks[env, max(j - 1, 0)]), (env, j)
    dead = np.repeat(live == 0, 512, axis=1)[:, None, :, None]
    poisoned_k, poisoned_v = jnp.where(dead, jnp.nan, cache_k), jnp.where(dead, jnp.nan, cache_v)
    run = jax.jit(sa.decode_selected_attention)
    o = run(q, k, v, cache_k, cache_v, selected, held)
    np.testing.assert_array_equal(run(q, k, v, poisoned_k, poisoned_v, selected, held), o)
    assert bool(jnp.all(jnp.isfinite(o)))


def test_the_decode_kernel_streams_whole_tiles_up_to_the_held_positions():
    assert sa.decode_tile(8192) == 512 and sa.decode_tile(32) == 32 and sa.decode_tile(DL) == 512
    assert sa.decode_cache_rows(np.asarray(HELD), DL) == 512 * (0 + 1 + 1 + 1 + 4 + 3)
    assert sa.decode_cache_rows(np.asarray([0, 7, 32]), 32) == 64


# -- what the decode kernel must leave as it was: the learner's kernels ----------------------------
GOLDEN = os.path.join(os.path.dirname(__file__), "..", "golden", "sparse_attention_learner.json")


def learner_program_hash() -> str:
    """The learner's four kernels, forward, weights and backward, lowered
    (StableHLO without locations, interpret mode off the TPU) at this file's
    small shapes and hashed.  ``python tests/test_ops/test_sparse_attention.py
    <out.json>`` writes it; ``tests/golden/sparse_attention_learner.json`` is that
    file written from the commit before the decode kernel came."""
    c = CASES["reset_inside_block"]
    selected = selection(c["pos"], c["resets"], c["block"], c["topk"], seed=3)
    q, kc, vc, k, v = operands(3)

    def loss(q, k, v):
        o, p, _ = sa.selected_attention(q, kc, vc, k, v, selected)
        return jnp.sum(o) + jnp.sum(p)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(q, k, v).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_the_learners_kernels_lower_to_what_they_did():
    assert learner_program_hash() == json.load(open(GOLDEN))["selected_attention"]


# -- the decode step compiled for a described v5e, no chip ----------------------------------------
@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    except Exception as e:  # no libtpu here, or another process holds its lock
        pytest.skip(f"no v5e topology can be described here: {e}")
    # such a compile can be written to the persistent cache and not read back
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield chip
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


class _Cfg(dict):
    __getattr__ = dict.get


def test_compiled_for_a_v5e_the_decode_step_holds_no_gathered_rows(v5e_chip, monkeypatch):
    """``jit_policy_step`` of the keye cell (16 envs, caches of 8,192 positions,
    the bfloat16 view) compiled for a described v5e, the kernels through Mosaic
    as on the chip: one kernel a layer, and its temporaries under the 268 MB of
    the eight ``bf16[32768, 512]`` copies the gathered form made."""
    from sheeprl_tpu.algos.ppo_recurrent import players
    from sheeprl_tpu.models.sparse_moe_lm import SparseMoEConfig, SparseMoELM

    shapes = json.load(open(os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks", "chip", "configs", "keye_vl2_30b.json")))["shapes"]
    agent, n, steps = SparseMoELM(SparseMoEConfig.from_cfg({**shapes, "vocab_share": 0})), shapes["num_envs"], shapes["rollout_steps"]
    # what the chip's backend would answer, asked of the CPU's here
    monkeypatch.setattr(sa, "_interpret", lambda: False)
    monkeypatch.setattr(sa, "dot_dtype", lambda dtype: jnp.dtype(jnp.bfloat16))
    monkeypatch.setattr(players, "products_round_to_bfloat16", lambda cfg: True)
    params, dtypes = players.policy_view_dtypes(agent, _Cfg(fabric=_Cfg(precision="32-true"), matmul_precision="default"), n)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)  # noqa: E731
    view = treedef.unflatten([on_chip(x.shape, d or x.dtype) for x, d in zip(leaves, dtypes)])
    carry = {"state": jax.tree_util.tree_map(lambda x: on_chip(x.shape, x.dtype), jax.eval_shape(lambda: agent.init_state(n))),
             "key": on_chip((2,), jnp.uint32), "t": on_chip((), jnp.int32), "logprobs": on_chip((steps, n), F32), "values": on_chip((steps, n), F32)}
    compiled = players.make_token_player(agent, None, steps)[0].lower(view, carry, on_chip((2, n), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == shapes["num_layers"]
    assert "bf16[32768,512]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 32768 * 512 * 2


def test_compiled_for_a_v5e_the_learners_index_score_holds_no_per_head_block(v5e_chip, monkeypatch):
    """The learner's index score for a block of 128 queries of the keye cell (16
    heads of 64, a cache of 8,192 positions and a sequence of 1,024), its value
    and gradient compiled for a described v5e: three kernels through Mosaic
    (forward, dQ and dW, dK), and no ``[128, 16, 9216]`` float32 block of
    per-head scores in the program."""
    from sheeprl_tpu.ops import sparse_index as si

    monkeypatch.setattr(si, "_interpret", lambda: False)
    on_chip = lambda *shape, dtype=F32: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)  # noqa: E731

    def loss(q, w, k, cache_k, visible, g):
        return jnp.sum(si.block_index_scores(q, w, cache_k, k, visible)[0] * g)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        on_chip(128, 16, 64), on_chip(128, 16), on_chip(1024, 64), on_chip(8192, 64), on_chip(128, 9216, dtype=jnp.bool_),
        on_chip(128, 9216)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "f32[128,16,9216]" not in text


# -- on the chip: the decode kernel at the keye cell's shapes ---------------------------------------
DECODE_CELL = dict(envs=16, length=8192, heads_q=32, groups=4, dh=128, topk=2048)


def _decode_cell_case(seed):
    c, rng = DECODE_CELL, np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), F32)  # noqa: E731
    width = c["groups"] * c["dh"]
    q, k, v = draw(c["envs"], c["heads_q"], c["dh"]), draw(c["envs"], width), draw(c["envs"], width)
    cache_k, cache_v = draw(c["envs"], 1, c["length"], width), draw(c["envs"], 1, c["length"], width)
    held = np.asarray([0, 1, 300, 512, 2047, 2048, 2049, 2800, 3000, 3500, 4096, 5000, 6000, 7000, 8000, c["length"] - 1])
    scores = jnp.asarray(rng.randn(c["envs"], c["length"] + 1), F32)
    visible = jnp.asarray(np.concatenate([np.arange(c["length"])[None] < held[:, None], np.ones((c["envs"], 1), bool)], axis=1))
    return q, k, v, cache_k, cache_v, scores, jnp.asarray(held, jnp.int32), select_mask(scores, visible, c["topk"])


@on_tpu
def test_on_the_chip_the_decode_kernel_is_the_gathered_form_at_the_cells_shapes():
    q, k, v, cache_k, cache_v, scores, held, selected = _decode_cell_case(0)
    o = jax.jit(sa.decode_selected_attention)(q, k, v, cache_k, cache_v, selected, held)
    want = jax.jit(lambda *a: gathered(*a, DECODE_CELL["topk"], operands=jnp.bfloat16))(q, k, v, cache_k, cache_v, scores, held)
    np.testing.assert_allclose(o, want, atol=2e-2)


@on_tpu
def test_on_the_chip_the_decode_kernel_lowers_through_mosaic_at_the_cells_shapes():
    q, k, v, cache_k, cache_v, _, held, selected = _decode_cell_case(1)
    text = jax.jit(sa.decode_selected_attention).lower(q, k, v, cache_k, cache_v, selected, held).compile().as_text()
    assert text.count("tpu_custom_call") == 1


if __name__ == "__main__":
    with open(sys.argv[1], "w") as fh:
        json.dump({"selected_attention": learner_program_hash()}, fh, indent=1)
