"""The lightning indexer's score and its exact top-k selection
(DeepSeek-V3.2-Exp's sparse attention, arXiv:2512.02556 section 2.1).

``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` over the indexer's heads
``j``, one key head.  The query at ``t`` attends the ``min(k, visible)``
positions of largest ``I[t, .]``, ties to the lower position.  A selection is
discrete: a rounded score picks other positions, so the score is float32 at
``highest`` matmul precision wherever it is computed, and the selection is
exact: :func:`select_mask` (a decoded token's and a training sequence's, a mask
over the positions) finds the k-th largest score of a row by a search over the
bits of its order-preserving integer image (32 counts a row, no sort), then
takes the tied positions in order until ``k`` are taken: ``jax.lax.top_k``'s
choice, whose sort is stable.

A training sequence's block of queries is scored by :func:`block_index_scores`:
one Pallas TPU kernel over the key tiles of cache and sequence (kept apart, as
``ops/sparse_attention.py`` keeps them) that any query of the block can see.
The ``Hi`` heads of the block are stacked as ``Hi x bq`` rows against a tile's
keys, so the per-head scores ``[Hi x bq, tile]`` live in VMEM and only the
head sum ``[bq, tile]`` reaches HBM.  A tile no query can see is neither
fetched (its index map repeats the block held) nor computed, and reads 0: the
selection masks by visibility and the indexer's loss by the selection, so
nothing reads it.  The backward is two kernels over the same tiles, dQ and dW
over the block's rows and dK over the sequence's own key tiles, each
recomputing the scores; the cache's index keys are a constant snapshot and take
no cotangent.  Every product is float32 at ``highest``.  Every ``pallas_call``,
the backward's too, sits under ``jax.named_scope(SCOPE)``.  Off the TPU the
same kernels run in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sheeprl_tpu.ops.sparse_attention import _NT, _TN, _dot, _fetch_order, _interpret, _on_live_tile, _params, key_tile, tile_table

SCOPE = "index_score"
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def index_scores(q: jax.Array, k: jax.Array, w: jax.Array) -> jax.Array:
    """``q`` ``[..., T, H, d]``, ``k`` ``[..., S, d]``, ``w`` ``[..., T, H]`` -> ``[..., T, S]`` float32."""
    s = jnp.einsum("...thd,...sd->...ths", q.astype(F32), k.astype(F32), precision=_HI)
    scores = jnp.sum(jax.nn.relu(s) * w.astype(F32)[..., None], axis=-2)
    return scores + 0.0  # -0.0 and 0.0 are one score


def sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32 with the same order (``-inf`` lowest; no NaN comes here), and none of them 0."""
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    negative = (bits >> 31).astype(bool)
    return jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(keys: jax.Array, k: int) -> jax.Array:
    """The k-th largest of each row of uint32 ``keys`` ``[..., S]`` -> ``[..., 1]``:
    the largest ``v`` with at least ``k`` keys ``>= v``, a bit at a time from the top."""

    def bit(i, v):
        candidate = v | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum((keys >= candidate).astype(jnp.int32), axis=-1, keepdims=True) >= k
        return jnp.where(enough, candidate, v)

    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))


def select_mask(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """``scores``, ``visible`` ``[..., S]`` -> the ``min(k, visible)`` visible
    positions of largest score, ties to the lower position, as a mask."""
    keys = jnp.where(visible, sortable(jax.lax.stop_gradient(scores)), jnp.uint32(0))  # what is not visible lies under every score
    threshold = kth_largest(keys, k)
    above, tied = keys > threshold, keys == threshold
    left = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    return visible & (above | (tied & (jnp.cumsum(tied.astype(jnp.int32), axis=-1) <= left)))


# -- a training sequence's block of queries, over the key tiles it can see -------------------------
def _head_scores(q, k, heads: int, bq: int):
    """The stacked rows ``q`` ``[heads x bq, di]`` against a tile's keys ``[tile, di]``: ``[heads, bq, tile]``."""
    s = _dot(q, k, _NT, F32)  # float32 operands: at highest
    return s.reshape(heads, bq, s.shape[1])


def _score_kernel(live_ref, _c, _s, q_ref, w_ref, kc_ref, ks_ref, o_ref, *, n_cache, heads):
    j = pl.program_id(0)
    bq, tile = o_ref.shape

    def visit(k_ref):
        s = _head_scores(q_ref[...], k_ref[...], heads, bq)
        o_ref[...] = jnp.sum(jnp.maximum(s, 0.0) * w_ref[...], axis=0) + 0.0  # -0.0 and 0.0 are one score

    @pl.when(live_ref[j] == 0)
    def _():
        o_ref[...] = jnp.zeros((bq, tile), F32)

    _on_live_tile(live_ref, j, n_cache, lambda: visit(kc_ref), lambda: visit(ks_ref))


def _score_dq_kernel(live_ref, _c, _s, q_ref, w_ref, kc_ref, ks_ref, g_ref, dq_ref, dw_ref, dq_sc, dw_sc, *, n_cache, heads):
    j = pl.program_id(0)
    bq = g_ref.shape[0]

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, F32)
        dw_sc[...] = jnp.zeros(dw_sc.shape, F32)

    def visit(k_ref):
        k, g = k_ref[...], g_ref[...]
        s = _head_scores(q_ref[...], k, heads, bq)
        dw_sc[...] += jnp.sum(jnp.maximum(s, 0.0) * g, axis=2, keepdims=True)
        ds = jnp.where(s > 0, g * w_ref[...], 0.0)
        dq_sc[...] += _dot(ds.reshape(heads * bq, -1), k, (((1,), (0,)), ((), ())), F32)  # float32 operands: at highest

    _on_live_tile(live_ref, j, n_cache, lambda: visit(kc_ref), lambda: visit(ks_ref))

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        dq_ref[...] = dq_sc[...]
        dw_ref[...] = dw_sc[...]


def _score_dk_kernel(live_ref, q_ref, w_ref, ks_ref, g_ref, dk_ref, *, n_cache, heads):
    live = live_ref[n_cache + pl.program_id(0)] != 0
    bq = g_ref.shape[0]

    @pl.when(live)
    def _():
        q = q_ref[...]
        s = _head_scores(q, ks_ref[...], heads, bq)
        ds = jnp.where(s > 0, g_ref[...] * w_ref[...], 0.0)
        dk_ref[...] = _dot(ds.reshape(heads * bq, -1), q, _TN, F32)

    @pl.when(jnp.logical_not(live))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, F32)


def _whole(x):
    return pl.BlockSpec(x.shape, lambda j, *_: (0,) * x.ndim)


def _score_specs(kc, ks):
    """The tile, the counts of cache tiles and of all tiles, and the blocks of the two kinds of key tile at step ``j``."""
    tile = key_tile(kc.shape[0], ks.shape[0])
    cached = pl.BlockSpec((tile, kc.shape[1]), lambda j, live, c, s: (c[j], 0))
    own = pl.BlockSpec((tile, ks.shape[1]), lambda j, live, c, s: (s[j], 0))
    return tile, kc.shape[0] // tile, (kc.shape[0] + ks.shape[0]) // tile, cached, own


def _score_call(q, w, kc, ks, order):
    heads, bq = w.shape[:2]
    tile, n_cache, n_tiles, cached, own = _score_specs(kc, ks)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_score_kernel, n_cache=n_cache, heads=heads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(n_tiles,), in_specs=[_whole(q), _whole(w), cached, own],
                out_specs=pl.BlockSpec((bq, tile), lambda j, *_: (0, j))),
            out_shape=jax.ShapeDtypeStruct((bq, n_tiles * tile), F32),
            compiler_params=_params("arbitrary"), interpret=_interpret(), name="index_score_fwd",
        )(*order, q, w, kc, ks)


def _score_dq_call(q, w, kc, ks, order, g):
    heads, bq = w.shape[:2]
    tile, n_cache, n_tiles, cached, own = _score_specs(kc, ks)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_score_dq_kernel, n_cache=n_cache, heads=heads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(n_tiles,),
                in_specs=[_whole(q), _whole(w), cached, own, pl.BlockSpec((bq, tile), lambda j, *_: (0, j))],
                out_specs=[_whole(q), _whole(w)],
                scratch_shapes=[pltpu.VMEM(q.shape, F32), pltpu.VMEM(w.shape, F32)]),
            out_shape=[jax.ShapeDtypeStruct(q.shape, F32), jax.ShapeDtypeStruct(w.shape, F32)],
            compiler_params=_params("arbitrary"), interpret=_interpret(), name="index_score_dq",
        )(*order, q, w, kc, ks, g)


def _score_dk_call(q, w, kc, ks, live, g):
    heads, bq = w.shape[:2]
    tile = key_tile(kc.shape[0], ks.shape[0])
    n_cache = kc.shape[0] // tile
    own = pl.BlockSpec((tile, ks.shape[1]), lambda j, live: (j, 0))
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_score_dk_kernel, n_cache=n_cache, heads=heads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(ks.shape[0] // tile,),
                in_specs=[_whole(q), _whole(w), own, pl.BlockSpec((bq, tile), lambda j, live: (0, n_cache + j))],
                out_specs=own),
            out_shape=jax.ShapeDtypeStruct(ks.shape, F32),
            compiler_params=_params("parallel"), interpret=_interpret(), name="index_score_dk",
        )(live, q, w, ks, g)


@jax.custom_vjp
def _score(q, w, kc, ks, order):
    return _score_call(q, w, kc, ks, order)


def _score_fwd(q, w, kc, ks, order):
    return _score_call(q, w, kc, ks, order), (q, w, kc, ks, order)


def _score_bwd(residuals, g):
    q, w, kc, ks, order = residuals
    dq, dw = _score_dq_call(q, w, kc, ks, order, g)
    dk = _score_dk_call(q, w, kc, ks, order[0], g)
    # the cache is a constant snapshot, the tables are integers: no cotangent
    return dq, dw, None, dk, None


_score.defvjp(_score_fwd, _score_bwd)


def block_index_scores(q: jax.Array, w: jax.Array, cache_k: jax.Array, k: jax.Array, visible: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A block of queries of a training sequence, scored where some query of it can see.

    ``q`` ``[bq, Hi, di]``, ``w`` ``[bq, Hi]``; ``cache_k`` ``[L, di]``, the
    snapshot's index keys, a constant; ``k`` ``[T, di]``, the sequence's own;
    ``visible`` ``[bq, L + T]`` bool (the cache's first).  Returns the scores
    ``[bq, L + T]`` float32, :func:`index_scores`'s on every key tile that some
    query can see and 0 on the others, and the tile table ``[(L + T) / key_tile]``
    int32: 1 on each tile computed."""
    bq, heads, dim = q.shape
    tile = key_tile(cache_k.shape[0], k.shape[0])
    with jax.named_scope(SCOPE):
        live = tile_table(visible, tile)
        _, _, cached, own = _fetch_order(live, cache_k.shape[0] // tile)  # the mask's table is attention's alone
        rows = q.astype(F32).transpose(1, 0, 2).reshape(heads * bq, dim)
        scores = _score(rows, w.astype(F32).T[..., None], cache_k.astype(F32), k.astype(F32), (live, cached, own))
    return scores, live
