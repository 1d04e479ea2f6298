"""Attention over the lightning indexer's selection, for a training sequence:
one Pallas TPU kernel that visits only the key tiles a block of queries selected from.

A block of ``bq`` queries of one sequence attends, each query its own selected
positions, over the layer's cached keys and values ``[L, Hkv dh]`` (a constant
snapshot: they take no cotangent) and the sequence's own ``[T, Hkv dh]``, kept
apart: a key tile never straddles the two.  The keys are cut into tiles of
:func:`key_tile` positions; a *tile table* (:func:`tile_table`, a reduction of
the selection) says which tiles any query of the block selected from, and the
kernels visit those alone: a tile no query selected from is neither fetched
(its index map repeats the block held, so no copy is issued, as splash
attention does) nor computed.  Such a tile holds only positions whose softmax
weight is exactly zero, so skipping it changes no number.

The grid runs over key-value groups and key tiles: the ``Hq / Hkv`` query heads
of a group are stacked as ``Hq / Hkv x bq`` rows against one key head, so a
tile's scores ``[Hq / Hkv x bq, tile]`` live in VMEM and never reach HBM.

- :func:`selected_attention`'s forward is an online softmax (float32 running
  maximum and sum) that returns the heads' outputs and their log-sum-exp;
- a second pass over the same tiles, with the log-sum-exp known, returns what
  the indexers' loss reads: the weights averaged over the heads ``[bq, L + T]``
  float32, zero off the selection (no gradient);
- the backward is two kernels over the same tiles, dQ over the block's rows
  and dK/dV over the sequence's key tiles, each recomputing the scores from
  ``q``, ``k`` and the saved log-sum-exp.

Products take their operands as :func:`dot_dtype` says, with float32
accumulation: on the TPU at the default matmul precision that is bfloat16, the
one MXU pass XLA gives the dense float32 product this replaces; elsewhere
float32.  The mask, the softmax statistics and every accumulator are float32.
Every ``pallas_call`` sits under ``jax.named_scope(SCOPE)``, the backward's too,
so that a profile counts the kernels under the scope they serve.  Off the TPU
the same kernels run in interpret mode.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SCOPE = "sparse_attention"
F32 = jnp.float32
_TILE = 512  # the most positions a key tile holds
_LANES = 128  # the log-sum-exp is kept lane-replicated, as a TPU tile row
_FLOOR = -0.7 * float(np.finfo(np.float32).max)  # the running maximum's start: finite, so a row with nothing yet stays finite
_VMEM_LIMIT = 48 * 1024 * 1024  # under half of a v5e core's 128 MiB; a step holds about 16 MiB
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def key_tile(cache_len: int, seq_len: int) -> int:
    """Positions a key tile holds: the most, up to 512, that divides both the cache and the sequence."""
    return math.gcd(cache_len, seq_len, _TILE)


def dot_dtype(dtype) -> jnp.dtype:
    """The operands' type of the kernels' products: bfloat16 where XLA's own
    product of ``dtype`` operands would take them so (bfloat16 operands; float32
    on the TPU at the default matmul precision), else float32."""
    precision = str(jax.config.jax_default_matmul_precision or "default").lower()
    if jnp.dtype(dtype) == jnp.bfloat16 or (jax.default_backend() == "tpu" and precision in ("default", "bfloat16", "fastest")):
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(F32)


def tile_table(selected: jax.Array, tile: int) -> jax.Array:
    """``selected`` ``[bq, S]`` -> ``[S / tile]`` int32: 1 where any query selected any position of the tile."""
    return jnp.any(selected.reshape(selected.shape[0], -1, tile), axis=(0, 2)).astype(jnp.int32)


def _held(live: jax.Array) -> jax.Array:
    """At each step the live tile at or before it, the first live tile before any (0 where none is)."""
    steps = jnp.arange(live.shape[0])
    last = jax.lax.cummax(jnp.where(live, steps, -1))
    return jnp.where(last >= 0, last, jnp.argmax(live)).astype(jnp.int32)


def _fetch_order(live: jax.Array, n_cache: int) -> Tuple[jax.Array, ...]:
    """The scalar-prefetched tables: which tiles are live, and at each step the
    tile of the mask, of the cache and of the sequence to hold.  A dead step holds
    what the step before it held, so its copies are never issued."""
    on = live > 0
    cached = jnp.arange(live.shape[0]) < n_cache
    return live, _held(on), _held(on & cached), jnp.maximum(_held(on & ~cached) - n_cache, 0)


# -- the kernels ----------------------------------------------------------------------
def _dot(a, b, dims, dtype):
    precision = jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims, precision=precision, preferred_element_type=F32)


def _masked_scores(q, k, mask, heads: int, scale: float, dtype):
    """``q`` ``[heads x bq, dh]`` against a tile's keys ``[tile, dh]``: scaled scores, ``-inf`` off the selection ``mask`` ``[bq, tile]``."""
    s = _dot(q, k, _NT, dtype) * scale
    bq, tile = mask.shape
    selected = (mask.astype(jnp.int32) != 0)[None]
    return jnp.where(selected, s.reshape(heads, bq, tile), -jnp.inf).reshape(heads * bq, tile)


def _on_live_tile(live_ref, j, n_cache: int, cached, own) -> None:
    """Run ``cached()`` on a live tile of the cache, ``own()`` on a live tile of the sequence."""
    live = live_ref[j] != 0
    pl.when(live & (j < n_cache))(cached)
    pl.when(live & (j >= n_cache))(own)


def _forward_kernel(live_ref, _m, _c, _s, q_ref, kc_ref, vc_ref, ks_ref, vs_ref, mask_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                    n_cache, heads, scale, dtype):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _FLOOR, F32)
        l_sc[...] = jnp.zeros(l_sc.shape, F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    def visit(k_ref, v_ref):
        s = _masked_scores(q_ref[0], k_ref[...], mask_ref[...], heads, scale, dtype)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + _dot(p, v_ref[...], (((1,), (0,)), ((), ())), dtype)
        m_sc[...] = m_new

    _on_live_tile(live_ref, j, n_cache, lambda: visit(kc_ref, vc_ref), lambda: visit(ks_ref, vs_ref))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_sc[...]
        some = l > 0  # a row that selected nothing reads 0, and its weights exp(-inf - inf) are 0
        o_ref[0] = acc_sc[...] / jnp.where(some, l, 1.0)
        lse = jnp.where(some, m_sc[...] + jnp.log(jnp.where(some, l, 1.0)), jnp.inf)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _weights_kernel(live_ref, _m, _c, _s, q_ref, kc_ref, ks_ref, mask_ref, lse_ref, p_ref, *, n_cache, groups, heads, head_dim, scale,
                    dtype):
    j = pl.program_id(0)
    bq, tile = p_ref.shape

    def visit(k_ref):
        total = jnp.zeros((bq, tile), F32)
        for g in range(groups):
            s = _masked_scores(q_ref[g], k_ref[:, g * head_dim:(g + 1) * head_dim], mask_ref[...], heads, scale, dtype)
            total = total + jnp.sum(jnp.exp(s - lse_ref[g][:, :1]).reshape(heads, bq, tile), axis=0)
        p_ref[...] = total / (groups * heads)

    @pl.when(live_ref[j] == 0)
    def _():
        p_ref[...] = jnp.zeros((bq, tile), F32)

    _on_live_tile(live_ref, j, n_cache, lambda: visit(kc_ref), lambda: visit(ks_ref))


def _dq_kernel(live_ref, _m, _c, _s, q_ref, kc_ref, vc_ref, ks_ref, vs_ref, mask_ref, lse_ref, o_ref, do_ref, dq_ref, acc_sc, di_sc, *,
               n_cache, heads, scale, dtype):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)
        di_sc[...] = jnp.sum(do_ref[0] * o_ref[0], axis=1, keepdims=True)

    def visit(k_ref, v_ref):
        k = k_ref[...]
        p = jnp.exp(_masked_scores(q_ref[0], k, mask_ref[...], heads, scale, dtype) - lse_ref[0][:, :1])
        ds = p * (_dot(do_ref[0], v_ref[...], _NT, dtype) - di_sc[...])
        acc_sc[...] += _dot(ds, k, (((1,), (0,)), ((), ())), dtype)

    _on_live_tile(live_ref, j, n_cache, lambda: visit(kc_ref, vc_ref), lambda: visit(ks_ref, vs_ref))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = acc_sc[...] * scale


def _dkv_kernel(live_ref, q_ref, ks_ref, vs_ref, mask_ref, lse_ref, o_ref, do_ref, dk_ref, dv_ref, *, n_cache, heads, scale, dtype):
    live = live_ref[n_cache + pl.program_id(1)] != 0

    @pl.when(live)
    def _():
        q, do = q_ref[0], do_ref[0]
        p = jnp.exp(_masked_scores(q, ks_ref[...], mask_ref[...], heads, scale, dtype) - lse_ref[0][:, :1])
        dv_ref[...] = _dot(p, do, _TN, dtype)
        ds = p * (_dot(do, vs_ref[...], _NT, dtype) - jnp.sum(do * o_ref[0], axis=1, keepdims=True))
        dk_ref[...] = _dot(ds, q, _TN, dtype) * scale

    @pl.when(jnp.logical_not(live))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, dk_ref.dtype)
        dv_ref[...] = jnp.zeros(dv_ref.shape, dv_ref.dtype)


# -- the calls ------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def _shapes(q, kc, ks, mask):
    groups, rows, head_dim = q.shape
    bq = mask.shape[0]
    tile = key_tile(kc.shape[0], ks.shape[0])
    return groups, rows, head_dim, rows // bq, bq, tile, kc.shape[0] // tile, mask.shape[1] // tile


def _tile_specs(head_dim, tile, bq, grouped: bool):
    """Blocks of the cached and own keys/values and of the mask at step ``j`` (and group ``g``): the tiles the tables hold."""
    if grouped:
        cached = pl.BlockSpec((tile, head_dim), lambda g, j, live, m, c, s: (c[j], g))
        own = pl.BlockSpec((tile, head_dim), lambda g, j, live, m, c, s: (s[j], g))
        mask = pl.BlockSpec((bq, tile), lambda g, j, live, m, c, s: (0, m[j]))
    else:
        cached = pl.BlockSpec((tile, head_dim), lambda j, live, m, c, s: (c[j], 0))
        own = pl.BlockSpec((tile, head_dim), lambda j, live, m, c, s: (s[j], 0))
        mask = pl.BlockSpec((bq, tile), lambda j, live, m, c, s: (0, m[j]))
    return cached, own, mask


def _rows(rows, width):
    return pl.BlockSpec((1, rows, width), lambda g, j, *_: (g, 0, 0))


def _forward_call(q, kc, vc, ks, vs, mask, order, dtype):
    groups, rows, head_dim, heads, bq, tile, n_cache, n_tiles = _shapes(q, kc, ks, mask)
    cached, own, mask_spec = _tile_specs(head_dim, tile, bq, grouped=True)
    kernel = functools.partial(_forward_kernel, n_cache=n_cache, heads=heads, scale=head_dim ** -0.5, dtype=dtype)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(groups, n_tiles),
                in_specs=[_rows(rows, head_dim), cached, cached, own, own, mask_spec],
                out_specs=[_rows(rows, head_dim), _rows(rows, _LANES)],
                scratch_shapes=[pltpu.VMEM((rows, 1), F32), pltpu.VMEM((rows, 1), F32), pltpu.VMEM((rows, head_dim), F32)]),
            out_shape=[jax.ShapeDtypeStruct((groups, rows, head_dim), F32), jax.ShapeDtypeStruct((groups, rows, _LANES), F32)],
            compiler_params=_params("parallel", "arbitrary"), interpret=_interpret(), name="sparse_attention_fwd",
        )(*order, q, kc, vc, ks, vs, mask)


def _weights_call(q, kc, ks, mask, order, lse, dtype):
    groups, rows, head_dim, heads, bq, tile, n_cache, n_tiles = _shapes(q, kc, ks, mask)
    cached, own, mask_spec = _tile_specs(groups * head_dim, tile, bq, grouped=False)
    whole = lambda shape: pl.BlockSpec(shape, lambda j, *_: (0,) * len(shape))  # noqa: E731
    kernel = functools.partial(_weights_kernel, n_cache=n_cache, groups=groups, heads=heads, head_dim=head_dim, scale=head_dim ** -0.5,
                               dtype=dtype)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(n_tiles,),
                in_specs=[whole(q.shape), cached, own, mask_spec, whole(lse.shape)],
                out_specs=pl.BlockSpec((bq, tile), lambda j, *_: (0, j))),
            out_shape=jax.ShapeDtypeStruct((bq, n_tiles * tile), F32),
            compiler_params=_params("arbitrary"), interpret=_interpret(), name="sparse_attention_weights",
        )(*order, q, kc, ks, mask, lse)


def _dq_call(q, kc, vc, ks, vs, mask, order, lse, o, do, dtype):
    groups, rows, head_dim, heads, bq, tile, n_cache, n_tiles = _shapes(q, kc, ks, mask)
    cached, own, mask_spec = _tile_specs(head_dim, tile, bq, grouped=True)
    kernel = functools.partial(_dq_kernel, n_cache=n_cache, heads=heads, scale=head_dim ** -0.5, dtype=dtype)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(groups, n_tiles),
                in_specs=[_rows(rows, head_dim), cached, cached, own, own, mask_spec, _rows(rows, _LANES), _rows(rows, head_dim),
                          _rows(rows, head_dim)],
                out_specs=_rows(rows, head_dim),
                scratch_shapes=[pltpu.VMEM((rows, head_dim), F32), pltpu.VMEM((rows, 1), F32)]),
            out_shape=jax.ShapeDtypeStruct(q.shape, F32),
            compiler_params=_params("parallel", "arbitrary"), interpret=_interpret(), name="sparse_attention_dq",
        )(*order, q, kc, vc, ks, vs, mask, lse, o, do)


def _dkv_call(q, kc, ks, vs, mask, live, lse, o, do, dtype):
    groups, rows, head_dim, heads, bq, tile, n_cache, _ = _shapes(q, kc, ks, mask)
    n_own = ks.shape[0] // tile
    own = pl.BlockSpec((tile, head_dim), lambda g, j, live: (j, g))
    rows_of = lambda width: pl.BlockSpec((1, rows, width), lambda g, j, live: (g, 0, 0))  # noqa: E731
    kernel = functools.partial(_dkv_kernel, n_cache=n_cache, heads=heads, scale=head_dim ** -0.5, dtype=dtype)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(groups, n_own),
                in_specs=[rows_of(head_dim), own, own, pl.BlockSpec((bq, tile), lambda g, j, live: (0, n_cache + j)), rows_of(_LANES),
                          rows_of(head_dim), rows_of(head_dim)],
                out_specs=[own, own]),
            out_shape=[jax.ShapeDtypeStruct(ks.shape, F32)] * 2,
            compiler_params=_params("parallel", "parallel"), interpret=_interpret(), name="sparse_attention_dkv",
        )(live, q, ks, vs, mask, lse, o, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _attend(q, kc, vc, ks, vs, mask, order, dtype):
    return tuple(_forward_call(q, kc, vc, ks, vs, mask, order, dtype))


def _attend_fwd(q, kc, vc, ks, vs, mask, order, dtype):
    o, lse = _forward_call(q, kc, vc, ks, vs, mask, order, dtype)
    return (o, lse), (q, kc, vc, ks, vs, mask, order, o, lse)


def _attend_bwd(dtype, residuals, cotangents):
    q, kc, vc, ks, vs, mask, order, o, lse = residuals
    do, _ = cotangents  # the log-sum-exp is read under stop_gradient alone
    dq = _dq_call(q, kc, vc, ks, vs, mask, order, lse, o, do, dtype)
    dk, dv = _dkv_call(q, kc, ks, vs, mask, order[0], lse, o, do, dtype)
    # the cache is a constant snapshot, the mask and the tables are integers: no cotangent
    return dq.astype(q.dtype), None, None, dk.astype(ks.dtype), dv.astype(vs.dtype), None, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def selected_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array, k: jax.Array, v: jax.Array,
                       selected: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A block of queries over its selection.  ``q`` ``[bq, Hq, dh]``;
    ``cache_k``/``cache_v`` ``[L, Hkv dh]``, a constant; ``k``/``v`` ``[T, Hkv, dh]``;
    ``selected`` ``[bq, L + T]`` bool, every position the query attends (the
    cache's first).  Returns the heads' outputs ``[bq, Hq, dh]`` in ``q``'s type,
    the weights averaged over the heads ``[bq, L + T]`` float32 (no gradient),
    and the tile table ``[(L + T) / key_tile]`` int32: 1 on each tile computed."""
    bq, heads_q, head_dim = q.shape
    length, groups = cache_k.shape[0], k.shape[1]
    heads = heads_q // groups
    dtype = dot_dtype(q.dtype)
    with jax.named_scope(SCOPE):
        tile = key_tile(length, k.shape[0])
        live = tile_table(selected, tile)
        order = _fetch_order(live, length // tile)
        mask = selected.astype(jnp.int8)
        qg = q.reshape(bq, groups, heads, head_dim).transpose(1, 2, 0, 3).reshape(groups, heads * bq, head_dim)
        ks, vs = k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1)
        o, lse = _attend(qg, cache_k, cache_v, ks, vs, mask, order, dtype)
        sg = jax.lax.stop_gradient  # the indexer's loss reads the weights as a constant
        p = _weights_call(sg(qg), sg(cache_k), sg(ks), mask, order, sg(lse), dtype)
        o = o.reshape(groups, heads, bq, head_dim).transpose(2, 0, 1, 3).reshape(bq, heads_q, head_dim)
    return o.astype(q.dtype), p, live
