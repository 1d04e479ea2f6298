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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
