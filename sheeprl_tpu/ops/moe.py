"""A routed expert layer as one chip of an expert-parallel group runs it.

:func:`route` picks ``top`` of *all* the model's experts a token (softmax over
the router's logits in float32, the ``top`` largest, renormalised to sum to
one under ``norm_topk_prob``) and returns the gates of the experts this chip
holds, nought where a pick fell elsewhere.  :func:`held_experts` is the
products grouped by expert, ``sum_e g_e (silu(x W1_e) * x W3_e) W2_e`` over
the experts held: the partial sum that the group's all-reduce would complete.

The products run over every held expert for every token, the unpicked
weighted by a gate of nought: exact, static in shape, and ``experts_held /
(top experts_held / experts_total)`` times the FLOPs of the picks alone (16x
at 16 of 128 held, 8 a token).  A product over sorted, uneven groups is the
optimisation this leaves open (PERF.md section 7).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def route(logits: jax.Array, top: int, normalise: bool, first_held: int, held: int) -> Tuple[jax.Array, jax.Array]:
    """``logits`` ``[N, experts_total]`` float32 -> the gates of the held
    experts ``[N, held]`` and how many of the ``N top`` picks fell on them."""
    picked, idx = jax.lax.top_k(jax.nn.softmax(logits.astype(F32), axis=-1), top)
    if normalise:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    local = idx - first_held
    gates = jnp.sum(jax.nn.one_hot(local, held, dtype=F32) * picked[..., None], axis=-2)  # a pick elsewhere is a row of nought
    return gates, jnp.sum(((local >= 0) & (local < held)).astype(jnp.int32))


def held_experts(x: jax.Array, gates: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    """``x`` ``[N, D]``, ``gates`` ``[N, E]``, ``w1``/``w3`` ``[E, D, F]``, ``w2`` ``[E, F, D]`` -> ``[N, D]``."""
    up = jnp.einsum("nd,edf->enf", x, w1.astype(x.dtype))
    hidden = up * jax.nn.sigmoid(up) * jnp.einsum("nd,edf->enf", x, w3.astype(x.dtype))
    hidden = hidden * gates.T.astype(hidden.dtype)[..., None]
    return jnp.einsum("enf,efd->nd", hidden, w2.astype(x.dtype))
