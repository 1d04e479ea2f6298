"""The gated delta rule (Yang et al., arXiv:2412.06464) in two forms.

Per head, with a state ``S`` of ``[dv, dk]``::

    S_t = a_t * S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T        o_t = S_t q_t

``a_t`` in (0, 1] is the decay (given as ``log_a``), ``b_t`` the write
strength; with ``b_t`` up to 2 the transition has eigenvalues down to -1
(``linear_allow_neg_eigval``).  Where ``resets[t]`` is 1 the state is zeroed
*before* step ``t``.

:func:`delta_rule_step` is the recurrence itself, one token: what a player
decoding token by token runs.  :func:`delta_rule_chunked` computes the same
sequence a chunk at a time: inside a chunk every product is a matrix
multiplication (the UT transform of the paper's section 3: the writes
``u_i = b_i (v_i - a_i S_{i-1} k_i)`` solve one unit-lower-triangular system
per chunk), and only the chunks are scanned.  Its backward pass is JAX's
through the scan.  Both work in float32 at ``highest`` matmul precision
whatever the model around them computes in: the state is a running sum over
the whole episode and is 0.4% of the model's FLOPs.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def delta_rule_step(
    state: jax.Array,  # [B, H, dv, dk]
    q: jax.Array,  # [B, H, dk]
    k: jax.Array,  # [B, H, dk]
    v: jax.Array,  # [B, H, dv]
    log_a: jax.Array,  # [B, H]
    b: jax.Array,  # [B, H]
    reset: jax.Array,  # [B], 1 zeroes the state before the step
) -> Tuple[jax.Array, jax.Array]:
    """One token: returns ``(state, o)`` with ``o`` of ``[B, H, dv]``."""
    q, k, v, log_a, b = (x.astype(F32) for x in (q, k, v, log_a, b))
    keep = 1.0 - reset.astype(F32)[:, None]
    state = state * (keep * jnp.exp(log_a))[..., None, None]
    written = jnp.einsum("bhvk,bhk->bhv", state, k, precision=_HI)
    u = b[..., None] * (v - written)
    state = state + u[..., :, None] * k[..., None, :]
    return state, jnp.einsum("bhvk,bhk->bhv", state, q, precision=_HI)


def delta_rule_chunked(
    state: jax.Array,  # [B, H, dv, dk]
    q: jax.Array,  # [B, T, H, dk]
    k: jax.Array,  # [B, T, H, dk]
    v: jax.Array,  # [B, T, H, dv]
    log_a: jax.Array,  # [B, T, H]
    b: jax.Array,  # [B, T, H]
    resets: jax.Array,  # [B, T]
    chunk: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """The whole sequence: returns ``(state after it, o)`` with ``o`` of
    ``[B, T, H, dv]``.  ``T`` must be a multiple of ``chunk`` or shorter than it."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"the sequence length ({T}) must be a multiple of the chunk ({C})")
    N = T // C
    q, k, v, log_a, b = (x.astype(F32) for x in (q, k, v, log_a, b))

    def chunks(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.reshape((B, N, C, H) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)
    log_a, b = chunks(log_a), chunks(b)  # [N, B, H, C]
    reset = jnp.broadcast_to(jnp.moveaxis(resets.astype(jnp.int32).reshape(B, N, C), 1, 0)[:, :, None, :], (N, B, H, C))
    seg = jnp.cumsum(reset, axis=-1)  # the episode a position belongs to, within its chunk
    # a reset position multiplies a zeroed state: its own decay has nothing to act on
    cum = jnp.cumsum(jnp.where(reset > 0, 0.0, log_a), axis=-1)
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    same = seg[..., :, None] == seg[..., None, :]
    upto = same & (j <= i)  # j writes before or at i, in i's episode
    decay = jnp.where(upto, jnp.exp(jnp.where(upto, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    from_start = jnp.where(seg == 0, jnp.exp(cum), 0.0)  # decay from the incoming state, nought after a reset

    kk = jnp.einsum("nbhik,nbhjk->nbhij", k, k, precision=_HI)
    lower = jnp.where(j < i, b[..., :, None] * decay * kk, 0.0)
    eye = jnp.eye(C, dtype=F32)
    # u = (I + lower)^-1 (b v - b g k S0^T): the part that needs no S0 is solved for every chunk at once
    rhs = jnp.concatenate([b[..., None] * v, (b * from_start)[..., None] * k], axis=-1)
    solved = solve_triangular(eye + lower, rhs, lower=True, unit_diagonal=True)
    u0, w = solved[..., :dv], solved[..., dv:]
    qk = decay * jnp.einsum("nbhik,nbhjk->nbhij", q, k, precision=_HI)
    q_start = from_start[..., None] * q
    to_end = decay[..., C - 1, :]  # [N, B, H, C]
    end_from_start = from_start[..., C - 1]

    def body(S, xs):
        u0, w, qk, q_start, k, to_end, end_from_start = xs
        u = u0 - jnp.einsum("bhck,bhvk->bhcv", w, S, precision=_HI)
        o = jnp.einsum("bhck,bhvk->bhcv", q_start, S, precision=_HI) + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HI)
        S = end_from_start[..., None, None] * S + jnp.einsum("bhcv,bhck->bhvk", u * to_end[..., None], k, precision=_HI)
        return S, o

    state, o = jax.lax.scan(body, state.astype(F32), (u0, w, qk, q_start, k, to_end, end_from_start))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, T, H, dv)  # [N,B,H,C,dv] -> [B,T,H,dv]
    return state, o
