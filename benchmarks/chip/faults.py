"""Faults a train step can have, planted under the harness to show that the
comparison catches them (``run.py --fault <name>``, never passed by the
driver; the tests plant the same ones at a tiny size).

Each takes the loop's compiled step and returns one that the loop calls in
its place, with the same shapes, so nothing compiles anew.
"""

from __future__ import annotations

from typing import Callable, Dict


def unchanged(step: Callable) -> Callable:
    """A step that does its work and returns its state as it got it."""
    import jax

    def broken(params, opt_states, moments_state, *rest):
        copy = lambda tree: jax.tree_util.tree_map(lambda x: x + 0, tree)  # noqa: E731  (the step donates its arguments)
        out = step(copy(params), copy(opt_states), copy(moments_state), *rest)
        return (params, opt_states, moments_state) + tuple(out[3:])

    return broken


def half_batch(step: Callable) -> Callable:
    """Half of the batch left out, the mean taken over the rest: the second
    half of the rows is overwritten with the first before the step sees them."""
    import jax.numpy as jnp

    def broken(params, opt_states, moments_state, batch, key, tau):
        def first_half_twice(v):
            half = v[:, : v.shape[1] // 2]
            return jnp.concatenate([half, half], axis=1)

        return step(params, opt_states, moments_state, {k: first_half_twice(v) for k, v in batch.items()}, key, tau)

    return broken


FAULTS: Dict[str, Callable[[Callable], Callable]] = {"unchanged": unchanged, "half_batch": half_batch}
