"""The weights of a run, made by the benchmark from ``--seed``.

One jitted call builds every leaf on the device in the type the program keeps
it in.  The program's own initialisation gives only the tree's names and
shapes.  Kernels are normal with variance ``1 / fan_in`` (none is zero, so
no head starts with a gradient of nought behind it, as the program's
zero-initialised reward and critic heads would); norm scales are one; biases
and the learned initial recurrent state are zero.  The target critic starts as
a copy of the critic, as in the program.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp


def _leaf(key: jax.Array, name: str, shape, dtype) -> jax.Array:
    if name == "kernel":
        fan_in = math.prod(shape[:-1])
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)
    if name == "scale":
        return jnp.ones(shape, dtype)
    return jnp.zeros(shape, dtype)


def make_weights(template: Any, seed: int) -> Any:
    """A tree like ``template`` (arrays or shapes), filled from ``seed``."""
    modules = {k: v for k, v in template.items() if k != "target_critic"}
    paths, treedef = jax.tree_util.tree_flatten_with_path(modules)
    specs = [(str(getattr(path[-1], "key", path[-1])), tuple(leaf.shape), leaf.dtype) for path, leaf in paths]

    @jax.jit
    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, i), *spec) for i, spec in enumerate(specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    params = build(jax.random.PRNGKey(int(seed) ^ 0x5EED))
    if "target_critic" in template:
        params["target_critic"] = jax.tree_util.tree_map(jnp.copy, params["critic"])
    return params
