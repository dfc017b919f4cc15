"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program and not the reference: the
same function fills the program's parameter tree (whose names and shapes come
from ``jax.eval_shape`` of the program's own init) and is called again, after
the program's state is freed, for the reference. The distributions are the
program's initialisers' (normal(0.02) tables, Xavier-uniform kernels, zero
biases, unit scales), so the numerics are those of a fresh run of the preset.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp


def _paths(tree) -> Iterable[Tuple[str, Any]]:
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path), leaf


def flat(tree) -> Dict[str, Any]:
    """``{"layer_0/mlp/mlp_in/kernel": leaf, ...}``."""
    return dict(_paths(tree))


def _leaf(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    if last == "bias":
        return jnp.zeros(shape, dtype)
    if last == "scale":
        return jnp.ones(shape, dtype)
    if last == "embedding" or last.endswith("position"):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if last == "kernel" and len(shape) == 2:
        bound = math.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    raise ValueError(
        f"no seeded initialiser for parameter {name!r} of shape {shape}: "
        f"benchmark/harness/weights.py knows bias, scale, embedding, "
        f"*position and 2-D kernel")


def seed_key(seed: int):
    """A key from the whole of ``--seed``: two 32-bit words, so that a seed
    over 2**31 keeps all its bits."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make(shapes, key):
    """Fill the tree of ``jax.ShapeDtypeStruct`` leaves ``shapes`` from
    ``key``. ``key`` is an argument of the jitted caller, never a constant of
    it, so that one compiled program serves every seed."""
    treedef = jax.tree_util.tree_structure(shapes)
    out = [_leaf(jax.random.fold_in(key, i), name, tuple(s.shape), s.dtype)
           for i, (name, s) in enumerate(_paths(shapes))]
    return jax.tree_util.tree_unflatten(treedef, out)
