"""The benchmark's weights and their digests.

The weights are the benchmark's input, made on the device from the seed in
one jitted call, in the dtype each leaf is served in: matrices
``normal / sqrt(fan_in)``, embedding rows ``normal x 0.02``, norm scales
``1 + normal x 0.1``.  The program receives them as data; the reference
reads the same values.  :func:`digest` is a position-sensitive 32-bit hash
of every leaf's bits, for comparing restored weights without a host copy.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def leaf_path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _leaf(key, path: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        x = 1.0 + 0.1 * z
    elif name == "table":
        x = 0.02 * z
    else:
        x = z / math.sqrt(shape[-2])
    return x.astype(dtype)


def make(template, seed: int):
    """Weights with ``template``'s tree, shapes and dtypes, from ``seed``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    specs = tuple((leaf_path(p), tuple(x.shape), jnp.dtype(x.dtype).name)
                  for p, x in paths)
    leaves = _make(specs, seed_key(seed))
    return jax.tree.unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnums=0)
def _make(specs, key):
    return [_leaf(jax.random.fold_in(key, i), path, shape, dtype)
            for i, (path, shape, dtype) in enumerate(specs)]


def _mix(words):
    h = words * jnp.uint32(0x9E3779B1)
    h = h ^ (h >> 15)
    return h * jnp.uint32(0x2C1B3C6D)


def _leaf_digest(x):
    bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    words = jax.lax.bitcast_convert_type(x, bits).reshape(-1).astype(jnp.uint32)
    pos = jnp.arange(words.size, dtype=jnp.uint32)
    return jnp.sum(_mix(words ^ _mix(pos + jnp.uint32(1))), dtype=jnp.uint32)


@jax.jit
def _digest(leaves):
    return jnp.stack([_leaf_digest(x) for x in leaves])


def digest(tree) -> np.ndarray:
    """uint32 per leaf, in leaf order."""
    return np.asarray(_digest(jax.tree.leaves(tree)))
