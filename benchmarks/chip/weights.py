"""Weights from the seed: the benchmark's own initializer.

The program under test and the plain reference both take their weights
from here, never from each other. A leaf is a function of the seed and its
path alone (its key folds in both), so the same seed gives the same
weights whatever tree holds them and however they are laid out on the
chips. `generator` builds the whole tree in one traced call, made on the
device in the parameters' own dtype.

A spec maps a leaf's path ("blocks/p0_attn/attn/wq") to `(shape, init)`,
where `init` is "ones" or the standard deviation of a normal draw.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> tuple:
    """The seed as two uint32 words: seeds may exceed 32 bits."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def leaf(lo, hi, path: str, shape: tuple, init, dtype) -> jax.Array:
    if init == "ones":
        return jnp.ones(shape, dtype)
    key = jax.random.PRNGKey(0)
    for word in (lo, hi, np.uint32(zlib.crc32(path.encode()))):
        key = jax.random.fold_in(key, word)
    return (jax.random.normal(key, shape, jnp.float32) * init).astype(dtype)


def generator(specs: dict, dtype):
    """(lo, hi) -> {path: array}; jit it with the shardings wanted."""
    def make(lo, hi):
        return {path: leaf(lo, hi, path, shape, init, dtype)
                for path, (shape, init) in specs.items()}
    return make
