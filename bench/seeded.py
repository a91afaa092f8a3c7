"""Seeded random weights, made on the device: what every family shares.

A family's ``weights.shapes(m)`` gives its layout as a tree of
``(shape, std)`` leaves; `make` draws every leaf in the dtype it is served
in (bf16), in one jitted call from the run's seed.  Leaves are drawn
uniformly with the given standard deviation (a uniform draw keeps each
leaf one fused pass of random bits), one key per leaf in the tree's
flattened order.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from traffic import rng_for


def jax_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(rng_for(seed, 0).integers(0, 2 ** 31)))


def _uniform(key, shape, std, dtype):
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def make(spec, seed: int):
    """Every leaf of ``spec`` for ``seed``, on the default device, in one
    jitted call."""
    leaves, tree = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            tree, [_uniform(k, shape, std, jnp.bfloat16)
                   for k, (shape, std) in zip(keys, leaves)])

    return build(jax_key(seed))
