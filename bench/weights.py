"""Seeded random weights of a dense GQA decoder, made on the device.

The benchmark makes the weights, in the dtype they are served in (bf16),
in one jitted call from the run's seed, and hands the same arrays to the
system under test and to the reference.  Layout (layers stacked on axis 0):

    embedding [V, D]                     (also the tied output head)
    layers.norm_attn.scale, norm_mlp.scale [L, D]   RMSNorm gain - 1
    layers.attn.wq [L, D, H*hd]  wk, wv [L, D, KV*hd]  wo [L, H*hd, D]
    layers.attn.bq [L, H*hd]     bk, bv [L, KV*hd]
    layers.mlp.w_gate, w_up [L, D, F]   w_down [L, F, D]
    final_norm.scale [D]

Norm gains are stored as offsets from 1 (gain = 1 + scale).  Matrices have
standard deviation 1/sqrt(fan-in), the embedding 0.02, biases and gain
offsets 0.1, all drawn uniformly (a uniform draw keeps each leaf one fused
pass of random bits).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from counts import dims
from traffic import rng_for

BIAS_STD = 0.1
GAIN_STD = 0.1
EMBED_STD = 0.02


def jax_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(rng_for(seed, 0).integers(0, 2 ** 31)))


def _uniform(key, shape, std, dtype):
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def shapes(m: dict) -> dict:
    """Leaf shapes and standard deviations, in the layout above."""
    s = dims(m)
    L, D, H, KV, hd, F, V = (s[k] for k in ("L", "D", "H", "KV", "hd", "F",
                                              "V"))
    mat = lambda i, o: ((L, i, o), 1.0 / math.sqrt(i))  # noqa: E731
    return {
        "embedding": ((V, D), EMBED_STD),
        "layers": {
            "norm_attn": {"scale": ((L, D), GAIN_STD)},
            "norm_mlp": {"scale": ((L, D), GAIN_STD)},
            "attn": {"wq": mat(D, H * hd), "wk": mat(D, KV * hd),
                     "wv": mat(D, KV * hd), "wo": mat(H * hd, D),
                     "bq": ((L, H * hd), BIAS_STD),
                     "bk": ((L, KV * hd), BIAS_STD),
                     "bv": ((L, KV * hd), BIAS_STD)},
            "mlp": {"w_gate": mat(D, F), "w_up": mat(D, F),
                    "w_down": mat(F, D)},
        },
        "final_norm": {"scale": ((D,), GAIN_STD)},
    }


def make(m: dict, seed: int):
    """All weights of config section ``m`` for ``seed``, on the default
    device, in one jitted call."""
    spec = shapes(m)
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
