"""Seeded random weights of the Qwen2 family (a dense GQA decoder).

Family code (`family`): the layout below and its standard deviations.
The draw itself is shared (`seeded.make`: bf16, on the device, one jitted
call from the run's seed); the same arrays go to the system under test and
to the reference.  Layout (layers stacked on axis 0):

    embedding [V, D]                     (also the tied output head)
    layers.norm_attn.scale, norm_mlp.scale [L, D]   RMSNorm gain - 1
    layers.attn.wq [L, D, H*hd]  wk, wv [L, D, KV*hd]  wo [L, H*hd, D]
    layers.attn.bq [L, H*hd]     bk, bv [L, KV*hd]
    layers.mlp.w_gate, w_up [L, D, F]   w_down [L, F, D]
    final_norm.scale [D]

Norm gains are stored as offsets from 1 (gain = 1 + scale).  Matrices have
standard deviation 1/sqrt(fan-in), the embedding 0.02, biases and gain
offsets 0.1.
"""

from __future__ import annotations

import math

import seeded

from .counts import dims

BIAS_STD = 0.1
GAIN_STD = 0.1
EMBED_STD = 0.02


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
    return seeded.make(shapes(m), seed)
