"""Pallas TPU RG-LRU kernel (Griffin gated linear recurrence).

h_t = a_t * h_{t-1} + b_t, elementwise per channel; a_t = exp(log_a_t).
Grid: (batch, n_width_blocks, n_chunks) with the chunk axis sequential; the
running hidden state (one vector per width block) persists in VMEM scratch.
Within a chunk the recurrence is evaluated in log-space prefix form:

    h_t = exp(cum_t) * (h0 + sum_{s<=t} b_s * exp(-cum_s))

with a mid-chunk shift keeping exp arguments bounded (|log_a| clipped at 8
per step, chunk <= 16 by default => exponent <= 128 ... so we clip the
*prefix* at 60 instead; contributions decayed by e^-60 are below fp32
resolution and are safely flushed to zero).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_CLIP = 60.0


def _rglru_kernel(loga_ref, b_ref, y_ref, h_final_ref, h_ref, *,
                  chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    la = loga_ref[0].astype(jnp.float32)       # [C, W]
    bb = b_ref[0].astype(jnp.float32)          # [C, W]
    h0 = h_ref[...]                            # [1, W]

    cum = jnp.cumsum(la, axis=0)               # <= 0, decreasing
    cum_c = jnp.maximum(cum, -_CLIP)
    # b_s * exp(-cum_s): exponent in [0, CLIP]
    scaled = bb * jnp.exp(-jnp.maximum(cum, -_CLIP))
    acc = jnp.cumsum(scaled, axis=0)
    h = jnp.exp(cum_c) * (h0 + acc)            # [C, W]

    y_ref[0] = h.astype(y_ref.dtype)
    h_ref[...] = h[-1:]

    @pl.when(ci == nc - 1)
    def _emit():
        h_final_ref[0] = h[-1:]


@functools.partial(jax.jit, static_argnames=("chunk", "block_w",
                                             "interpret"))
def rglru_scan(log_a: jax.Array, b: jax.Array, *, chunk: int = 16,
               block_w: int = 512, interpret: bool = False,
               ) -> Tuple[jax.Array, jax.Array]:
    """log_a, b: [B, S, W] fp32 (gates precomputed).  h0 = 0.
    Returns (h [B,S,W] fp32, h_last [B,W])."""
    bsz, s, w = log_a.shape
    assert s % chunk == 0
    block_w = min(block_w, w)
    assert w % block_w == 0
    nc = s // chunk
    nw = w // block_w

    def m(i, j, c):
        return (i, c, j)

    h, h_last = pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=(bsz, nw, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), m),
            pl.BlockSpec((1, chunk, block_w), m),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), m),
            pl.BlockSpec((1, 1, block_w), lambda i, j, c: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, w), jnp.float32),
            jax.ShapeDtypeStruct((bsz, 1, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b)
    return h, h_last[:, 0]
