"""Pallas TPU grouped expert GEMM: y[e] = x[e] @ w[e] for E experts with a
fixed per-expert capacity (the dispatch buffer layout of models/moe.py).

Grid: (E, n_cap_blocks, n_out_blocks, n_k_blocks) — k innermost/sequential
with an fp32 VMEM accumulator, so each (cap x out) tile is revisited across
k blocks and written once.  MXU-aligned tile defaults (128, 128, 512).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_gemm_kernel(x_ref, w_ref, o_ref, acc_ref):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "block_k", "interpret"))
def moe_gemm(x: jax.Array, w: jax.Array, *, block_c: int = 128,
             block_f: int = 128, block_k: int = 512,
             interpret: bool = False) -> jax.Array:
    """x: [E, C, D]; w: [E, D, F] -> [E, C, F]."""
    e, c, d = x.shape
    f = w.shape[2]
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_k = min(block_k, d)
    grid = (e, pl.cdiv(c, block_c), pl.cdiv(f, block_f),
            pl.cdiv(d, block_k))

    return pl.pallas_call(
        _moe_gemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_k),
                         lambda e_, i, j, k: (e_, i, k)),
            pl.BlockSpec((1, block_k, block_f),
                         lambda e_, i, j, k: (e_, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e_, i, j, k: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
