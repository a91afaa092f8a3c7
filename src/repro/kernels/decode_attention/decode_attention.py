"""Pallas TPU decode attention (FlashDecoding-style split-K).

One new token per sequence attends to a long KV cache.  Grid:
(batch * kv_heads, n_kv_blocks), sequential on the KV axis; the per-(kv
head) group of G=H/KVH query heads is processed as one (G, D) tile so GQA
costs one pass over the cache regardless of G.

The valid cache window arrives as two scalar-prefetch operands — a
per-sequence end (`kv_len`, exclusive) and start (`kv_start`, inclusive;
left-padded prompts have a contiguous invalid prefix) — and blocks
entirely outside [start, end) are skipped (pl.when), which is what makes
short-context decodes cheap even with a max-length cache.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_KV = 512
_NEG = -1e30


def _decode_kernel(len_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, block_kv: int, kv_heads: int):
    i = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    bi = i // kv_heads
    kv_len = len_ref[bi]
    kv_start = start_ref[bi]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = ki * block_kv

    @pl.when((k_start < kv_len) & (k_start + block_kv > kv_start))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [G, D]
        k = k_ref[0].astype(jnp.float32)                    # [bk, D]
        v = v_ref[0].astype(jnp.float32)                    # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where((kpos >= kv_start) & (kpos < kv_len), s, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_kv",
                                             "interpret"))
def decode_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len: jax.Array,
                         kv_start: Optional[jax.Array] = None, *,
                         scale: Optional[float] = None,
                         block_kv: int = DEFAULT_BLOCK_KV,
                         interpret: bool = False) -> jax.Array:
    """q: [B, H, D] (one token); k/v: [B, S, KVH, D]; kv_len: int32 scalar
    or [B] (valid cache entries, exclusive end); kv_start: optional int32
    scalar or [B] (first valid entry — left-padded prompts).
    Returns [B, H, D]."""
    b, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block_kv = min(block_kv, s)
    nk = pl.cdiv(s, block_kv)

    qt = q.reshape(b, kvh, g, d).reshape(b * kvh, g, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    if kv_start is None:
        kv_start = jnp.zeros((), jnp.int32)
    starts = jnp.broadcast_to(jnp.asarray(kv_start, jnp.int32), (b,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * kvh, nk),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda i, kk, lens, starts: (i, 0, 0)),
            pl.BlockSpec((1, block_kv, d),
                         lambda i, kk, lens, starts: (i, kk, 0)),
            pl.BlockSpec((1, block_kv, d),
                         lambda i, kk, lens, starts: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d),
                               lambda i, kk, lens, starts: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_kv=block_kv,
                          kv_heads=kvh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, starts, qt, kt, vt)
    return out.reshape(b, kvh, g, d).reshape(b, h, d)
