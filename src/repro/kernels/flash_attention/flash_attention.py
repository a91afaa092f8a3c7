"""Pallas TPU flash attention (fwd): blocked online-softmax with explicit
VMEM tiling.

Grid: (batch * q_heads, n_q_blocks, n_kv_blocks) with
dimension_semantics ("parallel", "parallel", "arbitrary") — the innermost
KV axis is sequential so the fp32 accumulator / running max / running sum
live in VMEM scratch across KV steps and the output block is written once
on the last step.

GQA is handled in the index maps (query head i reads KV head i // group).
Causal and sliding-window masking skip fully-dead KV blocks via pl.when
(the compute is predicated out, not just masked).

Block sizes default to (128, 512): q-block x kv-block tiles keep the
working set (q_blk*hd + 2*kv_blk*hd + q_blk*kv_blk floats) well under the
~16 MiB VMEM budget for hd <= 256 while keeping the MXU contraction dims
at >=128.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 512
_NEG = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, window: int, softcap: float,
                block_q: int, block_kv: int, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_kv

    # Block-level liveness: any (q, k) pair in range?
    live = jnp.asarray(True)
    if causal:
        live = live & (k_start <= q_start + block_q - 1)
    if window > 0:
        live = live & (k_start + block_kv - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
        k = k_ref[0].astype(jnp.float32)                  # [bk, d]
        v = v_ref[0].astype(jnp.float32)                  # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 1)
        mask = kpos < seq_len
        if causal:
            mask = mask & (kpos <= qpos)
        if window > 0:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, _NEG)

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


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "window", "softcap",
                              "block_q", "block_kv", "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        scale: Optional[float] = None, causal: bool = True,
                        window: int = 0, softcap: float = 0.0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_kv: int = DEFAULT_BLOCK_KV,
                        interpret: bool = False) -> jax.Array:
    """q: [B, Sq, H, D]; k/v: [B, Sk, KVH, D] -> [B, Sq, H, D]."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_kv)

    # [B, H, Sq, D] / [B, KVH, Sk, D] layouts for clean 2-D tiles.
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)

    def q_map(i, j, kk):
        return (i, j, 0)

    def kv_map(i, j, kk):
        return ((i // h) * kvh + (i % h) // g, kk, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, seq_len=sk)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
