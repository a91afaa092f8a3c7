"""Shared model components: norms, rotary embeddings, GQA attention (with
sliding-window / logit-softcap / QK-norm / bias options), gated MLPs, MoE-free
dense blocks, embeddings and KV caches.

All functions are pure (params in, arrays out) and jit/scan/shard_map
friendly.  Parameters are plain nested dicts; initializers return the same
tree structure as the apply functions consume.  Dtype policy: params and
activations in `cfg.dtype` (default bf16), softmax/logsumexp in fp32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key: Array, in_dim: int, out_dim: int, dtype,
               scale: Optional[float] = None) -> Array:
    """Truncated-normal fan-in init (LLaMA-style)."""
    if scale is None:
        scale = 1.0 / math.sqrt(in_dim)
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, (in_dim, out_dim), jnp.float32)).astype(dtype)


def embed_init(key: Array, vocab: int, dim: int, dtype) -> Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype) -> Params:
    return {"scale": jnp.zeros((dim,), dtype)}


def rmsnorm(params: Params, x: Array, eps: float = 1e-6,
            unit_offset: bool = True) -> Array:
    """RMSNorm.  `unit_offset=True` stores scale-1 (gemma convention) which
    is also a better init for all archs; apply uses (1 + scale)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    scale = params["scale"].astype(jnp.float32)
    scale = 1.0 + scale if unit_offset else scale
    return (xf * scale).astype(dt)


def layernorm_init(dim: int, dtype) -> Params:
    return {"scale": jnp.zeros((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params: Params, x: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = xf * (1.0 + params["scale"].astype(jnp.float32)) \
        + params["bias"].astype(jnp.float32)
    return out.astype(dt)


def make_norm(kind: str):
    if kind == "rmsnorm":
        return rmsnorm_init, rmsnorm
    if kind == "layernorm":
        return layernorm_init, layernorm
    raise ValueError(f"unknown norm {kind!r}")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> Array:
    """Inverse frequencies, fp32 [head_dim // 2]."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                       # [D/2]
    ang = positions[..., :, None].astype(jnp.float32) * inv  # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]                 # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(max_len: int, dim: int) -> Array:
    """Classic sin/cos absolute position table [max_len, dim] (fp32)."""
    pos = jnp.arange(max_len, dtype=jnp.float32)[:, None]
    half = dim // 2
    div = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                  / half)
    ang = pos * div[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA; masks for causal / sliding-window / cross)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    use_bias: bool = False          # qkv + out bias (qwen2: qkv only)
    qkv_bias_only: bool = False     # qwen2: bias on qkv, not out
    logit_softcap: float = 0.0      # gemma2: 50.0
    query_scale: Optional[float] = None  # default 1/sqrt(head_dim)
    rope_theta: float = 10000.0
    use_rope: bool = True
    qk_norm: bool = False           # olmoe
    sliding_window: int = 0         # 0 = full attention
    attn_impl: str = "naive"        # "naive" | "flash" (models/flash.py)


def attn_init(key: Array, spec: AttnSpec, dtype) -> Params:
    ks = jax.random.split(key, 4)
    d, h, kvh, hd = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim
    p: Params = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kvh * hd, dtype),
        "wv": dense_init(ks[2], d, kvh * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if spec.use_bias or spec.qkv_bias_only:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype)
        if spec.use_bias and not spec.qkv_bias_only:
            p["bo"] = jnp.zeros((d,), dtype)
    if spec.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(params: Params, spec: AttnSpec, x: Array,
                 positions: Optional[Array]) -> Tuple[Array, Array, Array]:
    b, s, _ = x.shape
    h, kvh, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = jnp.einsum("bsd,df->bsf", x, params["wq"])
    k = jnp.einsum("bsd,df->bsf", x, params["wk"])
    v = jnp.einsum("bsd,df->bsf", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if spec.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if spec.use_rope and positions is not None:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    return q, k, v


def mha_attend(q: Array, k: Array, v: Array, mask: Optional[Array],
               spec: AttnSpec) -> Array:
    """q: [B,Sq,H,D], k/v: [B,Sk,KVH,D] -> [B,Sq,H*D].  fp32 softmax.

    Under a mesh context the einsums carry sharding constraints from
    distributed.autoshard (kv-head / group / query-seq plans) so GQA head
    counts that don't divide the TP axis don't replicate the quadratic
    work (see autoshard module doc)."""
    from repro.distributed import autoshard
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    plan = autoshard.attn_plan(kvh, groups, sq)
    scale = spec.query_scale if spec.query_scale is not None \
        else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, sq, kvh, groups, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    logits = autoshard.constrain_attn_logits(logits, plan)
    if spec.logit_softcap > 0.0:
        cap = spec.logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    out = autoshard.constrain_attn_ctx(
        out.reshape(b, sq, kvh, groups, hd), plan)
    return out.reshape(b, sq, h * hd)


def attn_out(params: Params, spec: AttnSpec, ctx: Array) -> Array:
    out = jnp.einsum("bsf,fd->bsd", ctx, params["wo"])
    if "bo" in params:
        out = out + params["bo"]
    return out


def causal_mask(sq: int, sk: int, q_offset: int = 0,
                window: int = 0) -> Array:
    """[1, Sq, Sk] bool; True = attend.  Query i (global pos q_offset+i) sees
    key j iff j <= pos and (window == 0 or pos - j < window)."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window > 0:
        m = m & (kpos > qpos - window)
    return m[None]


def self_attention(params: Params, spec: AttnSpec, x: Array,
                   positions: Array, mask: Optional[Array] = None,
                   window_arr: Optional[Array] = None) -> Array:
    """Full-sequence self-attention (training / prefill without cache).
    `window_arr`: dynamic per-layer sliding window for the flash path
    (0 = full attention); the naive path encodes it in `mask`."""
    q, k, v = _project_qkv(params, spec, x, positions)
    if spec.attn_impl == "flash":
        from repro.models import flash
        ctx = flash.flash_attention(q, k, v, spec, causal=True,
                                    window=window_arr)
    else:
        if mask is None:
            mask = causal_mask(x.shape[1], x.shape[1],
                               window=spec.sliding_window)
        ctx = mha_attend(q, k, v, mask, spec)
    return attn_out(params, spec, ctx)


# --- KV cache -------------------------------------------------------------
#
# Two layouts:
#   bf16 : {"k": [B,S,KVH,D], "v": ...}
#   int8 : {"k": int8 codes, "v": int8 codes, "k_scale": [B,S,KVH,1] f32,
#           "v_scale": ...}  — per-(token, head) absmax quantization,
#          halving decode HBM traffic for the cache reads (the qwen2 x
#          decode_32k hillclimb; EXPERIMENTS.md SSPerf).

def kv_cache_init(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  dtype) -> Params:
    """Per-layer cache; callers stack over layers for scan."""
    shape = (batch, max_len, n_kv_heads, head_dim)
    if dtype == jnp.int8:
        sshape = (batch, max_len, n_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_kv(x: Array) -> Tuple[Array, Array]:
    """[..., D] -> (int8 codes, f32 absmax scale over D)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    return jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8), scale


def _dequantize_kv(codes: Array, scale: Array, dtype) -> Array:
    return (codes.astype(jnp.float32) * scale).astype(dtype)


def _pad_valid_at(pad_mask: Array, kpos: Array) -> Array:
    """Gather per-sequence validity at global key positions.

    pad_mask: [B, P] bool over global positions (True = real token);
    positions >= P (decode-written slots) are always valid.  kpos: [S]
    int32 global positions (may be out of range for invalid ring slots —
    those are already masked by the caller).  Returns [B, S] bool."""
    p = pad_mask.shape[1]
    idx = jnp.clip(kpos, 0, p - 1)
    gathered = pad_mask[:, idx]
    in_range = (kpos >= 0) & (kpos < p)
    return jnp.where(in_range[None, :], gathered, True)


def stack_layer(stack: Params, layer: Array) -> Params:
    """Layer `layer` of a stacked cache ([L,B,S,...] leaves)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        stack)


def kv_cache_write(cache: Params, k: Array, v: Array, slot: Array,
                   layer: Optional[Array] = None) -> Params:
    """Write one decode position's keys and values (k/v [B,1,KVH,HD]) into
    the cache at position `slot` (int8 caches take codes and scales).

    `cache` is one layer ([B,S,...] leaves) or, with `layer`, a stack of
    layers ([L,B,S,...] leaves) written only at `layer`: nothing else of
    the stack changes, so a loop that carries the stack updates it in
    place instead of re-emitting a layer slab per step."""
    rows = {"k": k, "v": v}
    if "k_scale" in cache:
        k8, ks = _quantize_kv(k)
        v8, vs = _quantize_kv(v)
        rows = {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
    lead = () if layer is None else (layer,)
    out = {name: jax.lax.dynamic_update_slice(
               arr, rows[name].astype(arr.dtype)[(None,) * len(lead)],
               (*lead, 0, slot, 0, 0))
           for name, arr in cache.items()}
    if layer is not None:
        # Keep a carried stack heads-major (L, B, KVH, S, HD in memory), the
        # order attention reads.  Left to itself the TPU compiler puts
        # positions outside slots so that this one-row write fills whole
        # tiles, and every layer's attention then copies its slab out of
        # the stack first: qwen2-1.5b at 16 x 2048 on one TPU v5e spent
        # 2.6 ms per decode step on those copies and that attention,
        # against 1.3 ms reading the stack in this layout.
        for name in ("k", "v"):
            out[name] = with_layout_constraint(
                out[name], Layout(major_to_minor=(0, 1, 3, 2, 4)))
    return out


def cached_attention(params: Params, spec: AttnSpec, x: Array,
                     cache: Params, pos: Array, ring: bool = False,
                     pad_mask: Optional[Array] = None,
                     layer: Optional[Array] = None,
                     ) -> Tuple[Array, Params]:
    """Decode-step attention: x [B,1,D], cache k/v [B,S,KVH,HD], pos scalar
    (current token's global position).  `ring=True` => the cache is a ring
    buffer of size S == sliding_window (RoPE applied pre-insert; positions
    remain global so rotation stays consistent).  `pad_mask` ([B, P] bool,
    True = real) invalidates left-pad prompt slots per sequence; positions
    >= P are always valid.  With `layer`, `cache` is the stack of a layer
    group ([L,B,S,KVH,HD]) and this is its layer `layer`: only the token's
    own position is written (`kv_cache_write`).
    Returns (attn output [B,1,D], updated cache)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(params, spec, x, positions)
    s_cache = cache["k"].shape[-3]
    slot = jnp.asarray(pos % s_cache if ring else pos, jnp.int32)
    cache = kv_cache_write(cache, k_new, v_new, slot, layer)
    this = cache if layer is None else stack_layer(cache, layer)
    out = cached_attend(params, spec, q, this, pos, ring, pad_mask)
    return out, cache


def cached_attend(params: Params, spec: AttnSpec, q: Array, cache: Params,
                  pos: Array, ring: bool = False,
                  pad_mask: Optional[Array] = None) -> Array:
    """Decode-step attention of q [B,1,H,HD] over one layer's cache, which
    already holds the token at `pos` (see `cached_attention` for `ring`
    and `pad_mask`).  Returns the attention output [B,1,D].

    When `spec.attn_impl == "flash"` and the layer is a plain causal one
    (no ring buffer, no sliding window, no logit softcap) the attention
    itself runs through the Pallas split-K decode kernel
    (`kernels/decode_attention`): one pass over the cache per KV head with
    the valid [start, pos] window as scalar-prefetch operands, so the
    decode hot path reads only live cache blocks.  Unsupported layer
    shapes fall back to the naive masked softmax below."""
    b = q.shape[0]
    s_cache = cache["k"].shape[1]
    if "k_scale" in cache:
        k = _dequantize_kv(cache["k"], cache["k_scale"], q.dtype)
        v = _dequantize_kv(cache["v"], cache["v_scale"], q.dtype)
    else:
        k, v = cache["k"], cache["v"]

    if (spec.attn_impl == "flash" and not ring
            and spec.sliding_window == 0 and spec.logit_softcap == 0.0):
        from repro.kernels.decode_attention.ops import decode_attention
        kv_start = None
        if pad_mask is not None:
            # Left-pad invalid slots form a contiguous prefix (the engine
            # contract), so the valid window start is just the pad count.
            kv_start = jnp.sum(~pad_mask, axis=1).astype(jnp.int32)
        ctx = decode_attention(q[:, 0], k, v,
                               jnp.asarray(pos + 1, jnp.int32), kv_start,
                               scale=spec.query_scale)
        return attn_out(params, spec, ctx.reshape(b, 1, -1))

    if ring:
        # Ring buffer: entry at index i holds global position
        #   pos - ((pos - i) mod S); valid iff within the window & <= pos.
        idx = jnp.arange(s_cache)
        age = (pos - idx) % s_cache          # 0 = the token just written
        kpos = pos - age
        valid = kpos >= jnp.maximum(0, pos - s_cache + 1)
        mask = valid[None, None, :]
        if pad_mask is not None:
            mask = mask & _pad_valid_at(pad_mask, kpos)[:, None, :]
    else:
        idx = jnp.arange(s_cache)
        mask = (idx <= pos)
        if spec.sliding_window > 0:
            mask = mask & (idx > pos - spec.sliding_window)
        mask = mask[None, None, :]
        if pad_mask is not None:
            mask = mask & _pad_valid_at(pad_mask, idx)[:, None, :]

    ctx = mha_attend(q, k, v, jnp.broadcast_to(mask, (b, 1, s_cache)), spec)
    return attn_out(params, spec, ctx)


def prefill_into_cache(params: Params, spec: AttnSpec, x: Array,
                       cache: Params, ring: bool = False,
                       pad_mask: Optional[Array] = None,
                       pos_offset: Optional[Array] = None,
                       ) -> Tuple[Array, Params]:
    """Prefill: write S prompt tokens into the cache, return attn output.
    For ring caches only the last `window` tokens are retained.
    `pad_mask` ([B, S] bool, True = real token) masks left-pad slots out
    of the keys so ragged batches match their unpadded logits.

    `pos_offset` (traced scalar) shifts the whole prompt to global
    positions ``[pos_offset, pos_offset + S)``: RoPE rotates at the global
    positions (so later scalar-position decode steps stay consistent) and
    cache writes land at the offset.  This is the continuous-batching
    admission path — a request joining a running batch at global clock C
    prefills at ``pos_offset = C - S``.  For a ring cache with S < window
    the caller must pass a fresh (all-zero) cache row: the prompt is
    written at 0 and the buffer rolled so token i lands in ring slot
    ``(pos_offset + i) % window``.  ``None`` (the default) keeps the
    original position-0 semantics bit-for-bit."""
    b, s, _ = x.shape
    s_cache = cache["k"].shape[1]
    quantized = "k_scale" in cache
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if pos_offset is not None:
        positions = positions + pos_offset
    q, k, v = _project_qkv(params, spec, x, positions)
    off = jnp.asarray(0 if pos_offset is None else pos_offset, jnp.int32)

    def write(kk, vv, offset=0):
        if quantized:
            k8, ks = _quantize_kv(kk)
            v8, vs = _quantize_kv(vv)
            return {
                "k": jax.lax.dynamic_update_slice(cache["k"], k8,
                                                  (0, offset, 0, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], v8,
                                                  (0, offset, 0, 0)),
                "k_scale": jax.lax.dynamic_update_slice(
                    cache["k_scale"], ks, (0, offset, 0, 0)),
                "v_scale": jax.lax.dynamic_update_slice(
                    cache["v_scale"], vs, (0, offset, 0, 0)),
            }
        return {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], kk.astype(cache["k"].dtype), (0, offset, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], vv.astype(cache["v"].dtype), (0, offset, 0, 0)),
        }

    if ring and s >= s_cache:
        # Keep the last `window` tokens; token at global position g lives
        # in ring slot g % window, so the kept block starts at slot
        # (off + s - w) % w (off = 0 reproduces the original layout).
        w = s_cache
        start = (off + s - w) % w
        rolled_k = jnp.roll(k[:, s - w:], shift=start, axis=1)
        rolled_v = jnp.roll(v[:, s - w:], shift=start, axis=1)
        if quantized:
            k8, ks = _quantize_kv(rolled_k)
            v8, vs = _quantize_kv(rolled_v)
            new_cache = {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
        else:
            new_cache = {"k": rolled_k.astype(cache["k"].dtype),
                         "v": rolled_v.astype(cache["v"].dtype)}
    elif ring and pos_offset is not None:
        # Short prompt into a ring cache at an offset: write at 0 into the
        # (fresh, all-zero) row, then roll so token i sits in slot
        # (off + i) % w.  A dirty row would smear old entries around the
        # ring — the admission path always scatters a fresh row.
        base = write(k, v)
        new_cache = {name: jnp.roll(arr, off % s_cache, axis=1)
                     for name, arr in base.items()}
    else:
        new_cache = write(k, v, off)
    if spec.attn_impl == "flash":
        from repro.models import flash
        ctx = flash.flash_attention(q, k, v, spec, causal=True,
                                    kv_valid=pad_mask)
    else:
        mask = causal_mask(s, s, window=spec.sliding_window)
        if pad_mask is not None:
            mask = mask & pad_mask[:, None, :]
        ctx = mha_attend(q, k, v, jnp.broadcast_to(mask, (b, s, s)), spec)
    return attn_out(params, spec, ctx), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def gated_mlp_init(key: Array, d_model: int, d_ff: int, dtype,
                   use_bias: bool = False) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_gate": dense_init(ks[0], d_model, d_ff, dtype),
         "w_up": dense_init(ks[1], d_model, d_ff, dtype),
         "w_down": dense_init(ks[2], d_ff, d_model, dtype)}
    if use_bias:
        p["b_gate"] = jnp.zeros((d_ff,), dtype)
        p["b_up"] = jnp.zeros((d_ff,), dtype)
        p["b_down"] = jnp.zeros((d_model,), dtype)
    return p


def gated_mlp(params: Params, x: Array, act: str = "silu") -> Array:
    """SwiGLU / GeGLU family."""
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    if "b_gate" in params:
        g = g + params["b_gate"]
        u = u + params["b_up"]
    h = ACTS[act](g) * u
    out = jnp.einsum("bsf,fd->bsd", h, params["w_down"])
    if "b_down" in params:
        out = out + params["b_down"]
    return out


def mlp_init(key: Array, d_model: int, d_ff: int, dtype,
             use_bias: bool = True) -> Params:
    ks = jax.random.split(key, 2)
    p = {"w_in": dense_init(ks[0], d_model, d_ff, dtype),
         "w_out": dense_init(ks[1], d_ff, d_model, dtype)}
    if use_bias:
        p["b_in"] = jnp.zeros((d_ff,), dtype)
        p["b_out"] = jnp.zeros((d_model,), dtype)
    return p


def mlp(params: Params, x: Array, act: str = "gelu") -> Array:
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"])
    if "b_in" in params:
        h = h + params["b_in"]
    h = ACTS[act](h)
    out = jnp.einsum("bsf,fd->bsd", h, params["w_out"])
    if "b_out" in params:
        out = out + params["b_out"]
    return out


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed(params: Params, tokens: Array, scale_by_sqrt_dim: bool = False
          ) -> Array:
    x = jnp.take(params["embedding"], tokens, axis=0)
    if scale_by_sqrt_dim:
        x = x * jnp.asarray(math.sqrt(x.shape[-1]), x.dtype)
    return x


def unembed(params: Params, x: Array, tied: bool = True,
            final_softcap: float = 0.0) -> Array:
    table = params["embedding"] if tied else params["lm_head"]
    logits = jnp.einsum("bsd,vd->bsv", x, table).astype(jnp.float32)
    if final_softcap > 0.0:
        logits = final_softcap * jnp.tanh(logits / final_softcap)
    return logits


def cross_entropy_loss(logits: Array, labels: Array,
                       ignore_id: int = -100) -> Array:
    """Mean token NLL in fp32; `ignore_id` labels are masked out."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe = jnp.maximum(labels, 0)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = lse - gold
    w = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
