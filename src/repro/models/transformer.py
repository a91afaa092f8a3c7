"""Generic decoder-only transformer LM.

Covers the dense/GQA family (smollm, qwen2, starcoder2, phi-3-vision
backbone), logit-softcap + alternating local:global attention (gemma2), and
MoE FFNs (mixtral, olmoe) — all through one scan-over-layers body driven by
per-layer flag vectors, so the HLO stays one-block-sized regardless of depth.

Public API (used by launch/, serving/ and tests):
    init_params(cfg, key)            -> params pytree
    abstract_params(cfg)             -> ShapeDtypeStruct tree (no allocation)
    forward(cfg, params, tokens, prefix_embeddings=None)    -> logits
    loss_fn(cfg, params, batch)      -> scalar loss
    init_cache(cfg, batch, max_len)  -> cache pytree
    prefill(cfg, params, tokens, cache) -> (last_logits, cache)
    decode_step(cfg, params, token, cache, pos) -> (logits, cache)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import AttnSpec
from repro.models.moe import MoEConfig, moe_apply, moe_init

Array = jax.Array
Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    norm: str = "rmsnorm"
    mlp_kind: str = "gated"        # "gated" (SwiGLU/GeGLU) | "dense"
    act: str = "silu"
    use_bias: bool = False         # bias on mlp + attn out (starcoder2)
    qkv_bias: bool = False         # qwen2
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    use_rope: bool = True
    attn_softcap: float = 0.0      # gemma2: 50
    final_softcap: float = 0.0     # gemma2: 30
    query_scale: Optional[float] = None
    qk_norm: bool = False          # olmoe
    embed_scale: bool = False      # gemma: sqrt(d) input scaling
    post_norms: bool = False       # gemma2 sandwich norms
    sliding_window: int = 0
    layer_pattern: Tuple[str, ...] = ("global",)  # cycled over layers
    attn_impl: str = "naive"       # "naive" | "flash"
    kv_cache_dtype: str = "native"  # "native" (cfg.dtype) | "int8"
    moe: Optional[MoEConfig] = None
    num_prefix_embeddings: int = 0  # VLM/audio stub prefix slots
    dtype: Any = jnp.bfloat16
    max_seq_len: int = 131072
    # remat policy for train_step: "none" | "dots" | "full"
    remat: str = "none"

    @property
    def is_local(self) -> Tuple[bool, ...]:
        pat = self.layer_pattern
        return tuple(pat[i % len(pat)] == "local"
                     for i in range(self.n_layers))

    def attn_spec(self) -> AttnSpec:
        return AttnSpec(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            use_bias=self.use_bias, qkv_bias_only=self.qkv_bias,
            logit_softcap=self.attn_softcap, query_scale=self.query_scale,
            rope_theta=self.rope_theta, use_rope=self.use_rope,
            qk_norm=self.qk_norm, sliding_window=self.sliding_window,
            attn_impl=self.attn_impl)

    @property
    def n_params(self) -> int:
        """Total parameter count (embedding included once if tied)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        h, kvh, hd = self.n_heads, self.n_kv_heads, self.head_dim
        attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
        if self.moe is not None:
            m = self.moe
            ffn = d * m.n_experts + m.n_experts * (2 * d * m.d_ff
                                                   + m.d_ff * d)
        elif self.mlp_kind == "gated":
            ffn = 3 * d * f
        else:
            ffn = 2 * d * f
        per_layer = attn + ffn + 2 * d
        emb = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb

    @property
    def n_active_params(self) -> int:
        """Activated params per token (MoE: top_k experts only)."""
        if self.moe is None:
            return self.n_params
        d, v = self.d_model, self.vocab_size
        h, kvh, hd = self.n_heads, self.n_kv_heads, self.head_dim
        attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
        m = self.moe
        ffn = d * m.n_experts + m.top_k * (2 * d * m.d_ff + m.d_ff * d)
        per_layer = attn + ffn + 2 * d
        emb = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _layer_init(cfg: TransformerConfig, key: Array) -> Params:
    norm_init, _ = common.make_norm(cfg.norm)
    k_attn, k_mlp = jax.random.split(key)
    p: Params = {
        "norm_attn": norm_init(cfg.d_model, cfg.dtype),
        "norm_mlp": norm_init(cfg.d_model, cfg.dtype),
        "attn": common.attn_init(k_attn, cfg.attn_spec(), cfg.dtype),
    }
    if cfg.post_norms:
        p["post_norm_attn"] = norm_init(cfg.d_model, cfg.dtype)
        p["post_norm_mlp"] = norm_init(cfg.d_model, cfg.dtype)
    if cfg.moe is not None:
        p["moe"] = moe_init(k_mlp, cfg.d_model, cfg.moe, cfg.dtype)
    elif cfg.mlp_kind == "gated":
        p["mlp"] = common.gated_mlp_init(k_mlp, cfg.d_model, cfg.d_ff,
                                         cfg.dtype, cfg.use_bias)
    else:
        p["mlp"] = common.mlp_init(k_mlp, cfg.d_model, cfg.d_ff, cfg.dtype,
                                   cfg.use_bias)
    return p


def init_params(cfg: TransformerConfig, key: Array) -> Params:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    # Stacked layer params: vmap the single-layer init over keys.
    layers = jax.vmap(lambda k: _layer_init(cfg, k))(layer_keys)
    norm_init, _ = common.make_norm(cfg.norm)
    params: Params = {
        "embedding": common.embed_init(k_emb, cfg.vocab_size, cfg.d_model,
                                       cfg.dtype),
        "layers": layers,
        "final_norm": norm_init(cfg.d_model, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = common.embed_init(k_head, cfg.vocab_size,
                                              cfg.d_model, cfg.dtype)
    return params


def abstract_params(cfg: TransformerConfig) -> Params:
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# Forward (training / full-sequence)
# ---------------------------------------------------------------------------

def _block(cfg: TransformerConfig, lp: Params, x: Array, positions: Array,
           mask: Array, window_arr=None) -> Tuple[Array, Array]:
    """One transformer block; returns (x, aux_loss)."""
    _, norm = common.make_norm(cfg.norm)
    spec = cfg.attn_spec()

    h = norm(lp["norm_attn"], x)
    a = common.self_attention(lp["attn"], spec, h, positions, mask,
                              window_arr=window_arr)
    if cfg.post_norms:
        a = norm(lp["post_norm_attn"], a)
    x = x + a

    h = norm(lp["norm_mlp"], x)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        m, aux = moe_apply(lp["moe"], cfg.moe, h)
    elif cfg.mlp_kind == "gated":
        m = common.gated_mlp(lp["mlp"], h, cfg.act)
    else:
        m = common.mlp(lp["mlp"], h, cfg.act)
    if cfg.post_norms:
        m = norm(lp["post_norm_mlp"], m)
    return x + m, aux


def _layer_masks(cfg: TransformerConfig, sq: int, sk: int,
                 q_offset: int = 0) -> Tuple[Array, Array]:
    """(global_mask, local_mask) [1, sq, sk]; the scan body selects by
    per-layer flag."""
    g = common.causal_mask(sq, sk, q_offset=q_offset, window=0)
    l = common.causal_mask(sq, sk, q_offset=q_offset,
                           window=cfg.sliding_window or 0)
    return g, l


def forward(cfg: TransformerConfig, params: Params, tokens: Array,
            prefix_embeddings: Optional[Array] = None,
            ) -> Tuple[Array, Array]:
    """tokens: [B, S] int32.  prefix_embeddings: [B, P, D] modality stub
    (prepended; logits are returned for token positions only).
    Returns (logits [B, S, V], aux_loss)."""
    x = common.embed(params, tokens, cfg.embed_scale)
    p = 0
    if prefix_embeddings is not None:
        p = prefix_embeddings.shape[1]
        x = jnp.concatenate([prefix_embeddings.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    gmask, lmask = _layer_masks(cfg, s, s)
    is_local = jnp.asarray(cfg.is_local)

    _, norm = common.make_norm(cfg.norm)

    def body(carry, layer):
        xc, aux_acc = carry
        lp, local_flag = layer
        mask = jnp.where(local_flag, lmask, gmask)
        window_arr = jnp.where(local_flag, cfg.sliding_window, 0)
        fn = _block
        if cfg.remat in ("dots", "full"):
            policy = (jax.checkpoint_policies.checkpoint_dots
                      if cfg.remat == "dots" else
                      jax.checkpoint_policies.nothing_saveable)
            fn = jax.checkpoint(_block, policy=policy, static_argnums=(0,))
        xc, aux = fn(cfg, lp, xc, positions, mask, window_arr)
        return (xc, aux_acc + aux), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (params["layers"], is_local))
    x = norm(params["final_norm"], x)
    if p:
        x = x[:, p:]
    logits = common.unembed(params, x, cfg.tie_embeddings, cfg.final_softcap)
    return logits, aux


def loss_fn(cfg: TransformerConfig, params: Params, batch: Dict[str, Array],
            ) -> Array:
    logits, aux = forward(cfg, params, batch["tokens"],
                          batch.get("prefix_embeddings"))
    return common.cross_entropy_loss(logits, batch["labels"]) + aux


# ---------------------------------------------------------------------------
# KV-cache inference
# ---------------------------------------------------------------------------

def cache_len(cfg: TransformerConfig, max_len: int, layer_local: bool) -> int:
    """Ring-buffer length for local layers; full length for global."""
    if layer_local and cfg.sliding_window and max_len > cfg.sliding_window:
        return cfg.sliding_window
    return max_len


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> Params:
    """Stacked over layers.  When local and global layers need different
    cache lengths they are stored as two stacked groups."""
    locals_ = cfg.is_local
    n_local = sum(locals_)
    n_global = cfg.n_layers - n_local
    lw = cache_len(cfg, max_len, True)
    gw = cache_len(cfg, max_len, False)
    cdtype = jnp.int8 if cfg.kv_cache_dtype == "int8" else cfg.dtype

    def group(n, w):
        one = common.kv_cache_init(batch, w, cfg.n_kv_heads, cfg.head_dim,
                                   cdtype)
        return jax.tree.map(
            lambda a: jnp.zeros((n,) + a.shape, a.dtype), one)

    cache: Params = {}
    if n_global:
        cache["global"] = group(n_global, gw)
    if n_local:
        cache["local"] = group(n_local, lw)
    return cache


def _interleave(cfg: TransformerConfig, params: Params, x: Array,
                cache: Params, step_fn) -> Tuple[Array, Params]:
    """Run the layers in their original order, carrying the cache.

    `step_fn(lp, stack, i, x, is_local) -> (x, stack)` runs layer `i` of
    its group (`stack` is that group's whole cache, "local" or "global")
    and returns the stack with what the layer wrote.  The stacks ride in
    the scan's carry, never in its inputs or outputs, so a decode step
    writes one position per layer in place instead of emitting a new
    cache.

    The scan runs over pattern units (gemma2's (local, global) pair; one
    layer when every layer is of one kind), unrolling the unit, so
    activations flow through the layers in order while each group keeps
    its own cache length.  It reads the stored layer stack as it lies:
    splitting the leading axis into [n_units, len(unit)] is a bitcast, so
    no layer weight is copied.
    """
    locals_ = cfg.is_local
    if all(locals_) or not any(locals_):
        unit = locals_[:1]
    else:
        unit = tuple(p == "local" for p in cfg.layer_pattern)
    n_units = cfg.n_layers // len(unit)
    assert n_units * len(unit) == cfg.n_layers, (
        "mixed local/global patterns must tile n_layers exactly")
    per_unit = {"local": sum(unit), "global": len(unit) - sum(unit)}
    layers = jax.tree.map(
        lambda a: a.reshape(n_units, len(unit), *a.shape[1:]),
        params["layers"])

    def unit_body(carry, xs):
        x, cache = carry
        u, unit_layers = xs
        seen = {"global": 0, "local": 0}
        for k, is_loc in enumerate(unit):
            name = "local" if is_loc else "global"
            j = seen[name]
            seen[name] += 1
            lp = jax.tree.map(lambda a: a[k], unit_layers)
            x, stack = step_fn(lp, cache[name], u * per_unit[name] + j, x,
                               is_loc)
            cache = {**cache, name: stack}
        return (x, cache), None

    (x, cache), _ = jax.lax.scan(
        unit_body, (x, cache),
        (jnp.arange(n_units, dtype=jnp.int32), layers))
    return x, cache


def prefill(cfg: TransformerConfig, params: Params, tokens: Array,
            cache: Params, prefix_embeddings: Optional[Array] = None,
            attn_mask: Optional[Array] = None,
            pos_offset: Optional[Array] = None) -> Tuple[Array, Params]:
    """Run the prompt through the model, filling the cache.
    `attn_mask` ([B, S] bool, True = real token) masks left-padded slots
    out of every layer's keys (ragged batched prefill); prefix embedding
    slots are always valid.
    `pos_offset` (traced scalar, continuous-batching admission) shifts
    the prompt to global positions ``[pos_offset, pos_offset + S)`` in
    both RoPE and the cache writes — see
    `common.prefill_into_cache`.
    Returns (logits for the last position [B, V], cache)."""
    _, norm = common.make_norm(cfg.norm)
    spec = cfg.attn_spec()

    x = common.embed(params, tokens, cfg.embed_scale)
    if prefix_embeddings is not None:
        x = jnp.concatenate([prefix_embeddings.astype(x.dtype), x], axis=1)
        if attn_mask is not None:
            p = prefix_embeddings.shape[1]
            attn_mask = jnp.concatenate(
                [jnp.ones((x.shape[0], p), bool), attn_mask], axis=1)

    def step_fn(lp, stack, i, x, is_local: bool):
        lspec = dataclasses.replace(
            spec, sliding_window=cfg.sliding_window if is_local else 0)
        h = norm(lp["norm_attn"], x)
        c = common.stack_layer(stack, i)
        a, nc = common.prefill_into_cache(
            lp["attn"], lspec, h, c,
            ring=is_local and c["k"].shape[1] == cfg.sliding_window,
            pad_mask=attn_mask, pos_offset=pos_offset)
        stack = jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
            stack, nc)
        if cfg.post_norms:
            a = norm(lp["post_norm_attn"], a)
        x = x + a
        h = norm(lp["norm_mlp"], x)
        if cfg.moe is not None:
            m, _ = moe_apply(lp["moe"], cfg.moe, h)
        elif cfg.mlp_kind == "gated":
            m = common.gated_mlp(lp["mlp"], h, cfg.act)
        else:
            m = common.mlp(lp["mlp"], h, cfg.act)
        if cfg.post_norms:
            m = norm(lp["post_norm_mlp"], m)
        return x + m, stack

    x, new_cache = _interleave(cfg, params, x, cache, step_fn)
    x = norm(params["final_norm"], x[:, -1:])
    logits = common.unembed(params, x, cfg.tie_embeddings, cfg.final_softcap)
    return logits[:, 0], new_cache


def decode_step(cfg: TransformerConfig, params: Params, token: Array,
                cache: Params, pos: Array,
                attn_mask: Optional[Array] = None) -> Tuple[Array, Params]:
    """token: [B] int32; pos: scalar int32 (global position of `token`).
    `attn_mask` ([B, P] bool over global positions, True = real token)
    keeps left-padded prompt slots masked during decode; positions >= P
    are always valid.  Returns (logits [B, V], updated cache)."""
    _, norm = common.make_norm(cfg.norm)
    spec = cfg.attn_spec()
    x = common.embed(params, token[:, None], cfg.embed_scale)

    def step_fn(lp, stack, i, x, is_local: bool):
        lspec = dataclasses.replace(
            spec, sliding_window=cfg.sliding_window if is_local else 0)
        h = norm(lp["norm_attn"], x)
        ring = is_local and stack["k"].shape[2] == cfg.sliding_window
        a, stack = common.cached_attention(lp["attn"], lspec, h, stack, pos,
                                           ring=ring, pad_mask=attn_mask,
                                           layer=i)
        if cfg.post_norms:
            a = norm(lp["post_norm_attn"], a)
        x = x + a
        h = norm(lp["norm_mlp"], x)
        if cfg.moe is not None:
            m, _ = moe_apply(lp["moe"], cfg.moe, h)
        elif cfg.mlp_kind == "gated":
            m = common.gated_mlp(lp["mlp"], h, cfg.act)
        else:
            m = common.mlp(lp["mlp"], h, cfg.act)
        if cfg.post_norms:
            m = norm(lp["post_norm_mlp"], m)
        return x + m, stack

    x, new_cache = _interleave(cfg, params, x, cache, step_fn)
    x = norm(params["final_norm"], x)
    logits = common.unembed(params, x, cfg.tie_embeddings, cfg.final_softcap)
    return logits[:, 0], new_cache
