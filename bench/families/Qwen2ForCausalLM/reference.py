"""Plain float32 reference of the Qwen2 family, and its fp8 control.

Family code (`family`): `logits` of one sequence.  What every family
shares -- the sample of served requests, the gap of each served token
below the reference's best, and the limit -- is `check`'s.

Written from the published description (Qwen2 technical report,
arXiv:2407.10671; Hugging Face ``Qwen2ForCausalLM``): token embedding; per
layer RMSNorm, attention with biased Q/K/V projections, rotary positions
(rotate-half, ``rope_theta``), grouped key/value heads and a causal
softmax, residual add, RMSNorm, SwiGLU MLP, residual add; final RMSNorm and
the output head tied to the embedding.  It imports nothing of the program
under test and reads only the configuration's ``model`` section and the
weights that this family's `weights.make` built (layout documented there).

Every matrix product runs at ``Precision.HIGHEST`` in float32.  The control
(``control=True``) is the same network computed in fp8 (e4m3): both
operands of every matrix product are rounded to e4m3 with one scale per
tensor, as an fp8 serving path would, and the products accumulate in
float32.

One sequence per call; the layers run as a scan that upcasts one layer's
weights at a time, so the reference fits beside the served bf16 weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .counts import dims

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x):
    """Round to e4m3 with one scale per tensor; back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(spec, a, b, control):
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain.astype(jnp.float32))


def _rope(x, theta):
    """x [S, heads, hd] at positions 0 .. S-1, rotate-half convention."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(m: dict, params, tokens, control: bool = False):
    """Logits [S, V] (float32) of one sequence ``tokens`` [S]."""
    s = dims(m)
    H, KV, hd = s["H"], s["KV"], s["hd"]
    eps = m["rms_norm_eps"]
    theta = m["rope_theta"]
    n = tokens.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    causal = jnp.tril(jnp.ones((n, n), bool))

    def layer(x, lp):
        a, mlp = lp["attn"], lp["mlp"]
        h = _rms(x, lp["norm_attn"]["scale"], eps)
        q = _mm("sd,df->sf", h, f32(a["wq"]), control) + f32(a["bq"])
        k = _mm("sd,df->sf", h, f32(a["wk"]), control) + f32(a["bk"])
        v = _mm("sd,df->sf", h, f32(a["wv"]), control) + f32(a["bv"])
        q = _rope(q.reshape(n, H, hd), theta).reshape(n, KV, H // KV, hd)
        k = _rope(k.reshape(n, KV, hd), theta)
        v = v.reshape(n, KV, hd)
        sc = _mm("qkgd,skd->kgqs", q, k, control) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        ctx = _mm("kgqs,skd->qkgd", p, v, control).reshape(n, H * hd)
        x = x + _mm("sf,fd->sd", ctx, f32(a["wo"]), control)
        h = _rms(x, lp["norm_mlp"]["scale"], eps)
        g = _mm("sd,df->sf", h, f32(mlp["w_gate"]), control)
        u = _mm("sd,df->sf", h, f32(mlp["w_up"]), control)
        x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, f32(mlp["w_down"]),
                    control)
        return x, None

    x = f32(params["embedding"][tokens])
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _mm("sd,vd->sv", x, f32(params["embedding"]), control)

