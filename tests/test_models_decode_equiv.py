"""Strong model-correctness test: teacher-forced forward logits must match
incremental prefill+decode logits for every architecture family (fp32)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models.registry import bundle_for

ARCHS = C.ARCHS + C.EDGE_MODELS


def _fp32(cfg):
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    if getattr(cfg, "moe", None) is not None:
        # Ample capacity: teacher-forced and incremental dispatch otherwise
        # differ by *which tokens overflow* (correct MoE semantics, but not
        # what this equivalence test probes).
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


# int8 KV cases: keys and values are rounded to absmax/127 per (token,
# head), which moves the smoke models' logits by up to ~6e-3 (std ~0.16),
# so those cases compare at 1.5e-2; a position written to the wrong slot
# or layer moves them by ~1e-1.
@pytest.mark.parametrize("name,kv_cache_dtype,tol", [
    *[pytest.param(a, "native", 2e-3, id=a) for a in ARCHS
      if a != "seamless_m4t_large_v2"],
    pytest.param("qwen2_1p5b", "int8", 1.5e-2, id="qwen2_1p5b-int8"),
    pytest.param("gemma2_27b", "int8", 1.5e-2, id="gemma2_27b-int8"),
])
def test_decode_matches_forward(name, kv_cache_dtype, tol):
    cfg = _fp32(C.get_smoke(name))
    if kv_cache_dtype != "native":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_cache_dtype)
    b = bundle_for(cfg)
    key = jax.random.PRNGKey(0)
    params = b.init_params(key)

    B, S_prompt, S_total = 2, 7, 12
    toks = jax.random.randint(key, (B, S_total), 1, cfg.vocab_size)
    kw = {}
    if getattr(cfg, "num_prefix_embeddings", 0):
        kw["prefix_embeddings"] = 0.02 * jax.random.normal(
            key, (B, cfg.num_prefix_embeddings, cfg.d_model))

    # teacher-forced logits for every position
    full_logits, _ = b.forward(params, toks, **kw)

    # incremental: prefill the prompt, then decode one token at a time
    prefix = kw.get("prefix_embeddings")
    plen = prefix.shape[1] if prefix is not None else 0
    cache = b.init_cache(B, S_total + plen + 4)
    logits, cache = b.prefill(params, toks[:, :S_prompt], cache, **kw)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full_logits[:, S_prompt - 1]),
        rtol=tol, atol=tol)

    for i in range(S_prompt, S_total):
        logits, cache = b.decode_step(params, toks[:, i], cache,
                                      jnp.asarray(i + plen, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, i]),
            rtol=tol, atol=tol, err_msg=f"{name} step {i}")


def test_encdec_decode_matches_forward():
    cfg = _fp32(C.get_smoke("seamless_m4t_large_v2"))
    b = bundle_for(cfg)
    key = jax.random.PRNGKey(0)
    params = b.init_params(key)
    B, T_src, S = 2, 8, 6
    speech = 0.02 * jax.random.normal(key, (B, T_src, cfg.d_model))
    toks = jax.random.randint(key, (B, S), 1, cfg.vocab_size)
    batch = {"speech_embeddings": speech, "tokens": toks}
    full_logits, _ = b.forward(params, batch)

    cache = b.init_cache(B, 16)
    logits, cache = b.prefill(params, batch, cache)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, 0]),
                               rtol=2e-3, atol=2e-3)
    for i in range(1, S):
        logits, cache = b.decode_step(params, toks[:, i], cache,
                                      jnp.asarray(i, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, i]),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"step {i}")


@pytest.mark.parametrize("name,layer_pattern", [
    pytest.param("gemma2_27b", None, id="gemma2_27b"),
    pytest.param("mixtral_8x22b", None, id="mixtral_8x22b"),
    # Two ring layers and one global per pattern unit: the carried local
    # stack is indexed past the unit's first layer.
    pytest.param("gemma2_27b", ("local", "local", "global"),
                 id="gemma2_27b-local-local-global"),
])
def test_ring_cache_sliding_window(name, layer_pattern):
    """Decode far past the window with a ring cache must equal the
    full-sequence forward (window masking identical)."""
    cfg = _fp32(C.get_smoke(name))
    if layer_pattern is not None:
        cfg = dataclasses.replace(cfg, layer_pattern=layer_pattern,
                                  n_layers=2 * len(layer_pattern))
    b = bundle_for(cfg)
    params = b.init_params(jax.random.PRNGKey(1))
    B = 1
    W = cfg.sliding_window
    S_total = W * 2 + 3     # far beyond the window
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S_total), 1,
                              cfg.vocab_size)
    full_logits, _ = b.forward(params, toks)

    cache = b.init_cache(B, S_total)   # local layers get ring length W
    logits, cache = b.prefill(params, toks[:, :W], cache)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, W - 1]),
                               rtol=3e-3, atol=3e-3)
    for i in range(W, S_total):
        logits, cache = b.decode_step(params, toks[:, i], cache,
                                      jnp.asarray(i, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, -1]),
                               rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("name,layer_pattern", [
    pytest.param("qwen2_1p5b", None, id="qwen2_1p5b"),
    pytest.param("gemma2_27b", None, id="gemma2_27b"),
    pytest.param("gemma2_27b", ("local", "local", "global"),
                 id="gemma2_27b-local-local-global"),
])
def test_layer_scan_gathers_no_weight_stack(name, layer_pattern):
    """Prefill and decode scan the stored layer stack as it lies: no
    `gather` takes a whole layer-weight stack ([n_layers, ...]) as its
    operand, which would copy every layer's weights on every call."""
    from repro.analysis.jaxpr_audit import iter_eqns

    cfg = C.get_smoke(name)
    if layer_pattern is not None:
        cfg = dataclasses.replace(cfg, layer_pattern=layer_pattern,
                                  n_layers=2 * len(layer_pattern))
    b = bundle_for(cfg)
    params = b.abstract_params()
    stacks = {(a.shape, a.dtype) for a in jax.tree.leaves(params["layers"])}
    B, S = 2, 32
    cache = jax.eval_shape(lambda: b.init_cache(B, S))
    toks = jax.ShapeDtypeStruct((B, 8), jnp.int32)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    programs = {
        "prefill": jax.make_jaxpr(b.prefill)(params, toks, cache),
        "decode_step": jax.make_jaxpr(b.decode_step)(params, tok, cache,
                                                     pos),
    }
    for program, jaxpr in programs.items():
        gathers = [e for e in iter_eqns(jaxpr)
                   if e.primitive.name == "gather"]
        assert gathers, f"{program}: the embedding lookup is a gather"
        copied = [(e.invars[0].aval.shape, e.invars[0].aval.dtype)
                  for e in gathers
                  if (e.invars[0].aval.shape,
                      e.invars[0].aval.dtype) in stacks]
        assert not copied, f"{program} gathers layer stacks {copied}"


@pytest.mark.parametrize("name", ["smollm_360m", "qwen2_1p5b",
                                  "gemma2_27b"])
def test_flash_decode_matches_naive(name):
    """The serving decode path with `attn_impl="flash"` (Pallas split-K
    decode attention for plain causal layers, masked fallback for
    softcap/sliding-window) must match the naive cached attention to
    1e-4 (fp32).  Covers GQA (qwen2), logit softcap + sliding window
    (gemma2), and the dense base case (smollm)."""
    cfg_n = _fp32(C.get_smoke(name))
    cfg_f = dataclasses.replace(cfg_n, attn_impl="flash")
    bn, bf = bundle_for(cfg_n), bundle_for(cfg_f)
    params = bn.init_params(jax.random.PRNGKey(0))

    B, S_prompt, steps = 2, 7, 5
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (B, S_prompt + steps), 1, cfg_n.vocab_size)
    cn = bn.init_cache(B, S_prompt + steps + 4)
    cf = bf.init_cache(B, S_prompt + steps + 4)
    ln, cn = bn.prefill(params, toks[:, :S_prompt], cn)
    lf, cf = bf.prefill(params, toks[:, :S_prompt], cf)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ln),
                               rtol=1e-4, atol=1e-4, err_msg="prefill")
    for i in range(S_prompt, S_prompt + steps):
        pos = jnp.asarray(i, jnp.int32)
        ln, cn = bn.decode_step(params, toks[:, i], cn, pos)
        lf, cf = bf.decode_step(params, toks[:, i], cf, pos)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(ln),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name} step {i}")


def test_train_step_reduces_loss():
    """A few optimizer steps on a fixed batch must reduce the loss for a
    representative arch of each family."""
    from repro.launch import steps as steps_mod
    from repro.training import optimizer as opt_mod
    from repro.training.optimizer import AdamWConfig

    for name in ("smollm_360m", "rwkv6_3b", "recurrentgemma_9b"):
        cfg = _fp32(C.get_smoke(name))
        b = bundle_for(cfg)
        params = b.init_params(jax.random.PRNGKey(0))
        opt_state = opt_mod.init(params)
        step = jax.jit(steps_mod.make_train_step(
            b, AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        first = None
        for _ in range(8):
            params, opt_state, metrics = step(params, opt_state, batch)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first - 0.05, name
