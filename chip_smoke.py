"""Serve qwen2-1.5b at its published widths on one TPU chip, end to end.

Drives the engine's main path once through the entry points a user
calls, on a model with seeded random weights, and checks what comes out:

  (a) device check: anything but a TPU is refused (no CPU fallback);
  (b) build the published model through the ``engine/<arch>`` registry
      entry, as ``serve.py --mode engine --preset published`` does;
  (c) serve ragged requests through ``generate_continuous`` and one
      static ``generate`` batch: token budgets, vocabulary range, finite
      logits, no compile inside the timed runs;
  (d) compare prefill-then-decode logits with an f32 ``forward``
      reference over the same tokens, plus a broken-offset control that
      must fail the same tolerance;
  (e) run the Pallas split-K decode kernel at the model's widths,
      compiled for the chip, against its reference;
  (f) run two rounds of Camel's controller over the engine.

Times printed here are smoke timings of one run, not benchmark numbers.
The last line of stdout is the verdict
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
a failing phase raises, and the script exits non-zero without printing
it.  The compile cache lives where `repro.launch.compile_cache` says.

Usage:
    python chip_smoke.py
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.decode_attention.decode_attention import (  # noqa: E402
    decode_attention_fwd)
from repro.kernels.decode_attention.ref import (  # noqa: E402
    decode_attention_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import engine_mode  # noqa: E402
from repro.models.registry import bundle_for  # noqa: E402
from repro.platform import make_env  # noqa: E402
from repro.serving.scheduler import EngineRequest  # noqa: E402

ARCH = "qwen2-1.5b"
PRESET = "published"
SEED = 0

# Serving shapes: 8 slots over a 1024-position arena; 16 requests with
# ragged 32-512-token prompts and 16-64-token budgets.  A 128-token prompt
# bucket keeps the admission prefills to four compiled shapes.
N_SLOTS = 8
MAX_SEQ_LEN = 1024
PROMPT_BUCKET = 128
N_REQUESTS = 16
PROMPT_RANGE = (32, 512)
BUDGET_RANGE = (16, 64)
STATIC_NEW_TOKENS = 32

# Logits check: one ragged prompt (left-padded to its bucket), then
# greedy decode steps, each compared with the f32 reference.
CHECK_PROMPT_LEN = 100
CHECK_STEPS = 4
# Relative L2 error ||engine - ref|| / ||ref|| per position.  The engine
# runs bf16 weights and activations (8-bit mantissa, spacing 2^-8 =
# 0.4%) through 28 residual layers; the reference is the same weights in
# f32 at "highest" matmul precision.  At published widths with 2-12
# layers (CPU) rounding gives ~1.0-1.2% at every position, flat in depth,
# while decoding one position late (a broken RoPE offset or cache slot)
# gives 11-16%.  The bound sits between the two, and the control below
# asserts that the offset still fails it on the chip.
LOGITS_RTOL = 0.04

# Decode kernel check: the served batch at the rehearsed arena length.
KERNEL_BATCH = 8
KERNEL_SEQ = 2048
# Both outputs are bf16 (spacing 2^-8 relative); two independent
# roundings plus f32 reduction-order differences stay within ~2.5 ulps
# of values below 1.
KERNEL_ATOL = 1e-2

CAMEL_ROUNDS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_identity() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu(ident: dict) -> None:
    if ident["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform is "
            f"{ident['platform']!r}, device {ident['kind']!r}); this check "
            f"runs only on a TPU and never falls back to the CPU")


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


# -- (b) build ------------------------------------------------------------

def build_env():
    """The engine the server builds (`make_env("engine/<arch>")`)."""
    t0 = time.monotonic()
    env = make_env(f"engine/{ARCH}", preset=PRESET, seed=SEED,
                   max_batch=N_SLOTS, max_seq_len=MAX_SEQ_LEN,
                   prompt_bucket=PROMPT_BUCKET)
    jax.block_until_ready(env.engine.params)
    cfg = env.engine.bundle.cfg
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(env.engine.params))
    log(f"(b) model {cfg.name}: layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_heads} "
        f"kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"dtype={jnp.dtype(cfg.dtype).name} params={cfg.n_params} "
        f"param_bytes={n_bytes}")
    log(f"(b) set-up: build + init {time.monotonic() - t0:.3f} s")
    return env


# -- (c) serve ------------------------------------------------------------

def make_requests(vocab: int) -> list:
    rng = np.random.default_rng(SEED)
    out = []
    for rid in range(N_REQUESTS):
        plen = int(rng.integers(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1))
        budget = int(rng.integers(BUDGET_RANGE[0], BUDGET_RANGE[1] + 1))
        prompt = rng.integers(1, vocab, size=plen).astype(np.int32)
        out.append(EngineRequest(rid=rid, prompt=prompt,
                                 max_new_tokens=budget))
    return out


def check_tokens(tokens: np.ndarray, n: int, vocab: int, what: str) -> None:
    if tokens.shape != (n,):
        raise AssertionError(f"{what}: {tokens.shape[0]} tokens, "
                             f"budget {n}")
    if tokens.min() < 0 or tokens.max() >= vocab:
        raise AssertionError(f"{what}: token ids outside [0, {vocab})")


def timed(engine, fn):
    """(result, warm-up seconds, timed seconds) of fn(): one warm-up call
    compiles every shape the run uses, and the timed call must compile
    nothing."""
    t0 = time.monotonic()
    fn()
    warm = time.monotonic() - t0
    before = dict(engine.compile_counts)
    t0 = time.monotonic()
    out = fn()
    dt = time.monotonic() - t0
    if engine.compile_counts != before:
        raise AssertionError(f"compiled inside the timed run: {before} -> "
                             f"{engine.compile_counts}")
    return out, warm, dt


def serve(engine) -> None:
    vocab = engine.bundle.cfg.vocab_size
    reqs = make_requests(vocab)

    (outputs, st), warm, dt = timed(engine, functools.partial(
        engine.generate_continuous, reqs, n_slots=N_SLOTS))
    for r in reqs:
        check_tokens(outputs[r.rid], r.max_new_tokens, vocab,
                     f"request {r.rid}")
    log(f"(c) continuous: {len(reqs)} requests served, every one with its "
        f"budget ({st.tokens_out} tokens, {st.decode_steps} decode steps, "
        f"{st.prefill_calls} prefill calls)")
    log(f"(c) set-up: continuous warm-up (compiles) {warm:.3f} s")
    log(f"(c) smoke timing, not a benchmark: continuous {dt:.3f} s wall, "
        f"prefill {st.prefill_s:.3f} s, decode {st.decode_s:.3f} s, "
        f"{st.tokens_out / dt:.1f} tokens/s")

    prompts = [r.prompt for r in reqs[:N_SLOTS]]
    (out, st), warm, dt = timed(engine, functools.partial(
        engine.generate, prompts, STATIC_NEW_TOKENS))
    for i in range(len(prompts)):
        check_tokens(out[i], STATIC_NEW_TOKENS, vocab, f"static row {i}")
    toks, mask, _ = engine._pad_batch(prompts)
    logits, _ = engine._prefill(engine.params, jnp.asarray(toks),
                                engine._cache_for(len(prompts)),
                                jnp.asarray(mask))
    if not bool(jnp.all(jnp.isfinite(logits))):
        raise AssertionError("static batch: non-finite prefill logits")
    log(f"(c) static: {len(prompts)} prompts x {STATIC_NEW_TOKENS} tokens, "
        f"all in vocabulary, prefill logits finite")
    log(f"(c) set-up: static warm-up (compiles) {warm:.3f} s")
    log(f"(c) smoke timing, not a benchmark: static {dt:.3f} s wall, "
        f"prefill {st.prefill_s:.3f} s, decode {st.decode_s:.3f} s, "
        f"{st.tokens_out / dt:.1f} tokens/s")
    log(f"(c) peak_bytes_in_use: {peak_bytes()}")


# -- (d) logits against the f32 reference --------------------------------

def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def check_logits(engine) -> None:
    bundle = engine.bundle
    cfg = bundle.cfg
    rng = np.random.default_rng(SEED + 1)
    prompt = rng.integers(1, cfg.vocab_size,
                          size=CHECK_PROMPT_LEN).astype(np.int32)
    toks, mask, plen = engine._pad_batch([prompt])
    logits, cache = engine._prefill(engine.params, jnp.asarray(toks),
                                    engine._cache_for(1), jnp.asarray(mask))
    dec_mask = np.ones((1, engine.max_seq_len), bool)
    dec_mask[:, :plen] = mask
    dec_mask = jnp.asarray(dec_mask)
    after_prefill = cache
    decode = jax.jit(bundle.decode_step)

    got = [np.asarray(logits[0])]
    seq = list(prompt)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    first = tok
    for i in range(CHECK_STEPS):
        seq.append(int(tok[0]))
        logits, cache = decode(engine.params, tok, cache,
                               jnp.asarray(plen + i, jnp.int32),
                               attn_mask=dec_mask)
        got.append(np.asarray(logits[0]))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # Control: the first decode step one position late.
    shifted, _ = decode(engine.params, first, after_prefill,
                        jnp.asarray(plen + 1, jnp.int32), attn_mask=dec_mask)

    ref_bundle = bundle_for(dataclasses.replace(cfg, dtype=jnp.float32))

    @jax.jit
    def reference(params, tokens):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return ref_bundle.forward(params, tokens)[0][0]

    with jax.default_matmul_precision("highest"):
        ref = reference(engine.params, jnp.asarray([seq], jnp.int32))
    ref = np.asarray(ref[CHECK_PROMPT_LEN - 1:])

    if not all(np.isfinite(g).all() for g in got):
        raise AssertionError("engine logits are not finite")
    errs = [rel_err(g, r) for g, r in zip(got, ref)]
    control = rel_err(shifted[0], ref[1])
    log(f"(d) logits vs f32 reference, prompt {CHECK_PROMPT_LEN} tokens "
        f"(bucket {plen}) + {CHECK_STEPS} decode steps: relative L2 "
        f"errors {[f'{e:.5f}' for e in errs]} (tolerance {LOGITS_RTOL}); "
        f"control one position late: {control:.5f}")
    if not max(errs) <= LOGITS_RTOL:
        raise AssertionError(f"engine logits off the reference: {errs}")
    if not control > LOGITS_RTOL:
        raise AssertionError(f"tolerance {LOGITS_RTOL} cannot tell a "
                             f"one-position offset ({control:.5f})")


# -- (e) the decode kernel on the chip -----------------------------------

def compile_decode_kernel(args):
    """The split-K decode kernel compiled for the device its arguments
    live on: (callable, compiled program text)."""
    compiled = decode_attention_fwd.lower(*args, interpret=False).compile()
    return compiled, compiled.as_text()


def check_kernel(cfg) -> None:
    b, s = KERNEL_BATCH, KERNEL_SEQ
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(kq, (b, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, kvh, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, kvh, d), jnp.bfloat16)
    rng = np.random.default_rng(SEED)
    lens = jnp.asarray(rng.integers(s // 2, s + 1, size=b), jnp.int32)
    starts = jnp.asarray(rng.integers(0, 64, size=b), jnp.int32)
    args = (q, k, v, lens, starts)

    fn, text = compile_decode_kernel(args)
    if "tpu_custom_call" not in text:
        raise AssertionError("decode kernel compiled without a "
                             "tpu_custom_call (interpret-mode lowering?)")
    out = np.asarray(fn(*args), np.float32)
    ref = np.asarray(decode_attention_ref(*args), np.float32)
    err = float(np.max(np.abs(out - ref)))
    log(f"(e) decode kernel B={b} S={s} H={h} KVH={kvh} D={d}: "
        f"tpu_custom_call present, max |kernel - ref| = {err:.5f} "
        f"(tolerance {KERNEL_ATOL})")
    if not err <= KERNEL_ATOL:
        raise AssertionError(f"decode kernel off its reference by {err}")


# -- (f) Camel over the engine -------------------------------------------

def camel_rounds() -> None:
    out = engine_mode(ARCH, CAMEL_ROUNDS, alpha=0.5, seed=SEED,
                      scheduler="continuous", preset=PRESET)
    if out["n_pulls"] != CAMEL_ROUNDS or out["total_tokens"] <= 0:
        raise AssertionError(f"Camel rounds did not serve: {out}")
    # The summary's energy and latency are Jetson-modelled (DVFS-scaled
    # time, board power model), so none of them is printed as a chip
    # number.
    log(f"(f) Camel: {out['n_pulls']} rounds over the {out['preset']} "
        f"engine, continuous scheduler, {out['total_tokens']} tokens "
        f"served; committed arm {out['best_knobs']}")


def main() -> None:
    t0 = time.monotonic()
    cache_dir = enable_compile_cache()
    ident = device_identity()
    log(f"(a) device: platform={ident['platform']} kind={ident['kind']} "
        f"count={ident['count']}")
    require_tpu(ident)

    env = build_env()
    engine = env.engine
    serve(engine)
    check_logits(engine)
    cfg = engine.bundle.cfg
    del env, engine
    gc.collect()             # free the first copy of the weights
    check_kernel(cfg)
    camel_rounds()
    log(f"peak_bytes_in_use: {peak_bytes()}")
    n_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {cache_dir} ({n_entries} entries)")
    log(f"total wall (compiles included): {time.monotonic() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": ident}))


if __name__ == "__main__":
    main()
