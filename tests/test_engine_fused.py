"""Engine hot path: fused fori_loop decode vs the per-token reference,
left-pad masking, prompt bucketing, input validation, and the retrace /
cache-reuse bounds a controller sweep relies on — plus the continuous-
batching differential harness: slot-level admission must be invisible in
the token streams (bit-identical to static batching when no slot churn
happens, and per-request streams independent of co-resident slots when
it does)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models.registry import bundle_for
from repro.platform import make_env
from repro.serving.engine import InferenceEngine
from repro.serving.scheduler import EngineRequest

# One representative per model family (dense/GQA transformer, RWKV
# recurrence, mixed recurrent/attention, softcap+sliding-window, MoE).
FAMILIES = ["smollm-360m", "rwkv6-3b", "recurrentgemma-9b",
            "gemma2-27b", "mixtral-8x22b"]


def _engine(name, **kw):
    cfg = C.get_smoke(name)
    b = bundle_for(cfg)
    params = b.init_params(jax.random.PRNGKey(0))
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_seq_len", 48)
    return InferenceEngine(b, params, **kw), cfg


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


@pytest.mark.parametrize("name", FAMILIES)
def test_fused_bit_identical_to_loop(name):
    """The fused fori_loop decode must produce exactly the greedy tokens
    of the per-token reference loop on every model family."""
    eng, cfg = _engine(name, decode_impl="fused")
    ref = InferenceEngine(eng.bundle, eng.params, max_batch=8,
                          max_seq_len=48, decode_impl="loop")
    prompts = _prompts(cfg, [5, 9, 7])
    out_f, st_f = eng.generate(prompts, max_new_tokens=8)
    out_l, st_l = ref.generate(prompts, max_new_tokens=8)
    np.testing.assert_array_equal(out_f, out_l)
    assert st_f.decode_impl == "fused" and st_l.decode_impl == "loop"
    assert out_f.shape == (3, 8)


def test_generate_validation_errors():
    eng, cfg = _engine("smollm-360m", max_batch=2, max_seq_len=48)
    good = _prompts(cfg, [4])
    with pytest.raises(ValueError, match="at least one prompt"):
        eng.generate([], max_new_tokens=4)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate([np.zeros(0, np.int32)], max_new_tokens=4)
    with pytest.raises(ValueError, match="exceeds max_batch"):
        eng.generate(_prompts(cfg, [4, 4, 4]), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.generate(good, max_new_tokens=0)
    with pytest.raises(ValueError, match="max_seq_len"):
        # bucketed to 16, 16 + 40 > 48
        eng.generate(good, max_new_tokens=40)
    with pytest.raises(ValueError, match="decode_impl"):
        InferenceEngine(eng.bundle, eng.params, max_batch=2,
                        max_seq_len=48, decode_impl="eager")
    with pytest.raises(ValueError, match="prompt_bucket"):
        InferenceEngine(eng.bundle, eng.params, max_batch=2,
                        max_seq_len=48, prompt_bucket=0)


def test_ragged_batch_matches_unpadded_logits():
    """Left-padding + the threaded attn_mask must reproduce the unpadded
    per-sequence logits exactly (fp32): prefill the ragged pair padded to
    a common length, compare each row against its solo unpadded run."""
    for attn_impl in ("naive", "flash"):
        cfg = dataclasses.replace(C.get_smoke("smollm-360m"),
                                  dtype=jnp.float32, attn_impl=attn_impl)
        b = bundle_for(cfg)
        params = b.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        p_short = rng.integers(1, cfg.vocab_size, 5).astype(np.int32)
        p_long = rng.integers(1, cfg.vocab_size, 9).astype(np.int32)

        plen = 9
        toks = np.zeros((2, plen), np.int32)
        mask = np.zeros((2, plen), bool)
        toks[0, plen - 5:] = p_short
        mask[0, plen - 5:] = True
        toks[1, :] = p_long
        mask[1, :] = True
        cache = b.init_cache(2, 32)
        ragged, cache = b.prefill(params, jnp.asarray(toks), cache,
                                  attn_mask=jnp.asarray(mask))

        solo_cache = b.init_cache(1, 32)
        solo, solo_cache = b.prefill(params, jnp.asarray(p_short[None]),
                                     solo_cache)
        np.testing.assert_allclose(np.asarray(ragged[0]),
                                   np.asarray(solo[0]),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"prefill {attn_impl}")

        # one decode step must agree too (the padded row decodes at a
        # shifted position; RoPE depends only on relative offsets)
        nxt = jnp.asarray([int(np.argmax(solo[0]))], jnp.int32)
        dmask = np.ones((2, 32), bool)
        dmask[:, :plen] = mask
        lr, _ = b.decode_step(params, jnp.concatenate([nxt, nxt]), cache,
                              jnp.asarray(plen, jnp.int32),
                              attn_mask=jnp.asarray(dmask))
        ls, _ = b.decode_step(params, nxt, solo_cache,
                              jnp.asarray(5, jnp.int32))
        np.testing.assert_allclose(np.asarray(lr[0]), np.asarray(ls[0]),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"decode {attn_impl}")


def test_prompt_bucketing_preserves_tokens():
    """Rounding the padded prompt length up to a bucket multiple shifts
    every sequence left-ward by the same pad amount; greedy tokens must
    not change between bucket sizes (fp32 — RoPE shift-invariance is
    exact in math, and bf16 rounding would flip near-tie argmaxes)."""
    cfg = dataclasses.replace(C.get_smoke("smollm-360m"),
                              dtype=jnp.float32)
    b = bundle_for(cfg)
    params = b.init_params(jax.random.PRNGKey(0))
    eng1 = InferenceEngine(b, params, max_batch=8, max_seq_len=48,
                           prompt_bucket=1)
    eng16 = InferenceEngine(b, params, max_batch=8, max_seq_len=48,
                            prompt_bucket=16)
    prompts = _prompts(cfg, [5, 9], seed=2)
    out1, _ = eng1.generate(prompts, max_new_tokens=6)
    out16, _ = eng16.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(out1, out16)


def test_sweep_compiles_once_per_shape():
    """A 10-round controller-style sweep over batch arms must compile the
    prefill and fused decode once per (batch, bucket) on first touch and
    never again: `compile_counts` stays flat and distinct batch arms hit
    distinct cache-pool entries."""
    env = make_env("engine/smollm-360m", seed=0, prompt_len=16,
                   max_new_tokens=8, max_batch=8, max_seq_len=64)
    batches = [4, 8]
    for b in batches:
        env.pull({"freq_mhz": 930.75, "batch": b}, 0)
    baseline = dict(env.engine.compile_counts)
    assert baseline["cache_pool"] == len(batches)
    assert baseline["prefill"] == len(batches)
    assert baseline["decode_fused"] == len(batches)
    assert baseline["decode_loop"] == 0
    for rnd in range(1, 10):
        env.pull({"freq_mhz": 930.75, "batch": batches[rnd % 2]}, rnd)
        assert env.engine.compile_counts == baseline, \
            f"retrace at round {rnd}: {env.engine.compile_counts}"


def test_engine_env_reports_throughput():
    env = make_env("engine/smollm-360m", seed=0, prompt_len=16,
                   max_new_tokens=8, max_batch=8, max_seq_len=64)
    obs = env.pull({"freq_mhz": 930.75, "batch": 4}, 0)
    assert obs.metadata["decode_impl"] == "fused"
    assert obs.metadata["tokens_per_s"] > 0


# -- continuous batching ----------------------------------------------------


@pytest.mark.parametrize("name", FAMILIES)
def test_continuous_identity_matches_static(name):
    """Differential identity: with every request present at t=0, equal
    budgets and no EOS, continuous scheduling performs exactly the static
    fused schedule (one seed prefill, no admission, no early exit) — the
    per-request token streams must be bit-identical to `generate` on
    every model family.  chunk=3 additionally crosses jit boundaries
    mid-decode (3+3+2 steps), which must not perturb the carry."""
    eng, cfg = _engine(name)
    prompts = _prompts(cfg, [5, 9, 7])
    out_s, _ = eng.generate(prompts, max_new_tokens=8)
    for chunk in (8, 3):
        reqs = [EngineRequest(rid=i, prompt=p, max_new_tokens=8)
                for i, p in enumerate(prompts)]
        out_c, st = eng.generate_continuous(reqs, n_slots=3, chunk=chunk)
        assert st.decode_steps == 8 and st.prefill_calls == 1
        for i in range(3):
            np.testing.assert_array_equal(
                out_c[i], out_s[i],
                err_msg=f"{name} chunk={chunk} request {i}")


def test_continuous_stream_independent_of_co_residents():
    """A request's token stream must not depend on what shares the slot
    pool with it: serve a long request alongside churning short ones
    (mid-generate admission into the neighbouring slot) and compare its
    stream to a solo static run."""
    eng, cfg = _engine("smollm-360m")
    prompts = _prompts(cfg, [5, 9, 13], seed=3)
    reqs = [EngineRequest(rid=0, prompt=prompts[0], max_new_tokens=20),
            EngineRequest(rid=1, prompt=prompts[1], max_new_tokens=4),
            EngineRequest(rid=2, prompt=prompts[2], max_new_tokens=6,
                          arrival_s=0.5)]
    out_c, st = eng.generate_continuous(reqs, n_slots=2, chunk=4,
                                        step_time_s=1.0)
    assert st.prefill_calls >= 2       # rid 2 was admitted mid-generate
    solo, _ = eng.generate([prompts[0]], max_new_tokens=20)
    np.testing.assert_array_equal(out_c[0], solo[0])
    assert len(out_c[1]) == 4 and len(out_c[2]) == 6


def test_continuous_eos_early_exit():
    """An all-EOS-at-step-1 batch must finish in O(1) decode steps, not
    max_new_tokens: probe the greedy continuation, declare it EOS."""
    eng, cfg = _engine("smollm-360m")
    prompt = _prompts(cfg, [6], seed=4)[0]
    probe, _ = eng.generate([prompt] * 4, max_new_tokens=1)
    eos = int(probe[0, 0])
    reqs = [EngineRequest(rid=i, prompt=prompt, max_new_tokens=24)
            for i in range(4)]
    out, st = eng.generate_continuous(reqs, n_slots=4, eos_id=eos,
                                      chunk=24)
    assert st.decode_steps <= 2, \
        f"early exit took {st.decode_steps} steps (cap 24)"
    for i in range(4):
        assert out[i][-1] == eos


def test_continuous_occupancy_sweep_no_retrace():
    """Slot churn must not retrace: after one warmup covering the shapes
    (seed prefill, single-row admission, chunked while_loop), serving
    workloads whose occupancy drains full -> one — with different
    budgets, arrival patterns and EOS positions — keeps `compile_counts`
    flat at one prefill/decode trace per shape."""
    eng, cfg = _engine("smollm-360m", max_batch=4, max_seq_len=64)

    def serve(seed, budgets, stagger):
        prompts = _prompts(cfg, [5, 9, 13, 7], seed=seed)
        reqs = [EngineRequest(rid=i, prompt=p, max_new_tokens=m,
                              arrival_s=stagger * i)
                for i, (p, m) in enumerate(zip(prompts, budgets))]
        eng.generate_continuous(reqs, n_slots=4, chunk=4, step_time_s=1.0)

    serve(0, [16, 8, 4, 2], stagger=0.0)   # drain: 4 live -> 1 live
    serve(1, [12, 3, 5, 2], stagger=2.0)   # admission mid-generate
    baseline = dict(eng.compile_counts)
    for s in range(2, 7):
        serve(s, [2 + 3 * s % 13, 16, 5, 8], stagger=0.5 * (s % 3))
        assert eng.compile_counts == baseline, \
            f"retrace at sweep {s}: {eng.compile_counts} != {baseline}"


def test_continuous_validation_errors():
    eng, cfg = _engine("smollm-360m", max_batch=2)
    p = _prompts(cfg, [4])[0]
    ok = EngineRequest(rid=0, prompt=p, max_new_tokens=4)
    with pytest.raises(ValueError, match="at least one"):
        eng.generate_continuous([])
    with pytest.raises(ValueError, match="duplicate"):
        eng.generate_continuous(
            [ok, EngineRequest(rid=0, prompt=p, max_new_tokens=2)])
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate_continuous(
            [EngineRequest(rid=1, prompt=np.zeros(0, np.int32),
                           max_new_tokens=2)])
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.generate_continuous(
            [EngineRequest(rid=2, prompt=p, max_new_tokens=40)])
    with pytest.raises(ValueError, match="eos_id"):
        eng.generate_continuous([ok], eos_id=-5)
    with pytest.raises(ValueError, match="chunk"):
        eng.generate_continuous([ok], chunk=0)
    with pytest.raises(ValueError, match="n_slots"):
        eng.generate_continuous([ok], n_slots=5)


def test_continuous_rejects_encdec():
    """Absolute sinusoidal positions forbid offset admission — the
    encdec family must be refused up front."""
    cfg = C.get_smoke("seamless-m4t-large-v2")
    b = bundle_for(cfg)
    params = b.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(b, params, max_batch=2, max_seq_len=48)
    req = EngineRequest(rid=0, prompt=np.ones(4, np.int32),
                        max_new_tokens=4)
    with pytest.raises(ValueError, match="encdec"):
        eng.generate_continuous([req])


def test_engine_env_continuous_reports_goodput():
    """The continuous environment serves Poisson arrivals and reports
    measured goodput / queue-wait / occupancy instead of the analytic
    queueing model."""
    env = make_env("engine/smollm-360m", seed=0, prompt_len=16,
                   max_new_tokens=8, max_batch=8, max_seq_len=64,
                   scheduler="continuous", requests_per_pull=6,
                   arrival_rate=4.0)
    obs = env.pull({"freq_mhz": 930.75, "batch": 4}, 0)
    md = obs.metadata
    assert md["scheduler"] == "continuous"
    assert md["n_requests"] == 6
    assert md["goodput_rps"] > 0
    assert 0 < md["mean_occupancy"] <= 4
    assert obs.energy > 0 and obs.latency > 0
    assert obs.queue_wait == md["mean_queue_wait_s"]


# -- spans and scheduler counters of the serving loop -----------------------

SPANS = {"engine.reseed": {"rows", "bucket"},
         "engine.admit": {"rid", "bucket", "slot"},
         "engine.chunk.upload": {"live"},
         "engine.chunk.wait": set(),
         "engine.chunk.fetch": {"steps"},
         "engine.bookkeep": set()}


def _blocking_mix(cfg):
    """Three slots, 160 positions, bucket 16.  Request 2 (bucket 64, 96
    new tokens) fits behind the KV clock only at position 64, which the
    32-step chunks step over, so slots stand vacant while it waits; it is
    served alone after a reseed, while the queue is drained."""
    rng = np.random.default_rng(3)
    spec = [(5, 120), (5, 10), (50, 96), (7, 30), (9, 20)]
    return [EngineRequest(rid=i, prompt=rng.integers(
                1, cfg.vocab_size, size=p).astype(np.int32),
                max_new_tokens=m) for i, (p, m) in enumerate(spec)]


@pytest.fixture(scope="module")
def served():
    """The same serving call with the observation session closed, then
    open: ({rid: tokens}, stats) of each and the session's span rows."""
    import io
    import json

    from repro import obs

    eng, cfg = _engine("smollm-360m", max_batch=3, max_seq_len=160)
    reqs = _blocking_mix(cfg)
    kw = dict(n_slots=3, chunk=32)
    eng.generate_continuous(reqs, **kw)                # compile
    out_off, st_off = eng.generate_continuous(reqs, **kw)
    sink = io.StringIO()
    with obs.observing(sink):
        out_on, st_on = eng.generate_continuous(reqs, **kw)
    rows = [json.loads(line) for line in sink.getvalue().splitlines()]
    return {"engine": eng, "requests": reqs, "kw": kw, "off": st_off,
            "on": st_on, "out_off": out_off, "out_on": out_on,
            "spans": [r for r in rows if r["kind"] == "span"]}


def _named(rows, name):
    return [r for r in rows if r["name"] == name]


def test_continuous_tokens_same_with_session_open_or_closed(served):
    assert served["out_on"].keys() == served["out_off"].keys()
    for rid, toks in served["out_off"].items():
        np.testing.assert_array_equal(served["out_on"][rid], toks)
    assert served["on"].decode_steps == served["off"].decode_steps
    assert served["on"].chunks == served["off"].chunks


def test_continuous_chunk_spans_sum_to_decode_time(served):
    """upload + wait + fetch of a chunk cover its share of `decode_s`
    (which times the same interval from outside), per chunk and in
    total; the fetch spans' `steps` sum to `decode_steps`."""
    rows = served["spans"]
    up, wait, fetch = (_named(rows, "engine.chunk." + n)
                       for n in ("upload", "wait", "fetch"))
    assert len(up) == len(wait) == len(fetch) == served["on"].chunks > 0
    for u, w, f in zip(up, wait, fetch):
        share = f["ts"] - u["start"]
        # the three spans tile the chunk: each starts where the last ended
        assert w["start"] == pytest.approx(u["ts"], abs=1e-8)
        assert f["start"] == pytest.approx(w["ts"], abs=1e-8)
        assert u["dur_s"] + w["dur_s"] + f["dur_s"] == pytest.approx(
            share, rel=0.01)
    total = sum(f["ts"] - u["start"] for u, f in zip(up, fetch))
    assert total == pytest.approx(served["on"].decode_s, rel=1e-6)
    for st in (served["on"], served["off"]):
        ph = st.phase_s
        assert sum(ph["engine.chunk." + n] for n in (
            "upload", "wait", "fetch")) == pytest.approx(st.decode_s,
                                                         rel=0.01)
        assert st.phase_n["engine.chunk.fetch"] == st.chunks
    assert sum(f["attrs"]["steps"] for f in fetch) == \
        served["on"].decode_steps
    assert all(u["attrs"]["live"] >= 1 for u in up)


def test_continuous_prefill_spans_count_prefill_calls(served):
    rows, st = served["spans"], served["on"]
    admits, reseeds = (_named(rows, "engine.admit"),
                       _named(rows, "engine.reseed"))
    assert len(admits) >= 1 and len(reseeds) == 2
    assert len(admits) + len(reseeds) == st.prefill_calls
    assert st.phase_n["engine.admit"] + st.phase_n["engine.reseed"] == \
        served["off"].prefill_calls == st.prefill_calls
    assert {a["rid"] for a in admits} <= {r.rid for r in
                                          served["requests"]}
    # the serving loop's spans are its top level: none nests in another
    loop = [r for r in rows if r["name"] in SPANS]
    assert all(r["parent"] is None for r in loop)


def test_continuous_slot_step_counters_cover_the_pool(served):
    """Blocked, drained and live slot-steps partition n_slots x steps."""
    for st in (served["off"], served["on"]):
        live = st.mean_occupancy * st.decode_steps
        assert st.empty_slot_steps_blocked > 0
        assert st.empty_slot_steps_drain > 0
        assert (st.empty_slot_steps_blocked + st.empty_slot_steps_drain
                + live) == pytest.approx(3 * st.decode_steps)


def test_continuous_request_wall_stamps(served):
    rows = _named(served["spans"], "engine.request")
    assert sorted(r["rid"] for r in rows) == list(range(5))
    by_rid = {r["rid"]: r for r in rows}
    for rec in served["on"].records:
        assert 0.0 <= rec.admit_wall_s <= rec.first_token_wall_s \
            <= rec.finish_wall_s
        row = by_rid[rec.rid]
        a = row["attrs"]
        # every request was due at the call's start
        assert a["queue_wait_s"] == pytest.approx(rec.admit_wall_s)
        assert a["ttft_s"] == pytest.approx(rec.first_token_wall_s)
        assert row["dur_s"] == pytest.approx(rec.finish_wall_s)
        assert a["tokens"] == rec.n_tokens
    # request 2 waited for a reseed: admitted after every other first token
    recs = {r.rid: r for r in served["on"].records}
    assert recs[2].admit_wall_s > max(recs[i].first_token_wall_s
                                      for i in (0, 1, 3))


def test_continuous_profile_holds_every_span(served, tmp_path):
    """Under the profiler every `engine.*` span lands on the host plane
    with its attributes as stats, and no program of the loop is a
    lambda."""
    import glob

    from jax.profiler import ProfileData

    eng = served["engine"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate_continuous(served["requests"], **served["kw"])
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen, names = {}, set()
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for e in line.events:
                names.add(e.name)
                if e.name.startswith("engine."):
                    seen.setdefault(e.name, set()).update(
                        k for k, _ in e.stats)
    assert set(seen) == set(SPANS)
    for name, stats in SPANS.items():
        assert stats <= seen[name], name
    programs = {n for n in names if n.startswith("PjitFunction(")}
    assert {"PjitFunction(_prefill_fn)", "PjitFunction(_admit_fn)",
            "PjitFunction(_fused_continuous_fn)"} <= programs
    assert "PjitFunction(<lambda>)" not in programs
