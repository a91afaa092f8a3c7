"""What decides ``correct``, at a size the CPU holds.

A whole run (`run.run_cell`, with the chip check skipped) of a two-layer
model at the published family's shape rules: served as built, it is
correct; with its timed path broken underneath -- a decode step that
returns the KV cache unchanged, half of the pool's rows left out (their
logits taken from the other half), or a token altered where the decode
loop produces it -- ``correct`` comes out false.  And the fp8 control
(`control.readings`) reads a widest gap well above the program's.
"""

import os
import sys

import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import control  # noqa: E402
import run  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.serving.engine import InferenceEngine  # noqa: E402

TINY = {"name": "tiny", "arch": "qwen2-1.5b",
        "model": {"architectures": ["Qwen2ForCausalLM"],
                  "hidden_act": "silu", "hidden_size": 64,
                  "intermediate_size": 160, "num_attention_heads": 4,
                  "num_hidden_layers": 2, "num_key_value_heads": 2,
                  "rms_norm_eps": 1e-6, "rope_theta": 1e6,
                  "tie_word_embeddings": True, "vocab_size": 256},
        "engine": {"n_slots": 4, "max_seq_len": 256, "prompt_bucket": 16,
                   "chunk": 4},
        "correct": {"widest_gap": 0.03}}
# A backlog fills every slot from the start, whatever the host's speed,
# so each fault below reaches served tokens.
MIX = {"arrivals": "backlog", "rate_rps": 20.0,
       "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                  "min": 4, "max": 48},
       "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                  "min": 8, "max": 32}}
PLAN = {"cell": {"chips": 1}, "config": TINY, "mix": MIX,
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"}],
        "per_layer": []}


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(run, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _run(seed=2 ** 31 + 7):
    return run.run_cell(PLAN, seed, 1.0, False, 0.0)


def test_served_as_built_is_correct(cpu_peaks):
    res = _run()
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] == 20
    assert list(res)[-1] == "checks"
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_state_left_unchanged_is_not_correct(cpu_peaks, monkeypatch):
    real = transformer.decode_step

    def stale(cfg, params, token, cache, pos, **kw):
        logits, _ = real(cfg, params, token, cache, pos, **kw)
        return logits, cache

    monkeypatch.setattr(transformer, "decode_step", stale)
    res = _run()
    assert res["correct"] is False
    gap = res["checks"]["widest_gap"]
    assert gap["value"] > gap["limit"]


def test_half_the_batch_left_out_is_not_correct(cpu_peaks, monkeypatch):
    real = transformer.decode_step

    def half(cfg, params, token, cache, pos, **kw):
        logits, new = real(cfg, params, token, cache, pos, **kw)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), new

    monkeypatch.setattr(transformer, "decode_step", half)
    res = _run()
    assert res["correct"] is False


def test_altered_token_is_not_correct(cpu_peaks, monkeypatch):
    real = InferenceEngine._fused_continuous_fn

    def altered(self, *args):
        steps, tok, cache, out, fin, em = real(self, *args)
        vocab = self.bundle.cfg.vocab_size
        out = jnp.where(out >= 0, (out + 1) % vocab, out)
        return steps, tok, cache, out, fin, em

    monkeypatch.setattr(InferenceEngine, "_fused_continuous_fn", altered)
    res = _run()
    assert res["correct"] is False


def test_fp8_control_reads_far_above_the_program():
    rows = control.readings(PLAN, [11, 12, 13], 1.0)
    program = max(r[1] for r in rows)
    ctl = min(r[2] for r in rows)
    assert program <= TINY["correct"]["widest_gap"] < ctl
    assert ctl > 3 * program
