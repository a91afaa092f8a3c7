"""Analytic counts (the family's `counts.py`, through `family.load`) and
the peaks table, on the CPU."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import family  # noqa: E402
import run  # noqa: E402


def _model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["qwen2-1.5b", "qwen2.5-3b"])
def test_param_count_matches_program(name):
    """`counts.param_count` is the program's `n_params` plus what that
    formula leaves out: the QKV biases and the final norm."""
    import repro.configs as C

    config = _model(name)
    counts = family.load(config).counts
    cfg = C.get(config["arch"])
    m = config["model"]
    s = counts.dims(m)
    extra = s["L"] * (s["H"] + 2 * s["KV"]) * s["hd"] + s["D"]
    assert counts.param_count(m) == cfg.n_params + extra
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == (
        s["L"], s["D"], s["F"], s["V"])


@pytest.mark.parametrize("name,kv", [("qwen2-1.5b", 28672),
                                     ("qwen2.5-3b", 36864)])
def test_kv_bytes_follow_live_context(name, kv):
    config = _model(name)
    counts = family.load(config).counts
    m = config["model"]
    assert counts.kv_bytes_per_token(m) == kv
    w = counts.weight_bytes(m)
    # Two live slots at contexts 100 and 300: weights once, 400 positions
    # read and two written -- nothing depends on the arena's length.
    assert counts.decode_step_bytes(m, [100, 300]) == w + kv * 402
    assert counts.decode_step_bytes(m, []) == w
    arena = config["engine"]["n_slots"] * config["engine"]["max_seq_len"]
    assert counts.decode_step_bytes(m, [1]) < w + kv * arena


def test_window_work_sums_request_steps():
    config = _model("qwen2-1.5b")
    counts = family.load(config).counts
    m = config["model"]
    # prompt 10, 4 tokens: decode steps at contexts 11, 12, 13.
    work = counts.window_work(m, [(10, 4)], decode_steps=3)
    assert work["decode_tokens"] == 3
    assert work["decode_flops"] == counts.decode_step_flops(m, [11, 12, 13])
    assert work["decode_bytes"] == sum(
        counts.decode_step_bytes(m, [c]) for c in (11, 12, 13))
    assert work["prefill_flops"] == counts.prefill_flops(m, 10)


def test_peaks_known_kind_and_unknown_kind_raises():
    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")
