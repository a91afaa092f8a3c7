"""Every metric reader (`bench/metrics/`) on hand-built program records."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import family  # noqa: E402
import run  # noqa: E402
from repro.serving.engine import ContinuousStats  # noqa: E402
from repro.serving.scheduler import RequestRecord  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NAMES = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
with open(os.path.join(BENCH, "configs", "qwen2-1.5b.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
counts = family.load(CONFIG).counts
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(trace=None):
    # Four requests on the engine's clock (seconds): arrival, admit,
    # finish, tokens.
    recs = [RequestRecord(rid=i, arrival_s=a, admit_s=b, prompt_len=100,
                          slot=i % 2, finish_s=c, n_tokens=n)
            for i, (a, b, c, n) in enumerate([(0.0, 0.1, 1.1, 11),
                                              (0.0, 0.3, 2.3, 21),
                                              (1.0, 1.2, 1.6, 5),
                                              (2.0, 3.0, 3.2, 2)])]
    stats = ContinuousStats(prefill_s=0.4, decode_s=2.6, tokens_out=39,
                            decode_steps=130, prefill_calls=4,
                            mean_occupancy=1.5, records=recs)
    work = counts.window_work(MODEL, [(100, r.n_tokens) for r in recs],
                              stats.decode_steps)
    return {"stats": stats, "records": recs, "n_slots": 2, "window_s": 3.9,
            "setup_s": 12.5, "trace": trace, "model": MODEL, "peaks": PEAKS,
            "work": work}


TRACE = {"idle_share": 0.25,
         "module_s": {"jit__fused_continuous_fn": 2.0, "jit__admit_fn": 0.3},
         "work": counts.window_work(MODEL, [(100, 9), (300, 17)], 20)}


def test_every_metric_has_a_reader():
    for name in NAMES:
        assert callable(run.reader(name))


@pytest.mark.parametrize("name,want", [
    ("tokens_per_s", 39 / 3.9),
    ("setup_s", 12.5),
    ("ttft_p50_ms", 250.0),            # waits 100, 300, 200, 1000 ms
    ("ttft_p95_ms", 300.0 + 0.85 * 700.0),
    ("queue_wait_p95_ms.chat", 300.0 + 0.85 * 700.0),
    ("occupancy.offline", 75.0),
    ("occupancy.chat", 75.0),
    ("prefill_ms.chat", 100.0),
    ("decode_step_ms.offline", 20.0),
    ("decode_step_ms.chat", 20.0),
])
def test_host_metrics(name, want):
    assert run.reader(name)(_ctx()) == pytest.approx(want)


def test_tpot_p95():
    # per-token gaps (ms): 100, 100, 100, 200 -> p95 = 100 + 0.85 * 100
    assert run.reader("tpot_p95_ms")(_ctx()) == pytest.approx(185.0)


def test_trace_metrics():
    ctx = _ctx(TRACE)
    assert run.reader("idle_share.offline")(ctx) == pytest.approx(25.0)
    work = TRACE["work"]
    least = max(work["decode_flops"] / 197e12, work["decode_bytes"] / 819e9)
    assert run.reader("decode_roofline.offline")(ctx) == pytest.approx(
        100 * least / 2.0)
    work = ctx["work"]
    flops = work["prefill_flops"] + work["decode_flops"]
    assert run.reader("mfu.offline")(ctx) == pytest.approx(
        100 * flops / (3.9 * 197e12))


def test_trace_metrics_silent_without_a_trace():
    ctx = _ctx()
    assert run.reader("idle_share.chat")(ctx) is None
    assert run.reader("decode_roofline.offline")(ctx) is None
    no_module = _ctx({"idle_share": 0.1, "module_s": {"jit__admit_fn": 1.0},
                      "work": TRACE["work"]})
    assert run.reader("decode_roofline.offline")(no_module) is None
