"""Device idle time by the program's `engine.*` spans (`bench/spans.py`),
on hand-made events and on the recorded v5e trace, and the four readers
that use the spans and the serving loop's counters."""

import os
import shutil
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402
from repro.serving.engine import ContinuousStats  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small.xplane.pb")


def test_innermost_span_wins_and_gaps_outside_spans_are_none():
    # Device ops at 50-150, 300-400, 700-800 on the device's clock; the
    # first host Execute at 100 shifts them by +50: busy 100-200,
    # 350-450, 750-850 in the host window 0-1000.
    ops = {0: [(50, 150), (300, 400), (700, 800)]}
    host = [("bench.window", 0, 1000, {}),
            ("PJRT_Execute", 100, 110, {}),
            ("engine.chunk.fetch", 120, 130, {"steps": 3}),
            ("engine.bookkeep", 180, 700, {}),
            ("engine.admit", 250, 300, {"rid": 1}),    # inside bookkeep
            ("engine.chunk.fetch", 900, 950, {"steps": 4}),
            ("other", 0, 1000, {})]                    # not the program's
    out = spans.attribute(ops, {}, host, (0, 1000))
    # gaps: 200-350 (mid 275: admit), 450-750 (mid 600: bookkeep),
    # 0-100 (mid 50: no engine span), 850-1000 (mid 925: fetch)
    assert out["idle_s"] == pytest.approx({
        "engine.admit": 150e-9, "engine.bookkeep": 300e-9,
        spans.NONE: 100e-9, "engine.chunk.fetch": 150e-9})
    assert out["interior_idle_s"] == pytest.approx({
        "engine.admit": 150e-9, "engine.bookkeep": 300e-9})
    assert out["named_idle_s"] == pytest.approx(600e-9)
    assert (out["chunks"], out["steps"]) == (2, 7)
    assert out["alignment"] == "one shift"


def _drifting(n=40, drift_ns=1e4):
    """`n` programs 1 ms apart, 0.2 ms long, each launched 5 ms (host
    minus device) plus `drift_ns` more per program after its device
    start; an `engine.chunk.wait` span of +-0.15 ms over the midpoint of
    each true gap between programs."""
    offset = [5e6 + k * drift_ns for k in range(n)]
    mods = [(k * 1e6, k * 1e6 + 2e5, k) for k in range(n)]
    host = [("bench.window", 4e6, (n + 6) * 1e6, {}),
            ("PJRT_Execute", 5e6, 5e6 + 10, {})]
    host += [(spans.LAUNCH, k * 1e6 + offset[k], k * 1e6 + offset[k] + 10,
              {"run_id": k}) for k in range(n)]
    for k in range(n - 1):
        mid = (k * 1e6 + 2e5 + offset[k] + (k + 1) * 1e6 + offset[k + 1]) / 2
        host.append(("engine.chunk.wait", mid - 1.5e5, mid + 1.5e5, {}))
    ops = {0: [(s, e) for s, e, _ in mods]}
    return ops, {0: mods}, host, (4e6, (n + 6) * 1e6)


def test_drifting_clock_is_aligned_per_launch(monkeypatch):
    ops, modules, host, window = _drifting()
    out = spans.attribute(ops, modules, host, window)
    assert out["alignment"] == "per launch"
    assert out["drift_ms"] == pytest.approx(0.27)    # (39 - 12) x 10 us
    inner = out["interior_idle_s"]
    assert set(inner) == {"engine.chunk.wait"}
    # 39 gaps of 0.8 ms on the device's clock, 0.81 ms on the host's
    assert inner["engine.chunk.wait"] == pytest.approx(39 * 8.1e5 * 1e-9,
                                                       rel=0.01)
    # one shift for the whole window puts the later gaps beside their spans
    monkeypatch.setattr(spans, "DRIFT_NS", 1e9)
    one = spans.attribute(ops, modules, host, window)
    assert one["alignment"] == "one shift"
    assert one["interior_idle_s"][spans.NONE] > 0.5 * sum(
        one["interior_idle_s"].values())


def test_steady_clock_keeps_one_shift():
    ops, modules, host, window = _drifting(drift_ns=1e3)
    out = spans.attribute(ops, modules, host, window)
    assert out["alignment"] == "one shift"
    assert set(out["interior_idle_s"]) == {"engine.chunk.wait"}


def test_recorded_tpu_trace_has_no_engine_span(tmp_path):
    """The recorded v5e trace (`record_trace.py`) holds no `engine.*`
    span, as a program without them would: all idle time is `none`, the
    counts are zero, and it sums to the window minus `trace_reduce`'s
    busy time."""
    log = tmp_path / "plugins" / "profile" / "run"
    log.mkdir(parents=True)
    shutil.copy(FIXTURE, log / "small.xplane.pb")
    out = spans.analysis(str(tmp_path))
    assert (out["chunks"], out["steps"], out["named_idle_s"]) == (0, 0, 0)
    assert set(out["idle_s"]) == {spans.NONE}
    red = trace_reduce.reduce_file(FIXTURE, "bench.window")
    assert sum(out["idle_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-3)
    assert spans.analysis(str(tmp_path)) is out          # loaded once
    assert spans.analysis(str(tmp_path / "empty")) is None


FOUND = {"chunks": 4, "steps": 50, "named_idle_s": 0.02,
         "idle_s": {"engine.chunk.wait": 0.02, spans.NONE: 0.001}}
TRACE = {"module_s": {"jit__fused_continuous_fn": 0.6, "jit__admit_fn": 0.1}}


def _ctx(trace=None, stats=None):
    st = stats or ContinuousStats(
        prefill_s=0.4, decode_s=2.6, tokens_out=39, decode_steps=130,
        prefill_calls=4, mean_occupancy=1.5, chunks=10,
        phase_s={"engine.chunk.upload": 0.01, "engine.chunk.wait": 2.5,
                 "engine.chunk.fetch": 0.02, "engine.bookkeep": 0.03,
                 "engine.admit": 0.3},
        empty_slot_steps_blocked=39, empty_slot_steps_drain=26)
    return {"stats": st, "records": [], "n_slots": 2, "window_s": 3.9,
            "setup_s": 12.5, "trace": trace}


@pytest.mark.parametrize("name,want", [
    ("decode_device_step_ms.offline", 1e3 * 0.6 / 50),
    ("chunk_idle_ms.offline", 1e3 * 0.02 / 4),
    ("host_ms_per_chunk.offline", 1e3 * 0.06 / 10),
    ("blocked_slot_share.offline", 100 * 39 / (2 * 130)),
])
def test_span_metrics(monkeypatch, name, want):
    monkeypatch.setattr(spans, "analysis", lambda *a: FOUND)
    assert run.reader(name)(_ctx(TRACE)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["decode_device_step_ms.offline",
                                  "chunk_idle_ms.offline"])
def test_device_span_metrics_silent_untraced_or_without_spans(monkeypatch,
                                                              name):
    read = run.reader(name)
    monkeypatch.setattr(spans, "analysis", lambda *a: FOUND)
    assert read(_ctx(None)) is None                     # untraced
    monkeypatch.setattr(spans, "analysis", lambda *a: None)
    assert read(_ctx(TRACE)) is None                    # no trace file
    monkeypatch.setattr(spans, "analysis", lambda *a: dict(
        FOUND, chunks=0, steps=0, named_idle_s=0))
    assert read(_ctx(TRACE)) is None                    # no engine span


@pytest.mark.parametrize("name", ["host_ms_per_chunk.offline",
                                  "blocked_slot_share.offline"])
def test_counter_metrics_silent_without_counters(name):
    """A program whose stats carry no spans or counters (the parent's)
    reports nothing."""
    older = types.SimpleNamespace(decode_s=2.6, decode_steps=130,
                                  mean_occupancy=1.5)
    assert run.reader(name)(_ctx(TRACE, older)) is None
