"""The trace reduction (`bench/trace_reduce.py`): on hand-made events, and
on a small trace recorded on a TPU v5e by `record_trace.py`."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small.xplane.pb")


def test_union_counts_overlap_once():
    assert tr.union([(5, 9), (0, 4), (3, 6), (12, 13), (13, 15)]) == [
        (0, 9), (12, 15)]
    assert tr.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]


def test_reduce_events():
    # One device; host window [0, 100) ns.  Ops: two overlapping in module
    # A, one in module B.  No host "Execute" event, so the device timeline
    # is shifted to start at the window's start (-10 ns): busy 0-30, 70-80.
    ops = {0: [("%fusion.1 = f32[] fusion()", 10, 30), ("fusion.2", 20, 40),
               ("dot.3", 80, 90)]}
    modules = {0: [("jit_a(7)", 10, 40), ("jit_b(8)", 80, 90)]}
    host = [("bench.window", 0, 100), ("host_prep", 25, 75),
            ("inner", 45, 60)]
    out = tr.reduce_events(ops, modules, host, (0, 100), "bench.window")
    assert out["busy_s"] == pytest.approx(40e-9)       # overlap counted once
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["idle_share"] == pytest.approx(0.6)
    assert out["module_s"] == pytest.approx({"jit_a": 30e-9, "jit_b": 10e-9})
    assert out["device_ops"][0] == ["jit_a/fusion.1", pytest.approx(20e-9)]
    # gaps: 30-70 (midpoint 50: innermost host event "inner"), 80-100
    assert out["idle_gaps"] == [["inner", pytest.approx(40e-9)],
                                ["no host event", pytest.approx(20e-9)]]


def _sweep_busy(intervals):
    """Busy length by an event sweep (independent of `tr.union`)."""
    edges = sorted([(s, 1) for s, _ in intervals]
                   + [(e, -1) for _, e in intervals])
    busy, depth, since = 0.0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_tpu_trace():
    """`record_trace.py` on a v5e: 4 matrix products and 4 elementwise
    passes inside ``bench.window``, three of them after a 2 ms sleep."""
    ops, modules, host = tr.load(FIXTURE)
    assert list(ops) == [0] and len(ops[0]) == 16
    out = tr.reduce_file(FIXTURE, "bench.window")
    busy = _sweep_busy([(s, e) for _, s, e in ops[0]]) * 1e-9
    assert out["busy_s"] == pytest.approx(busy)
    assert out["busy_s"] == pytest.approx(508.687e-6)
    assert out["window_s"] == pytest.approx(13.555719e-3)
    assert out["idle_share"] == pytest.approx(1 - busy / 13.555719e-3)
    assert out["module_s"] == pytest.approx({"jit__lambda": 508.72e-6})
    assert out["device_ops"][0][0] == "jit__lambda/convolution_reduce_fusion"
    lengths = [g[1] for g in out["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)
    assert [g[0] for g in out["idle_gaps"][:3]] == ["$time sleep"] * 3
    assert all(g > 2e-3 for g in lengths[:3])
