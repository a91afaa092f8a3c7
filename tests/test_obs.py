"""repro.obs: sensors, energy metering, tracing, and the bit-identity
contract that lets `--sensor simulated` ride along on every default run.

Covers (ISSUE satellites): the ReplaySensor <-> RecordingSensor
round-trip, EnergyMeter trapezoid accuracy against closed-form ramps and
its constant-signal exactness, EngineEnvironment bit-identity with and
without a simulated sensor, sysfs rail scaling, spec parsing, trace
content for an instrumented controller run, and the trace_report
summarizer."""

import io
import json
import os
import sys
import types

import numpy as np
import pytest

from repro import obs
from repro.core import baselines, controller, cost, priors
from repro.obs import meter as meter_mod
from repro.obs import sensors as sensors_mod
from repro.obs import tracing as tracing_mod
from repro.platform import DVFSPlatform, make_env, make_space
from repro.serving import energy
from repro.serving.engine import EngineEnvironment, EngineStats

DATA_TRACE = os.path.join(os.path.dirname(__file__), "data",
                          "rails_small.jsonl")


# ---------------------------------------------------------------------------
# Sensors
# ---------------------------------------------------------------------------


class _SeqSensor:
    """Emits a fixed watt sequence, then holds the last value."""

    name = "seq"

    def __init__(self, seq):
        self.seq = list(seq)
        self.i = 0
        self.closed = False

    def read_watts(self):
        w = self.seq[min(self.i, len(self.seq) - 1)]
        self.i += 1
        return w

    def close(self):
        self.closed = True


def test_recording_replay_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    seq = [2.0, 5.0, 8.0, 11.0, 14.0]
    rec = obs.RecordingSensor(_SeqSensor(seq), path)
    assert [rec.read_watts() for _ in seq] == seq
    rec.close()
    assert rec.inner.closed

    rep = obs.ReplaySensor(path)
    assert [rep.read_watts() for _ in seq] == seq
    # rows carry monotonically non-decreasing timestamps
    with open(path) as f:
        ts = [json.loads(line)["t"] for line in f]
    assert ts == sorted(ts) and len(ts) == len(seq)


def test_replay_sensor_loop_and_hold():
    src = io.StringIO('{"t": 0, "watts": 1.0}\n{"t": 1, "watts": 2.0}\n')
    looping = obs.ReplaySensor(src)
    assert [looping.read_watts() for _ in range(5)] == [1, 2, 1, 2, 1]
    src.seek(0)
    holding = obs.ReplaySensor(src, loop=False)
    assert [holding.read_watts() for _ in range(4)] == [1, 2, 2, 2]


def test_replay_sensor_reads_checked_in_rails_trace():
    rep = obs.ReplaySensor(DATA_TRACE)
    assert len(rep.samples) == 50
    assert rep.read_watts() == 12.0          # first recorded sample
    assert all(5.0 < w < 25.0 for w in rep.samples)


def test_replay_sensor_missing_or_empty_trace(tmp_path):
    with pytest.raises(obs.SensorUnavailable, match="cannot read"):
        obs.ReplaySensor(str(tmp_path / "nope.jsonl"))
    empty = tmp_path / "empty.jsonl"
    empty.write_text("\n")
    with pytest.raises(obs.SensorUnavailable, match="no samples"):
        obs.ReplaySensor(str(empty))


def test_sysfs_rails_scaling_and_resilience(tmp_path):
    iio = tmp_path / "iio"
    hwmon = tmp_path / "hwmon"
    iio.mkdir(), hwmon.mkdir()
    rail_mw = iio / "in_power0_input"
    rail_mw.write_text("12000\n")            # iio path: mW -> 12 W
    rail_uw = hwmon / "power1_input"
    rail_uw.write_text("15000000\n")         # hwmon path: uW -> 15 W
    gone = tmp_path / "unplugged" / "power2_input"   # never created

    s = obs.SysfsRailsSensor(paths=[str(rail_mw), str(rail_uw), str(gone)])
    assert s.read_watts() == pytest.approx(27.0)
    assert s.name == "sysfs:3rails"
    with pytest.raises(obs.SensorUnavailable):
        obs.SysfsRailsSensor(paths=[])


def test_simulated_sensor_tracks_platform_actuation():
    plat = DVFSPlatform(energy.JETSON_AGX_ORIN)
    s = obs.SimulatedSensor(plat, utilization=0.5)
    w0 = s.read_watts()
    assert w0 == float(plat.power(plat.current_level, 0.5))
    plat.set_level(plat.n_levels - 1)
    s.set_utilization(1.0)
    assert s.read_watts() == float(plat.power(plat.n_levels - 1, 1.0))
    assert s.read_watts() > w0


def test_make_sensor_specs(tmp_path):
    plat = DVFSPlatform(energy.JETSON_AGX_ORIN)
    assert isinstance(obs.make_sensor("simulated", platform=plat),
                      obs.SimulatedSensor)
    with pytest.raises(obs.SensorUnavailable, match="Platform"):
        obs.make_sensor("simulated")
    rep = obs.make_sensor(f"replay:{DATA_TRACE}")
    assert isinstance(rep, obs.ReplaySensor)
    # a ready sensor instance passes through unchanged
    assert obs.make_sensor(rep) is rep
    rec = obs.make_sensor(f"record:{tmp_path / 'out.jsonl'}", platform=plat)
    assert isinstance(rec, obs.RecordingSensor)
    rec.read_watts(), rec.close()
    with pytest.raises(ValueError, match="unknown sensor spec"):
        obs.make_sensor("thermocouple")


def test_nvml_sensor_unavailable_without_pynvml(monkeypatch):
    monkeypatch.setitem(sys.modules, "pynvml", None)
    with pytest.raises(obs.SensorUnavailable, match="pynvml"):
        obs.NVMLSensor()


# ---------------------------------------------------------------------------
# EnergyMeter
# ---------------------------------------------------------------------------


class _Bench:
    """Deterministic (clock, sensor) pair: the sensor reads f(t) at the
    clock's current time; the test advances time between samples."""

    def __init__(self, f):
        self.t = 0.0
        self.f = f

    def clock(self):
        return self.t

    @property
    def sensor(self):
        bench = self

        class _S:
            name = "bench"

            def read_watts(self):
                return bench.f(bench.t)

            def close(self):
                pass

        return _S()


def test_energy_meter_trapezoid_exact_on_linear_ramp():
    # w(t) = 2 + 3t over [0, 4]: integral = 2*4 + 1.5*16 = 32 J exactly
    # (the trapezoid rule is exact for piecewise-linear power).
    bench = _Bench(lambda t: 2.0 + 3.0 * t)
    m = obs.EnergyMeter(bench.sensor, clock=bench.clock, background=False)
    with m.measure() as meas:
        for t in (1.0, 2.0, 3.0):
            bench.t = t
            meas.sample()
        bench.t = 4.0
    assert meas.times == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert meas.joules == 32.0
    assert meas.avg_watts == pytest.approx(8.0)
    assert meas.peak_watts == 14.0
    assert meas.duration_s == 4.0


def test_energy_meter_trapezoid_second_order_on_quadratic():
    # w(t) = t^2 over [0, 2]: closed form 8/3; the composite trapezoid
    # with h=0.25 overestimates by exactly (b-a) h^2 w''/12 = 1/48
    # (w'' is constant), pinning the integrator's second-order accuracy.
    bench = _Bench(lambda t: t * t)
    m = obs.EnergyMeter(bench.sensor, clock=bench.clock, background=False)
    with m.measure() as meas:
        for i in range(1, 8):
            bench.t = i * 0.25
            meas.sample()
        bench.t = 2.0
    assert meas.joules - 8.0 / 3.0 == pytest.approx(1.0 / 48.0)


def test_energy_meter_constant_signal_is_exact():
    # Exactness contract: avg_watts must be the sensor's float, not a
    # joules/duration reconstruction (this is what keeps the simulated
    # sensor bit-identical to the analytical path).
    bench = _Bench(lambda t: 17.3)
    m = obs.EnergyMeter(bench.sensor, clock=bench.clock, background=False)
    with m.measure() as meas:
        bench.t = 0.7
    assert meas.avg_watts == 17.3            # exact, not approx
    assert meas.joules == 17.3 * meas.duration_s
    summary = meas.summary()
    assert summary["n_samples"] == 2 and summary["sensor"] == "bench"


def test_energy_meter_background_thread_samples():
    bench = _Bench(lambda t: 5.0)
    m = obs.EnergyMeter(bench.sensor, hz=200.0)
    import time as _time
    with m.measure() as meas:
        _time.sleep(0.05)
    assert meas.n_samples >= 3               # entry + exit + background
    assert meas.avg_watts == 5.0
    with pytest.raises(ValueError):
        obs.EnergyMeter(bench.sensor, hz=0.0)


# ---------------------------------------------------------------------------
# EnergyMeter fault tolerance (ISSUE satellite: the sampler thread no
# longer dies on a raising sensor)
# ---------------------------------------------------------------------------


class _FaultySensor:
    """Reads a constant, but fails (raise or NaN) on scripted indices."""

    name = "faulty"

    def __init__(self, watts=9.0, raise_at=(), nan_at=()):
        self.watts = watts
        self.raise_at = set(raise_at)
        self.nan_at = set(nan_at)
        self.i = -1

    def read_watts(self):
        self.i += 1
        if self.i in self.raise_at:
            raise obs.SensorUnavailable(f"scripted failure at {self.i}")
        if self.i in self.nan_at:
            return float("nan")
        return self.watts

    def close(self):
        pass


def test_energy_meter_counts_errors_and_keeps_sampling():
    """A raising read and a NaN read are each dropped and counted in
    `sample_errors`; the samples around them still integrate exactly."""
    bench = _Bench(None)
    sensor = _FaultySensor(watts=9.0, raise_at={1}, nan_at={3})
    m = obs.EnergyMeter(sensor, clock=bench.clock, background=False)
    with m.measure() as meas:
        for t in (1.0, 2.0, 3.0):            # reads 1 (raises), 2, 3 (NaN)
            bench.t = t
            meas.sample()
        bench.t = 4.0                        # exit read: index 4, clean
    assert meas.sample_errors == 2
    assert meas.n_samples == 3               # entry + read 2 + exit
    assert meas.avg_watts == 9.0             # constant-signal exactness
    assert meas.joules == 9.0 * 4.0
    assert meas.summary()["sample_errors"] == 2


def test_energy_meter_background_thread_survives_raising_sensor():
    """The regression the ISSUE names: `read_watts()` raising inside the
    background sampler used to kill the thread, silently truncating the
    measurement.  Now every other read raising still yields a full
    measurement with the errors counted."""
    sensor = _FaultySensor(watts=5.0,
                           raise_at=set(range(1, 10_000, 2)))
    m = obs.EnergyMeter(sensor, hz=500.0)
    import time as _time
    with m.measure() as meas:
        _time.sleep(0.05)
    # the thread kept sampling past the failures: successes AND errors
    # both kept accumulating until exit
    assert meas.sample_errors >= 2
    assert meas.n_samples >= 2
    assert meas.avg_watts == 5.0
    assert meas.summary()["sample_errors"] == meas.sample_errors


def test_energy_meter_all_samples_failed_finalizes_to_zeros():
    bench = _Bench(None)
    sensor = _FaultySensor(raise_at=set(range(100)))
    m = obs.EnergyMeter(sensor, clock=bench.clock, background=False)
    with m.measure() as meas:
        bench.t = 1.0
        meas.sample()
    assert meas.n_samples == 0 and meas.sample_errors == 3
    s = meas.summary()
    assert s["joules"] == 0.0 and s["duration_s"] == 0.0
    assert s["sample_errors"] == 3           # the zeros tell the story


# ---------------------------------------------------------------------------
# Degradation: replay exhaustion + fallback chains (ISSUE satellites)
# ---------------------------------------------------------------------------


def _rows(sink):
    return [json.loads(line) for line in sink.getvalue().splitlines()]


def test_replay_sensor_exhaustion_holds_and_warns_once():
    src = io.StringIO('{"t": 0, "watts": 3.0}\n{"t": 1, "watts": 7.0}\n')
    sink = io.StringIO()
    with obs.observing(sink) as sess:
        s = obs.ReplaySensor(src, loop=False)
        assert [s.read_watts() for _ in range(6)] == [3, 7, 7, 7, 7, 7]
        assert s.exhausted
        assert sess.metrics.counter("sensor_faults_total").value == 1
    events = [r for r in _rows(sink) if r["name"] == "fault.sensor"]
    assert len(events) == 1                  # warned once, not per read
    assert events[0]["attrs"]["reason"] == "trace-exhausted"
    assert events[0]["attrs"]["held_watts"] == 7.0


def test_fallback_sensor_degrades_mid_run():
    first = _FaultySensor(watts=10.0, raise_at={2})
    second = _SeqSensor([20.0])
    sink = io.StringIO()
    with obs.observing(sink):
        chain = obs.FallbackSensor([first, second])
        assert chain.name == "fallback:faulty"
        assert [chain.read_watts() for _ in range(2)] == [10.0, 10.0]
        # read 2 raises -> permanent degradation to the next sensor,
        # which serves the SAME read (the caller never sees the failure)
        assert chain.read_watts() == 20.0
        assert chain.degradations == 1
        assert chain.name == "fallback:seq"
        assert chain.read_watts() == 20.0    # no flap-back
    events = [r for r in _rows(sink) if r["name"] == "fault.sensor"]
    assert len(events) == 1
    assert events[0]["attrs"]["degraded_to"] == "seq"
    # a NaN is NOT a chain failure (the meter counts it instead)
    nan_chain = obs.FallbackSensor([_FaultySensor(nan_at={0}),
                                    _SeqSensor([1.0])])
    import math as _math
    assert _math.isnan(nan_chain.read_watts())
    assert nan_chain.degradations == 0


def test_fallback_sensor_exhausted_chain_raises():
    chain = obs.FallbackSensor([_FaultySensor(raise_at={0}),
                                _FaultySensor(raise_at={0})])
    with pytest.raises(obs.SensorUnavailable, match="chain exhausted"):
        chain.read_watts()
    with pytest.raises(obs.SensorUnavailable):
        obs.FallbackSensor([])


def test_fallback_from_specs_skips_dead_constructors(monkeypatch,
                                                     tmp_path):
    monkeypatch.setitem(sys.modules, "pynvml", None)
    plat = DVFSPlatform(energy.JETSON_AGX_ORIN)
    sink = io.StringIO()
    with obs.observing(sink):
        s = obs.make_sensor(
            f"fallback:nvml,replay:{tmp_path / 'missing.jsonl'},simulated",
            platform=plat)
    assert isinstance(s, obs.FallbackSensor)
    assert s.name.startswith("fallback:simulated:")
    assert s.read_watts() > 0.0
    skipped = [r for r in _rows(sink) if r["name"] == "fault.sensor"]
    assert len(skipped) == 2                 # nvml + missing trace
    assert all(r["attrs"]["phase"] == "construct" for r in skipped)
    with pytest.raises(obs.SensorUnavailable, match="no sensor in the"):
        obs.make_sensor("fallback:nvml,sysfs")
    # metering a degrading chain surfaces the exhaustion as sample
    # errors, never a dead thread
    dead = obs.FallbackSensor([_FaultySensor(raise_at=set(range(100)))])
    bench = _Bench(None)
    m = obs.EnergyMeter(dead, clock=bench.clock, background=False)
    with m.measure() as meas:
        bench.t = 1.0
    assert meas.sample_errors == 2 and meas.n_samples == 0


# ---------------------------------------------------------------------------
# Engine bit-identity: sensor=None vs sensor="simulated"
# ---------------------------------------------------------------------------


def _stub_engine(vocab=64):
    return types.SimpleNamespace(
        bundle=types.SimpleNamespace(
            cfg=types.SimpleNamespace(vocab_size=vocab)),
        generate=lambda prompts, mnt: (
            None, EngineStats(prefill_s=0.25, decode_s=0.75,
                              tokens_out=len(prompts) * mnt)))


def test_engine_env_bit_identical_with_simulated_sensor():
    board = energy.JETSON_AGX_ORIN
    work = energy.ORIN_WORKLOADS["llama3.2-1b"]
    mk = lambda sensor: EngineEnvironment(  # noqa: E731
        _stub_engine(), board, work, seed=7, sensor=sensor)
    plain, metered = mk(None), mk("simulated")
    for knobs in ({"freq_mhz": board.freqs_mhz[2], "batch": 8},
                  {"freq_mhz": board.freqs_mhz[-1], "batch": 16}):
        a = plain.pull(knobs, 0)
        b = metered.pull(knobs, 0)
        assert (a.energy, a.latency, a.power) == (b.energy, b.latency,
                                                  b.power)
        assert a.batch_time == b.batch_time
        # the metered pull additionally reports the measurement
        assert b.metadata["sensor"].startswith("simulated:")
        assert b.metadata["sensor_samples"] >= 2
        assert b.metadata["sensor_peak_w"] == a.power


# ---------------------------------------------------------------------------
# Metrics + tracing
# ---------------------------------------------------------------------------


def test_metrics_registry_counters_gauges_histograms():
    reg = obs.MetricsRegistry()
    reg.counter("pulls_total").inc()
    reg.counter("pulls_total").inc(2)
    reg.gauge("clock_s").set(3.5)
    h = reg.histogram("edp")
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    snap = {(r["name"], r["metric_type"]): r for r in reg.snapshot()}
    assert snap[("pulls_total", "counter")]["value"] == 3
    assert snap[("clock_s", "gauge")]["value"] == 3.5
    hist = snap[("edp", "histogram")]
    assert hist["count"] == 3 and hist["min"] == 0.5 and hist["max"] == 50.0
    with pytest.raises(TypeError):
        reg.counter("clock_s")               # name already a gauge


def test_emit_without_session_is_noop():
    assert not tracing_mod.active()
    tracing_mod.emit("pull", arm=1)          # must not raise


def test_observing_writes_events_spans_and_metrics(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with obs.observing(path) as session:
        obs.emit("round.start", round=0, width=4)
        obs.emit("pull", arm=3, energy_j=1.5, latency_s=2.0, edp=3.0,
                 cost=0.5, knobs={"batch": 8})
        session.emit("round", kind="span", dur_s=0.25, round=0, width=4)
    assert not tracing_mod.active()          # session restored
    rows = [json.loads(line) for line in open(path)]
    kinds = {r["kind"] for r in rows}
    assert kinds == {"event", "span", "metric"}
    pull = next(r for r in rows if r["name"] == "pull")
    assert pull["attrs"]["edp"] == 3.0
    metrics = {r["name"]: r for r in rows if r["kind"] == "metric"}
    assert metrics["pulls_total"]["value"] == 1
    assert metrics["pull_edp"]["count"] == 1
    assert metrics["rounds_total"]["value"] == 1
    assert metrics["events_total.round"]["value"] == 1


def test_span_nesting_records_parent_ids_and_rid():
    sink = io.StringIO()
    with obs.observing(sink):
        with tracing_mod.span("outer", phase=1):
            with tracing_mod.span("inner", rid=7, slot=2):
                with tracing_mod.span("leaf"):
                    pass
            with tracing_mod.span("sibling"):
                pass
        with tracing_mod.span("root2"):
            pass
    rows = {r["name"]: r for r in _rows(sink) if r["kind"] == "span"}
    assert rows["outer"]["parent"] is None
    assert rows["inner"]["parent"] == rows["outer"]["id"]
    assert rows["leaf"]["parent"] == rows["inner"]["id"]
    assert rows["sibling"]["parent"] == rows["outer"]["id"]
    assert rows["root2"]["parent"] is None
    assert len({r["id"] for r in rows.values()}) == 5
    assert rows["inner"]["rid"] == 7 and "rid" not in rows["leaf"]
    assert rows["inner"]["attrs"] == {"slot": 2}
    assert rows["outer"]["attrs"] == {"phase": 1}
    for r in rows.values():
        assert 0.0 <= r["start"] <= r["ts"]
        assert r["dur_s"] == pytest.approx(r["ts"] - r["start"], abs=1e-8)
    # rows are appended at each span's end
    order = [r["name"] for r in _rows(sink) if r["kind"] == "span"]
    assert order == ["leaf", "inner", "sibling", "outer", "root2"]


def test_span_without_session_records_no_row():
    acc = tracing_mod.PhaseTimes()
    assert not tracing_mod.active()
    with tracing_mod.span("engine.chunk.wait", acc=acc) as sp:
        pass
    with tracing_mod.span("engine.chunk.wait", acc=acc, steps=3):
        pass
    tracing_mod.record_span("engine.request", 0.0, 1.0, rid=1)
    assert sp.t1 >= sp.t0 > 0.0
    assert acc.n == {"engine.chunk.wait": 2}
    assert acc.s["engine.chunk.wait"] >= sp.t1 - sp.t0
    sink = io.StringIO()
    with obs.observing(sink):       # a later session sees none of them
        pass
    assert [r for r in _rows(sink) if r["kind"] != "metric"] == []


def test_span_start_tiles_consecutive_phases():
    acc = tracing_mod.PhaseTimes()
    with tracing_mod.span("a", acc=acc) as a:
        pass
    with tracing_mod.span("b", acc=acc, start=a.t1) as b:
        pass
    with tracing_mod.span("c", acc=acc, start=b.t1, steps=2) as c:
        pass
    assert b.t0 == a.t1 and c.t0 == b.t1
    assert sum(acc.s.values()) == pytest.approx(c.t1 - a.t0, rel=1e-12)
    assert c.attrs == {"steps": 2}


def test_session_buffers_rows_until_close():
    sink = io.StringIO()
    with obs.observing(sink) as sess:
        obs.emit("round.start", round=0)
        with tracing_mod.span("engine.bookkeep"):
            pass
        t = tracing_mod.CLOCK()
        tracing_mod.record_span("engine.request", t - 0.5, t, rid=4,
                                tokens=3)
        assert sink.getvalue() == ""          # nothing written yet
    rows = _rows(sink)
    assert [r["name"] for r in rows if r["kind"] != "metric"] == [
        "round.start", "engine.bookkeep", "engine.request"]
    req = rows[2]
    assert req["rid"] == 4 and req["parent"] is None
    assert req["dur_s"] == pytest.approx(0.5)
    assert req["start"] == pytest.approx(t - 0.5 - sess.t0, abs=1e-8)
    metrics = {r["name"]: r for r in rows if r["kind"] == "metric"}
    assert metrics["engine_requests_total"]["value"] == 1
    assert metrics["events_total.engine.bookkeep"]["value"] == 1
    assert "engine.tokens_per_s" not in metrics
    sess.close()                               # idempotent
    assert len(_rows(sink)) == len(rows)


def test_controller_run_produces_queryable_trace(tmp_path):
    name = "jetson/llama3.2-1b/landscape"
    space = make_space(name)
    cm = cost.CostModel(alpha=0.5)
    env0 = make_env(name, noise=0.0)
    e_ref, l_ref = env0.expected(space.values(space.corner()))
    cm = cm.with_reference(e_ref, l_ref)
    _, mu0, sig0 = priors.jetson_camel_policy("llama3.2-1b", space)
    mk_policy = lambda: baselines.make_policy(  # noqa: E731
        "camel", prior_mu=mu0, prior_sigma=sig0)

    path = str(tmp_path / "run.jsonl")
    ctrl = controller.BatchController(space, mk_policy(), cm, seed=0, k=4)
    with obs.observing(path):
        res = ctrl.run(make_env(name, noise=0.0, seed=0), 3)
    rows = [json.loads(line) for line in open(path)]
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r)
    assert len(by_name["round.start"]) == 3
    assert len(by_name["pull"]) == 12        # 3 rounds x k=4
    assert len(by_name["update"]) == 3
    assert len(by_name["commit"]) == 1
    assert len(by_name["round"]) == 3        # spans with real durations
    assert all(r["kind"] == "span" and r["dur_s"] >= 0
               for r in by_name["round"])
    for r in by_name["pull"]:
        a = r["attrs"]
        assert a["edp"] == pytest.approx(a["energy_j"] * a["latency_s"])
        assert set(a["knobs"]) == {"freq_mhz", "batch"}
    assert by_name["commit"][0]["attrs"]["best_arm"] == res.best_arm
    # the same run, untraced, is bit-identical (observability is passive)
    res2 = controller.BatchController(space, mk_policy(), cm, seed=0, k=4) \
        .run(make_env(name, noise=0.0, seed=0), 3)
    assert res2.best_arm == res.best_arm
    np.testing.assert_array_equal(res2.cum_regret, res.cum_regret)


def test_trace_report_renders_per_arm_table(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "t.jsonl")
    with obs.observing(path):
        for arm, e, l in ((3, 2.0, 1.0), (3, 4.0, 2.0), (7, 1.0, 1.0)):
            obs.emit("pull", arm=arm, energy_j=e, latency_s=l, edp=e * l,
                     cost=e * l, knobs={"batch": arm})
        obs.emit("commit", best_arm=7, knobs={"batch": 7}, n_pulls=3)
    text = trace_report.report(path)
    assert "per-arm summary (3 pulls, 2 distinct arms" in text
    assert "committed: arm 7 (batch=7)" in text
    marked = [ln for ln in text.splitlines()
              if ln.lstrip().startswith("*")]
    assert len(marked) == 1 and " 7 " in marked[0]   # committed arm marked
    assert "metrics snapshot:" in text


def test_trace_report_blank_cells_for_missing_metadata(tmp_path):
    """Pulls without tokens_per_s/cost (non-engine backends) and multiple
    arms with no cost at all must render blank cells, never crash on a
    missing key or a None comparison in the sort."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "t.jsonl")
    with obs.observing(path):
        # two cost-less arms force the None-None sort comparison; no
        # pull carries tokens_per_s or power_w
        obs.emit("pull", arm=1, energy_j=2.0, latency_s=1.0,
                 knobs={"batch": 1})
        obs.emit("pull", arm=2, energy_j=3.0, latency_s=1.5,
                 knobs={"batch": 2})
        obs.emit("pull", arm=0, energy_j=1.0, latency_s=0.5, cost=0.5,
                 edp=0.5, knobs={"batch": 4})
    text = trace_report.report(path)
    assert "per-arm summary (3 pulls, 3 distinct arms" in text
    arm_rows = [ln for ln in text.splitlines()
                if ln.lstrip().lstrip("*").strip()[:1].isdigit()
                and "batch=" in ln]
    assert len(arm_rows) == 3
    for row in arm_rows[1:]:          # the two cost-less arms
        assert "-" in row             # blank cells, not a crash


def test_trace_report_renders_per_request_table(tmp_path):
    """engine.request spans (continuous batching) get a per-request
    table; requests missing optional attrs render blank cells."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "t.jsonl")
    with obs.observing(path):
        obs.emit("engine.request", dur_s=1.5, rid=0, slot=1,
                 tokens=8, prompt_len=5, queue_wait_s=0.25)
        obs.emit("engine.request", dur_s=0.5, rid=1)
    text = trace_report.report(path)
    assert "per-request summary (2 requests)" in text
    lines = text.splitlines()
    row0 = next(ln for ln in lines if ln.strip().startswith("0"))
    assert "8" in row0 and "0.25" in row0 and "1.5" in row0
    row1 = next(ln for ln in lines if ln.strip().startswith("1 "))
    assert "-" in row1                # missing attrs -> blank cells
    assert "0.5" in row1              # but the span duration renders
    # metrics derived from the spans (counter + latency histogram)
    assert "engine_requests_total" in text


def test_trace_report_request_table_reads_wall_stamps(tmp_path):
    """`engine.request` spans as the engine records them: rid at the top
    level, wait and time to first token in the attributes, latency the
    span's own duration on the span clock."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "t.jsonl")
    with obs.observing(path):
        t = tracing_mod.CLOCK()
        tracing_mod.record_span("engine.request", t - 2.0, t, rid=5,
                                slot=0, tokens=9, prompt_len=3,
                                queue_wait_s=0.75, ttft_s=1.25,
                                cancelled=False)
    text = trace_report.report(path)
    assert "per-request summary (1 requests)" in text
    assert "mean ttft 1.25 s" in text
    row = next(ln for ln in text.splitlines() if ln.strip().startswith("5"))
    assert row.split() == ["5", "0", "3", "9", "0.75", "1.25", "2"]
