"""The repro.platform contract: Platform adapters, Observation telemetry,
the shared queueing-latency helper, and the environment registry across
all four backends."""

import numpy as np
import pytest

from repro.core import baselines, controller, cost
from repro.platform import (DVFSPlatform, Observation, TPUPlatform,
                            as_platform, available_envs, make_env,
                            make_space, observe, parse_name, pull_many,
                            queue_wait, queueing_latency,
                            saturation_backlog)
from repro.serving import energy


# ---------------------------------------------------------------------------
# Queueing-latency helper (the single copy of the wait+backlog model)
# ---------------------------------------------------------------------------


def test_queueing_latency_matches_energy_module_closed_form():
    board, work = energy.JETSON_AGX_ORIN, energy.LLAMA32_1B_ORIN
    for level in (0, 3, 6):
        for b in (4, 16, 28):
            tb = work.batch_time(board, level, b)
            q = queueing_latency(tb, b, arrival_rate=1.0, n_requests=2500)
            assert q.total == energy.mean_latency(board, work, level, b,
                                                  1.0, 2500)
            assert q.wait == queue_wait(b, 1.0)
            assert q.backlog == saturation_backlog(tb, b, 1.0, 2500)


def test_queueing_latency_single_batch_has_no_backlog():
    q = queueing_latency(100.0, 8, arrival_rate=1.0, n_requests=8)
    assert q.backlog == 0.0
    assert q.total == q.wait + 100.0


def test_queueing_latency_n_servers_drains_faster():
    slow = queueing_latency(30.0, 8, 1.0, 2500, n_servers=1)
    fast = queueing_latency(30.0, 8, 1.0, 2500, n_servers=4)
    assert fast.backlog < slow.backlog


@pytest.mark.parametrize("bad_rate", [0.0, -1.0, -0.5])
def test_queueing_model_rejects_nonpositive_arrival_rate(bad_rate):
    """lambda <= 0 must fail loudly at the seam (division by zero /
    negative waits would otherwise silently poison every cost)."""
    with pytest.raises(ValueError, match="arrival_rate must be positive"):
        queue_wait(8, bad_rate)
    with pytest.raises(ValueError, match="arrival_rate must be positive"):
        saturation_backlog(1.0, 8, bad_rate, 2500)
    with pytest.raises(ValueError, match="arrival_rate must be positive"):
        queueing_latency(1.0, 8, bad_rate)
    with pytest.raises(ValueError, match="arrival_rate must be positive"):
        observe(10.0, 1.0, 8, bad_rate)


# ---------------------------------------------------------------------------
# Observation
# ---------------------------------------------------------------------------


def test_observation_tuple_compat_and_coercion():
    obs = Observation(energy=2.0, latency=3.0)
    e, l = obs
    assert (e, l) == (2.0, 3.0)
    assert obs.edp == 6.0
    assert Observation.of((4.0, 5.0)).energy == 4.0
    assert Observation.of(obs) is obs


def test_observe_builds_consistent_record():
    obs = observe(power_w=50.0, batch_time_s=10.0, batch=20,
                  arrival_rate=1.0, n_requests=2500, tokens=1400,
                  metadata={"backend": "x"})
    assert obs.energy == 50.0 * 10.0 / 20.0
    assert obs.latency == obs.queue_wait + obs.batch_time + obs.backlog
    assert obs.power == 50.0 and obs.batch == 20 and obs.tokens == 1400
    assert obs.metadata["backend"] == "x"


def test_observation_scaled_noise_touches_only_headline_numbers():
    obs = observe(50.0, 10.0, 20, 1.0, 2500)
    noisy = obs.scaled(1.1, 0.9)
    assert np.isclose(noisy.energy, obs.energy * 1.1)
    assert np.isclose(noisy.latency, obs.latency * 0.9)
    assert noisy.batch_time == obs.batch_time
    assert noisy.power == obs.power


# ---------------------------------------------------------------------------
# Platform adapters
# ---------------------------------------------------------------------------


def test_dvfs_platform_adapter():
    p = DVFSPlatform(energy.JETSON_AGX_ORIN)
    assert p.knob_name == "freq_mhz"
    assert p.n_levels == 7
    assert p.levels[-1] == 930.75
    assert p.level_of(816.0) == 5
    assert p.power(5, 0.8) == energy.JETSON_AGX_ORIN.power(5, 0.8)
    p.set_level(2)
    assert p.current_level == 2
    with pytest.raises(ValueError):
        p.set_level(99)
    with pytest.raises(ValueError):
        p.level_of(123.4)


def test_tpu_platform_adapter():
    chip = energy.TPUChip()
    p = TPUPlatform(chip, compute_share=0.4)
    assert p.knob_name == "perf_state"
    assert p.n_levels == len(chip.perf_states)
    assert p.level_of(1.0) == p.n_levels - 1
    assert p.power(0, 0.9) == chip.power(chip.perf_states[0], 0.4, 0.9)
    # lower perf states draw less power at fixed share/util
    assert p.power(0) < p.power(p.n_levels - 1)


def test_as_platform_dispatch():
    assert isinstance(as_platform(energy.JETSON_AGX_ORIN), DVFSPlatform)
    assert isinstance(as_platform(energy.TPUChip()), TPUPlatform)
    p = DVFSPlatform(energy.JETSON_AGX_ORIN)
    assert as_platform(p) is p
    with pytest.raises(TypeError):
        as_platform(object())


# ---------------------------------------------------------------------------
# Registry: names, errors, arm -> env -> Observation round trips
# ---------------------------------------------------------------------------


def test_parse_name_and_available():
    assert parse_name("jetson/llama3.2-1b/landscape") == (
        "jetson", "llama3.2-1b", "landscape")
    assert parse_name("engine/smollm-360m") == ("engine", "smollm-360m",
                                                "live")
    # listings name concrete registered models, not a <model> placeholder
    assert "jetson/llama3.2-1b/landscape" in available_envs()
    assert "engine/smollm-360m/live" in available_envs()
    assert not any("<model>" in n for n in available_envs())


def test_registry_every_platform_has_model_lister():
    """Contract: each register_env'd platform also registers a `models=`
    lister, so available_envs() stays concrete and model typos fail with
    the real alternatives (docs/ENVIRONMENTS.md 'Adding a backend')."""
    from repro.platform import registry
    platforms = {p for (p, _scenario) in registry._BUILDERS}
    missing = sorted(platforms - set(registry._MODELS))
    assert not missing, \
        f"platforms registered without a models= lister: {missing}"
    for p in sorted(platforms):
        names = registry._MODELS[p]()
        assert names, f"platform {p!r} lister returned no models"
        assert all(isinstance(m, str) and m and "<" not in m
                   for m in names)


def test_registry_name_errors():
    with pytest.raises(KeyError, match="available"):
        make_env("mars/llama3.2-1b/landscape")
    with pytest.raises(KeyError, match="unknown jetson model"):
        make_env("jetson/not-a-model/landscape")
    with pytest.raises(KeyError, match="available"):
        make_env("jetson/llama3.2-1b/not-a-scenario")
    with pytest.raises(KeyError, match="omits the scenario"):
        make_env("jetson/llama3.2-1b")
    with pytest.raises(KeyError):
        make_env("toomany/parts/in/this/name")
    with pytest.raises(KeyError, match="unknown tpu-v5e model"):
        make_env("tpu-v5e/not-a-model/landscape")
    # model errors name the concrete alternatives
    with pytest.raises(KeyError, match="llama3.2-1b"):
        make_env("jetson/bogus/landscape")


@pytest.mark.parametrize("name,knob", [
    ("jetson/llama3.2-1b/landscape", "freq_mhz"),
    ("jetson/llama3.2-1b/events", "freq_mhz"),
    ("tpu-v5e/qwen2-1.5b/landscape", "perf_state"),
    ("tpu-v5e/qwen2-1.5b/elastic", "perf_state"),
])
def test_arm_to_env_to_observation_round_trip(name, knob):
    """Every registered simulator backend: arm index -> make_env -> pull
    -> full Observation with coherent telemetry."""
    kw = {"seed": 0}
    if "events" in name:
        kw["requests_per_pull"] = 40
    env = make_env(name, **kw)
    space = make_space(name)
    assert env.platform.knob_name == knob
    for arm in (0, space.n_arms // 2, space.n_arms - 1):
        knobs = space.values(arm)
        obs = env.pull(knobs, arm)
        assert isinstance(obs, Observation)
        assert obs.energy > 0 and obs.latency > 0
        assert obs.power > 0 and obs.batch == knobs["batch"]
        assert obs.tokens > 0
        assert "backend" in obs.metadata
        # the actuated level matches the pulled arm
        assert env.platform.current_level == env.platform.level_of(
            knobs[knob])
        e, l = obs                       # tuple contract still holds
        assert (e, l) == (obs.energy, obs.latency)


def test_engine_preset_is_validated():
    with pytest.raises(ValueError, match="preset"):
        make_env("engine/smollm-360m", preset="tiny")


def test_engine_round_trip():
    """arm -> make_env("engine/...") -> Observation through the real
    InferenceEngine (reduced smoke model on CPU)."""
    env = make_env("engine/smollm-360m", seed=0, prompt_len=8,
                   max_new_tokens=2, max_batch=8, max_seq_len=32)
    space = make_space("engine/smollm-360m")
    knobs = {"freq_mhz": 816.0, "batch": 4}
    obs = env.pull(knobs, 0)
    assert isinstance(obs, Observation)
    assert obs.energy > 0 and obs.latency > 0
    assert obs.backlog == 0.0            # single-batch live measurement
    assert obs.tokens == 4 * 2
    assert obs.metadata["backend"] == "engine"
    assert space.n_arms == 49


def test_events_env_backlog_only_when_saturated():
    """The measured latency decomposition must not report saturation
    backlog for configs whose service keeps up with arrivals, even with
    batch-time noise."""
    env = make_env("jetson/llama3.2-1b/events", requests_per_pull=60,
                   noise=0.02, seed=0)
    stable = env.pull({"freq_mhz": 816.0, "batch": 20}, 0)
    assert stable.backlog == 0.0
    assert np.isclose(stable.latency,
                      stable.queue_wait + stable.batch_time)
    # a genuinely saturated config (low freq, small batch) must show it
    env2 = make_env("jetson/qwen2.5-3b/events", requests_per_pull=60,
                    noise=0.02, seed=0)
    saturated = env2.pull({"freq_mhz": 306.0, "batch": 4}, 0)
    assert saturated.backlog > 1.0


def test_landscape_env_expected_unchanged_by_pull_noise():
    env = make_env("jetson/llama3.2-1b/landscape", noise=0.0, seed=0)
    knobs = {"freq_mhz": 816.0, "batch": 20}
    a = env.pull(knobs, 0)
    b = env.expected(knobs)
    assert (a.energy, a.latency) == (b.energy, b.latency)


def test_pull_many_matches_sequential_pulls():
    """The landscape env's vectorized pull_many (one jitted f32 evaluation)
    consumes the same noise stream as sequential pulls and agrees with the
    scalar f64 path to float32 precision."""
    env_a = make_env("jetson/llama3.2-1b/landscape", noise=0.03, seed=7)
    env_b = make_env("jetson/llama3.2-1b/landscape", noise=0.03, seed=7)
    space = make_space("jetson/llama3.2-1b/landscape")
    knob_list = [space.values(a) for a in range(5)]
    batched = pull_many(env_a, knob_list)
    sequential = [env_b.pull(k, i) for i, k in enumerate(knob_list)]
    assert all(o.metadata.get("vectorized") for o in batched)
    np.testing.assert_allclose(
        [(o.energy, o.latency) for o in batched],
        [(o.energy, o.latency) for o in sequential], rtol=1e-5)


def test_pull_many_fallback_for_plain_envs():
    class Minimal:
        def pull(self, knobs, round_index):
            return (float(knobs["batch"]), float(round_index + 1))

    out = pull_many(Minimal(), [{"batch": 4}, {"batch": 8}], round_index=3)
    assert [o.energy for o in out] == [4.0, 8.0]
    assert [o.latency for o in out] == [4.0, 5.0]
    assert all(isinstance(o, Observation) for o in out)


# ---------------------------------------------------------------------------
# Controller integration: Observation-based summaries
# ---------------------------------------------------------------------------


def test_controller_summary_parity_and_telemetry():
    """ControllerResult.summary() over Observation-returning envs keeps the
    old scalar keys (identical to recomputing from records) and adds the
    telemetry aggregates."""
    name = "jetson/llama3.2-1b/landscape"
    env = make_env(name, noise=0.03, seed=0)
    space = make_space(name)
    cm = cost.CostModel(alpha=0.5)
    e_ref, l_ref = env.expected(space.values(space.corner()))
    cm = cm.with_reference(e_ref, l_ref)
    ctrl = controller.Controller(
        space, baselines.make_policy("camel", prior_mu=1.0,
                                     prior_sigma=0.1), cm, seed=0)
    res = ctrl.run(make_env(name, noise=0.03, seed=0), 20)
    s = res.summary()

    # scalar-path parity: the headline keys recompute from the records
    e = np.array([r.energy for r in res.records])
    l = np.array([r.latency for r in res.records])
    assert np.isclose(s["energy_per_req"], e.mean())
    assert np.isclose(s["latency_per_req"], l.mean())
    assert np.isclose(s["edp"], (e * l).mean())

    # telemetry aggregates present and coherent
    assert s["mean_power_w"] > 0
    assert s["mean_batch_time_s"] > 0
    assert s["total_tokens"] > 0
    assert 0 <= s["saturated_rounds"] <= 20
    for r in res.records:
        assert isinstance(r.obs, Observation)
        assert r.energy == r.obs.energy


def test_controller_accepts_legacy_tuple_env():
    """Environments that still return bare (energy, latency) pairs keep
    working through Observation.of coercion."""
    class TupleEnv:
        def pull(self, knobs, round_index):
            return (1.0 + knobs["batch"] / 28.0, 2.0)

    space = make_space("jetson/llama3.2-1b/landscape")
    cm = cost.CostModel(alpha=0.5)
    ctrl = controller.Controller(
        space, baselines.make_policy("camel", prior_mu=1.0,
                                     prior_sigma=0.1), cm, seed=0)
    res = ctrl.run(TupleEnv(), 5)
    s = res.summary()
    assert s["latency_per_req"] == 2.0
    assert "mean_power_w" in s           # obs coerced, power defaults to 0
    assert s["mean_power_w"] == 0.0
