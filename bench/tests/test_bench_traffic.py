"""The traffic generator (`bench/traffic.py`) and its mix files."""

import glob
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import traffic  # noqa: E402

MIXES = sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json")))
BIG_SEED = 2 ** 31 + 12345


def _mix(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_deterministic_per_seed(path):
    mix = _mix(path)
    a = traffic.generate(mix, BIG_SEED, 10, 151936)
    b = traffic.generate(mix, BIG_SEED, 10, 151936)
    c = traffic.generate(mix, BIG_SEED + 1, 10, 151936)
    key = lambda rs: [(r["max_new_tokens"], r["arrival_s"],  # noqa: E731
                       r["prompt"].tolist()) for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_same_sizes_every_seed_within_clips(path):
    mix = _mix(path)
    sizes = None
    for seed in (1, 7, BIG_SEED, -3):
        reqs = traffic.generate(mix, seed, 10, 151936)
        assert len(reqs) == traffic.n_requests(mix, 10)
        p = sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs)
        sizes = sizes or p
        assert p == sizes
        for r in reqs:
            assert mix["prompt"]["min"] <= len(r["prompt"]) \
                <= mix["prompt"]["max"]
            assert mix["output"]["min"] <= r["max_new_tokens"] \
                <= mix["output"]["max"]
            assert r["prompt"].dtype == np.int32
            assert 1 <= r["prompt"].min() and r["prompt"].max() < 151936
        arr = [r["arrival_s"] for r in reqs]
        if mix["arrivals"] == "backlog":
            assert set(arr) == {0.0}
        else:
            gaps = np.diff([0.0] + sorted(arr))
            assert np.all(gaps > 0)
            assert max(arr) == pytest.approx(len(reqs) / mix["rate_rps"])


@pytest.mark.parametrize("name", ["qwen2-1.5b", "qwen2.5-3b"])
def test_every_mix_fits_the_engine(name):
    """Every request of every mix passes the scheduler's validation at the
    configuration's engine shapes."""
    from repro.serving.scheduler import EngineRequest, SlotScheduler

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        eng = json.load(f)["engine"]
    sched = SlotScheduler(eng["n_slots"], eng["max_seq_len"],
                          eng["prompt_bucket"])
    for path in MIXES:
        for r in traffic.generate(_mix(path), 3, 10, 151936):
            sched.validate_request(EngineRequest(
                rid=r["rid"], prompt=r["prompt"],
                max_new_tokens=r["max_new_tokens"]))


def test_lengths_follow_their_distribution():
    spec = {"dist": "lognormal", "median": 128, "sigma": 0.5, "min": 1,
            "max": 10 ** 6}
    x = traffic.lengths(spec, 1001)
    assert np.median(x) == 128
    assert np.percentile(x, 25) == pytest.approx(
        128 * np.exp(-0.6745 * 0.5), abs=1)
    with pytest.raises(ValueError):
        traffic.lengths(dict(spec, dist="uniform"), 10)
