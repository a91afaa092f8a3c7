"""Serving driver: the paper's full loop (Fig. 2) end to end.

All backends are constructed through the `repro.platform` registry
(`make_env` / `make_space`), so each mode is just: name an environment,
normalize the cost model at the reference corner, run the controller.

Modes:
  --mode search    Camel vs. grid configuration search on the calibrated
                   Jetson landscapes (paper Results 1); --k > 1 runs the
                   batched controller (K concurrent arms per round through
                   the vectorized pull_many hook)
  --mode validate  event-driven serving of N requests at the found optimal
                   vs. the three default corners (paper Results 2)
  --mode engine    Camel drives the *real* JAX engine — the arm's
                   batch/frequency change actual batched inference
                   calls; --preset smoke (default, the CPU demo model)
                   or --preset published (the arch's published widths,
                   sized for an accelerator)
  --mode tpu       Camel on the TPU v5e roofline-derived landscape
                   (DESIGN.md SS3 adaptation; per --arch)
  --mode fleet     batched Camel over a --fleet-size device fleet behind
                   one shared arrival queue (fleet/<n>xjetson registry
                   platform), K = fleet size slots per round; --rounds is
                   the *exact* pull budget in every mode (the final round
                   truncates to the remaining budget).  --policy
                   contextual swaps in device-contextual Thompson
                   sampling: per-device additive cost offsets learned
                   from obs.metadata["device"], so persistent fleet
                   heterogeneity (speed/power jitter) stops biasing the
                   shared posterior's commit
  --mode async-fleet  the same fleet without the round barrier: K arms in
                   flight through the completion-ordered dispatcher,
                   per-completion staleness-aware posterior updates;
                   --straggler S makes device 0 return results S x slower
                   (its telemetry is unchanged — the pulls just arrive
                   late and stale).  Reports the simulated wall-clock and
                   the staleness distribution alongside the usual summary.

Observability (any mode):
  --metrics-out PATH   open a `repro.obs` session for the run: the
                   instrumented seams (controller rounds/pulls/updates/
                   commit, async dispatcher submits/waves, engine
                   prefill/decode) write a queryable JSONL event trace
                   with per-pull energy/latency/EDP, and the metrics
                   snapshot is appended on exit.  Summarize it with
                   `tools/trace_report.py PATH`.
  --sensor SPEC    power source: `simulated` (default — the analytical
                   `Platform.power`, bit-identical to not sensing),
                   `sysfs` (Jetson INA3221 rails), `nvml`,
                   `replay:<path>` (deterministic JSONL trace),
                   `record:<path>` (capture a trace), or
                   `fallback:a,b,...` (degrade down a chain on sensor
                   failure).  Engine mode meters every pull with the
                   sensor; other modes meter the whole run with
                   non-simulated sensors and report the measurement
                   under a `sensor` output key + a `sensor.run` trace
                   event.
  --faults SPEC    seeded fault injection (`repro.faults.parse_faults`
                   grammar, e.g. ``pull_fail=0.2,crash=0@4,deadline=4``):
                   fleet modes run behind the fault-wrapping fleet env
                   (crashed/throttled devices, flaky pulls, dispatcher
                   deadlines + retries), engine mode stamps request
                   deadlines/cancellations and wraps the power sensor,
                   and any run-level sensor becomes flaky.  The empty/
                   ``none`` spec is a no-op (bit-identical run).  See
                   docs/RESILIENCE.md.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --mode search \
        --model llama3.2-1b --rounds 49
    PYTHONPATH=src python -m repro.launch.serve --mode fleet \
        --model llama3.2-1b --fleet-size 4 --rounds 49 --policy contextual
    PYTHONPATH=src python -m repro.launch.serve --mode async-fleet \
        --model llama3.2-1b --fleet-size 4 --rounds 49 --straggler 4 \
        --metrics-out trace.jsonl
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math

from repro import obs as obs_mod
from repro.core import baselines, controller, cost, priors
from repro.faults import parse_faults, wrap_env, wrap_sensor
from repro.launch.compile_cache import enable_compile_cache
from repro.platform import make_env, make_space
from repro.platform.registry import ENGINE_PRESETS
from repro.serving import energy as energy_mod
from repro.serving import simulator as sim_mod
from repro.serving.requests import ArrivalProcess


def search_mode(model: str, rounds: int, alpha: float, seed: int,
                policy_name: str = "camel", k: int = 1) -> dict:
    """`rounds` is the pull budget; with k > 1 it is served in
    ceil(rounds / k) batched rounds of K concurrent evaluations, the
    final round truncated so exactly `rounds` pulls run."""
    name = f"jetson/{model}/landscape"
    env = make_env(name, noise=0.03, seed=seed)
    space = make_space(name)
    cm = cost.CostModel(alpha=alpha)
    e_ref, l_ref = env.expected(space.values(space.corner()))
    cm = cm.with_reference(e_ref, l_ref)
    opt_arm, opt_cost = controller.landscape_optimal(space, env.expected, cm)

    if policy_name == "camel":
        policy, _, _ = priors.jetson_camel_policy(model, space, alpha)
    else:
        policy = baselines.make_policy(policy_name)

    ctrl = controller.BatchController(space, policy, cm,
                                      optimal_cost=opt_cost, seed=seed, k=k)
    res = ctrl.run(env, max(1, math.ceil(rounds / k)), pull_budget=rounds)
    summary = res.summary()
    summary["optimal_knobs"] = space.values(opt_arm)
    summary["found_optimal"] = bool(res.best_arm == opt_arm)
    summary["k"] = k
    summary["n_rounds"] = res.n_rounds
    summary["n_pulls"] = len(res.records)
    return summary


def validate_mode(model: str, n_requests: int, alpha: float, seed: int,
                  ) -> dict:
    name = f"jetson/{model}/landscape"
    env = make_env(name, noise=0.0)
    space = make_space(name)
    cm = cost.CostModel(alpha=alpha)
    e_ref, l_ref = env.expected(space.values(space.corner()))
    cm = cm.with_reference(e_ref, l_ref)
    opt_arm, _ = controller.landscape_optimal(space, env.expected, cm)

    board = energy_mod.JETSON_AGX_ORIN
    work = energy_mod.ORIN_WORKLOADS[model]
    configs = {
        "camel_optimal": space.values(opt_arm),
        "maxf_minb": space.values(space.corner(batch="min")),
        "maxf_maxb": space.values(space.corner()),
        "minf_maxb": space.values(space.corner(freq_mhz="min")),
    }
    out = {}
    for cname, knobs in configs.items():
        server = sim_mod.EventDrivenServer(
            board, work, ArrivalProcess(interval_s=1.0, seed=seed),
            n_requests, noise=0.02, seed=seed)
        res = server.run(sim_mod.fixed_config_tuner(knobs["freq_mhz"],
                                                    knobs["batch"]))
        s = res.summary()
        s["knobs"] = knobs
        s["cost"] = float(cm.cost(s["energy_per_req"], s["latency_per_req"]))
        out[cname] = s
    base = out["maxf_maxb"]["edp"]
    for cname in configs:
        out[cname]["edp_vs_maxf_maxb"] = 1.0 - out[cname]["edp"] / base
    return out


def engine_mode(arch: str, rounds: int, alpha: float, seed: int,
                sensor: str = "simulated",
                scheduler: str = "static", faults=None,
                preset: str = "smoke") -> dict:
    """`sensor` selects the per-pull power source (`repro.obs.make_sensor`
    spec): every engine pull is metered through it.  The default
    "simulated" sensor reads the same analytical board model the
    unmetered path evaluates, bit-identically.  `scheduler` picks the
    traffic each pull serves: "static" (one fixed batch) or
    "continuous" (Poisson arrivals with ragged output lengths — the
    batch arm becomes max concurrency); both run the engine's one
    slot-pool decode loop.
    `preset` picks the model: "smoke" (reduced, CPU-sized) or
    "published" (the architecture's published widths)."""
    name = f"engine/{arch}"
    env = make_env(name, preset=preset, seed=seed, prompt_len=16,
                   max_new_tokens=8, sensor=sensor, scheduler=scheduler,
                   faults=faults)
    space = make_space(name)
    cm = cost.CostModel(alpha=alpha)
    e0, l0 = env.pull(space.values(space.corner()), 0)
    cm = cm.with_reference(e0, l0)
    policy = baselines.make_policy("camel", prior_mu=1.0, prior_sigma=0.1)
    ctrl = controller.Controller(space, policy, cm, seed=seed)
    res = ctrl.run(env, rounds)
    out = res.summary()
    out["preset"] = preset
    out["n_pulls"] = len(res.records)
    return out


def tpu_mode(arch: str, rounds: int, alpha: float, seed: int) -> dict:
    name = f"tpu-v5e/{arch}/landscape"
    env = make_env(name, noise=0.03, seed=seed)
    space = make_space(name)
    cm = cost.CostModel(alpha=alpha)
    e_ref, l_ref = env.expected(space.values(space.corner()))
    cm = cm.with_reference(e_ref, l_ref)
    opt_arm, opt_cost = controller.landscape_optimal(space, env.expected, cm)
    policy = baselines.make_policy("camel", prior_mu=1.0, prior_sigma=0.1)
    ctrl = controller.Controller(space, policy, cm, optimal_cost=opt_cost,
                                 seed=seed)
    res = ctrl.run(env, rounds)
    out = res.summary()
    out["optimal_knobs"] = space.values(opt_arm)
    return out


def _fleet_policy(policy_name: str, model: str, space, alpha: float,
                  n_devices: int):
    """Resolve a fleet-mode policy name.  "camel" and "contextual" share
    the analytic Camel prior; "contextual" additionally learns per-device
    additive offsets (`bandit.ContextualTS`) from the device ids the
    fleet stamps on every observation — prefer it whenever the fleet is
    heterogeneous (speed/power jitter)."""
    if policy_name == "contextual":
        return priors.jetson_contextual_policy(model, space, n_devices,
                                               alpha)[0]
    if policy_name == "camel":
        return priors.jetson_camel_policy(model, space, alpha)[0]
    return baselines.make_policy(policy_name)


def fleet_mode(model: str, rounds: int, alpha: float, seed: int,
               n_devices: int, k: int = 0,
               policy_name: str = "camel", faults=None) -> dict:
    """Batched Camel search over an N-device fleet: K slots per round
    (default: one per device) dispatched across the fleet's shared
    arrival queue; one delayed posterior update per round.  `rounds` is
    the pull budget, served in ceil(rounds / k) K-wide rounds with the
    final round truncated to the remaining budget — the same exact-budget
    semantics as every other mode.  `--policy contextual` swaps in the
    device-contextual sampler (per-device offsets; see
    docs/ENVIRONMENTS.md)."""
    k = k if k > 0 else n_devices
    name = f"fleet/{n_devices}xjetson/{model}/landscape"
    env = make_env(name, noise=0.03, seed=seed)
    space = make_space(name)
    cm = cost.CostModel(alpha=alpha)
    e_ref, l_ref = env.expected(space.values(space.corner()))
    cm = cm.with_reference(e_ref, l_ref)
    opt_arm, opt_cost = controller.landscape_optimal(space, env.expected, cm)

    policy = _fleet_policy(policy_name, model, space, alpha, n_devices)
    ctrl = controller.BatchController(space, policy, cm,
                                      optimal_cost=opt_cost, seed=seed, k=k)
    # Faults wrap the *run* env only; the analytic reference (e_ref,
    # optimal landscape) above stays fault-free.
    run_env = wrap_env(env, faults) if faults is not None else env
    res = ctrl.run(run_env, max(1, math.ceil(rounds / k)),
                   pull_budget=rounds)
    out = res.summary()
    out["optimal_knobs"] = space.values(opt_arm)
    out["found_optimal"] = bool(res.best_arm == opt_arm)
    out["n_devices"] = n_devices
    out["k"] = k
    out["policy"] = policy_name
    out["n_rounds"] = res.n_rounds
    out["n_pulls"] = len(res.records)
    return out


def async_fleet_mode(model: str, rounds: int, alpha: float, seed: int,
                     n_devices: int, k: int = 0, straggler: float = 1.0,
                     policy_name: str = "camel", faults=None) -> dict:
    """Asynchronous Camel search over an N-device fleet: K arms in flight
    through the completion-ordered dispatcher (default K = fleet size),
    per-completion staleness-aware posterior updates instead of a round
    barrier.  `straggler` slows device 0's *completions* by that factor
    without changing its telemetry; `rounds` is the exact pull budget, as
    in every other mode; `--policy contextual` applies each completion's
    device context through the widened `update_stale(..., device=)`."""
    k = k if k > 0 else n_devices
    name = f"fleet/{n_devices}xjetson/{model}/landscape"
    dispatch = (straggler,) + (1.0,) * (n_devices - 1)
    env_kw = dict(noise=0.03, seed=seed, dispatch_factors=dispatch)
    env = make_env(name, **env_kw)
    space = make_space(name)
    cm = cost.CostModel(alpha=alpha)
    e_ref, l_ref = env.expected(space.values(space.corner()))
    cm = cm.with_reference(e_ref, l_ref)
    opt_arm, opt_cost = controller.landscape_optimal(space, env.expected, cm)

    policy = _fleet_policy(policy_name, model, space, alpha, n_devices)
    ctrl = controller.AsyncController(space, policy, cm,
                                      optimal_cost=opt_cost, seed=seed, k=k)
    run_env = make_env(name, **env_kw)
    if faults is not None:
        # Chaos wraps the run env only (injected pull faults, device
        # crashes/throttles, dispatcher deadlines + retries); the
        # analytic reference above stays fault-free.
        run_env = wrap_env(run_env, faults)
    res = ctrl.run(run_env, max(1, math.ceil(rounds / k)),
                   pull_budget=rounds)
    out = res.summary()
    staleness = [r.obs.metadata["staleness"] for r in res.records]
    out["optimal_knobs"] = space.values(opt_arm)
    out["found_optimal"] = bool(res.best_arm == opt_arm)
    out["n_devices"] = n_devices
    out["k"] = k
    out["policy"] = policy_name
    out["straggler"] = straggler
    out["n_waves"] = res.n_rounds
    out["n_pulls"] = len(res.records)
    out["wall_clock_sim_s"] = float(
        res.records[-1].obs.metadata["finished_at"]) if res.records else 0.0
    out["mean_staleness"] = (float(sum(staleness) / len(staleness))
                             if staleness else 0.0)
    out["max_staleness"] = int(max(staleness)) if staleness else 0
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["search", "validate", "engine",
                                       "tpu", "fleet", "async-fleet"],
                    default="search")
    ap.add_argument("--model", default="llama3.2-1b")
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--rounds", type=int, default=49)
    ap.add_argument("--requests", type=int, default=2500)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=0,
                    help="arms evaluated concurrently per round (batched "
                         "Thompson sampling); 0 = auto (1, or the fleet "
                         "size in fleet mode)")
    ap.add_argument("--fleet-size", type=int, default=4)
    ap.add_argument("--policy", default="camel",
                    choices=sorted(baselines.POLICIES),
                    help="search policy; 'contextual' (fleet modes only) "
                         "learns per-device cost offsets so heterogeneous "
                         "fleets commit on the fleet-level optimum")
    ap.add_argument("--straggler", type=float, default=1.0,
                    help="async-fleet: device 0 returns results this many "
                         "times slower (telemetry unchanged; 1.0 = "
                         "homogeneous)")
    ap.add_argument("--scheduler", default="static",
                    choices=["static", "continuous"],
                    help="engine mode serving discipline: static batches "
                         "or continuous (slot-level) batching")
    ap.add_argument("--preset", default="smoke", choices=ENGINE_PRESETS,
                    help="engine mode model: the reduced smoke config or "
                         "the architecture at its published widths")
    ap.add_argument("--sensor", default="simulated",
                    help="power source: simulated | sysfs | nvml | "
                         "replay:<path> | record:<path> (engine mode "
                         "meters every pull; other modes meter the whole "
                         "run for non-simulated sensors)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the run's JSONL event trace + metrics "
                         "snapshot here (summarize with "
                         "tools/trace_report.py)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="seeded fault injection spec, e.g. "
                         "'pull_fail=0.2,crash=0@4,deadline=4,seed=1' "
                         "(see docs/RESILIENCE.md); empty or 'none' "
                         "disables injection")
    args = ap.parse_args()
    enable_compile_cache()

    plan = parse_faults(args.faults) if args.faults else None
    if plan is not None and plan.is_zero:
        plan = None      # explicit no-op spec: keep the bit-identical path

    if args.policy == "contextual" and args.mode not in ("fleet",
                                                         "async-fleet"):
        ap.error("--policy contextual needs device context; use "
                 "--mode fleet or --mode async-fleet")

    def dispatch() -> dict:
        if args.mode == "search":
            return search_mode(args.model, args.rounds, args.alpha,
                               args.seed, policy_name=args.policy,
                               k=max(1, args.k))
        if args.mode == "validate":
            return validate_mode(args.model, args.requests, args.alpha,
                                 args.seed)
        if args.mode == "engine":
            return engine_mode(args.arch, args.rounds, args.alpha,
                               args.seed, sensor=args.sensor,
                               scheduler=args.scheduler, faults=plan,
                               preset=args.preset)
        if args.mode == "fleet":
            return fleet_mode(args.model, args.rounds, args.alpha,
                              args.seed, args.fleet_size, k=args.k,
                              policy_name=args.policy, faults=plan)
        if args.mode == "async-fleet":
            return async_fleet_mode(args.model, args.rounds, args.alpha,
                                    args.seed, args.fleet_size, k=args.k,
                                    straggler=args.straggler,
                                    policy_name=args.policy, faults=plan)
        return tpu_mode(args.arch, args.rounds, args.alpha, args.seed)

    session = obs_mod.observing(args.metrics_out) if args.metrics_out \
        else contextlib.nullcontext()
    with session:
        if args.sensor != "simulated" and args.mode != "engine":
            # Run-level host power measurement: the engine mode meters
            # per pull (the sensor goes into the environment); every
            # other backend is simulation-clocked, so the sensor meters
            # the whole search instead and its joules/avg/peak land in
            # the output and the trace.
            sensor = obs_mod.make_sensor(args.sensor)
            if plan is not None:
                sensor = wrap_sensor(sensor, plan)
            meter = obs_mod.EnergyMeter(sensor)
            try:
                with meter.measure() as m:
                    out = dispatch()
            finally:
                sensor.close()
            obs_mod.emit("sensor.run", **m.summary())
            out["sensor"] = m.summary()
        else:
            out = dispatch()
    if plan is not None:
        out["faults"] = args.faults
    print(json.dumps(out, indent=2, default=str))


if __name__ == "__main__":
    main()
