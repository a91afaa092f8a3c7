"""Environment registry: construct any Camel backend by name.

Names follow ``<platform>/<model>/<scenario>``:

    jetson/llama3.2-1b/landscape     closed-form Jetson landscape + noise
    jetson/qwen2.5-3b/events         event-driven simulation per pull
    tpu-v5e/qwen2-1.5b/landscape     roofline-derived TPU decode landscape
    tpu-v5e/qwen2-1.5b/elastic       + mesh-slice width third knob
    engine/smollm-360m               real InferenceEngine (scenario "live"
                                     implied; "engine/<arch>/live" also ok;
                                     preset="published" builds the
                                     published widths, default "smoke")

plus the composite fleet form ``fleet/<n>x<platform>/<model>/<scenario>``
(e.g. ``fleet/4xjetson/llama3.2-1b/landscape``): N devices of the named
backend behind one shared arrival queue, with per-device jitter knobs —
see `repro.platform.fleet`.

`make_env` returns the environment; `make_space` the matching ArmSpace;
`pull_many` evaluates a batch of knob dicts through an environment's
batched hook (or the sequential fallback).  `open_dispatcher` /
`pull_async` are the asynchronous counterparts: completion-ordered
dispatch through `platform.base.AsyncDispatcher`, where results return in
finish order rather than behind a round barrier (see the delay/staleness
contracts in base.py).  Builders take keyword overrides (noise=, seed=,
arrival_rate=, ...) which pass straight through to the environment
constructor, so benchmarks and examples construct any backend by name
without importing its module.

New backends register with `register_env("myboard", "landscape")` and are
immediately constructible everywhere — the bandit core never changes.
Pass `models=` (a callable returning the valid model names) so
`available_envs()` and the registry's KeyErrors can list concrete names.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, List, Sequence, Tuple

from repro.core.arms import (paper_arm_space, tpu_arm_space,
                             tpu_elastic_arm_space)
from repro.platform.telemetry import Observation

# (platform, scenario) -> builder(model, **overrides) -> Environment
_BUILDERS: Dict[Tuple[str, str], Callable] = {}

# (platform, scenario) -> space builder(**overrides) -> ArmSpace
_SPACES: Dict[Tuple[str, str], Callable] = {}

# platform -> callable() -> list of valid model names (lazy: listing may
# need heavy imports, and third-party platforms may not know theirs)
_MODELS: Dict[str, Callable[[], Sequence[str]]] = {}

#: Platforms whose names may omit the scenario ("engine/<arch>").
_DEFAULT_SCENARIO = {"engine": "live"}

_FLEET_SPEC = re.compile(r"^(\d+)x(.+)$")


def register_env(platform: str, scenario: str, space: Callable = None,
                 models: Callable[[], Sequence[str]] = None):
    """Decorator registering an environment builder (and optionally the
    matching arm-space builder and a model-name lister) under
    (platform, scenario)."""
    def deco(fn):
        _BUILDERS[(platform, scenario)] = fn
        if space is not None:
            _SPACES[(platform, scenario)] = space
        if models is not None:
            _MODELS[platform] = models
        return fn
    return deco


def parse_name(name: str) -> Tuple[str, str, str]:
    parts = name.split("/")
    if parts and parts[0] == "fleet":
        if len(parts) != 4 or not _FLEET_SPEC.match(parts[1]):
            raise KeyError(
                f"fleet environment name must be "
                f"'fleet/<n>x<platform>/<model>/<scenario>' "
                f"(e.g. 'fleet/4xjetson/llama3.2-1b/landscape'), got "
                f"{name!r}")
        return f"fleet/{parts[1]}", parts[2], parts[3]
    if len(parts) == 2:
        platform, model = parts
        scenario = _DEFAULT_SCENARIO.get(platform)
        if scenario is None:
            raise KeyError(
                f"environment name {name!r} omits the scenario and platform "
                f"{platform!r} has no default; use "
                "'<platform>/<model>/<scenario>'")
    elif len(parts) == 3:
        platform, model, scenario = parts
    else:
        raise KeyError(f"environment name must be "
                       f"'<platform>/<model>/<scenario>' or "
                       f"'fleet/<n>x<platform>/<model>/<scenario>', "
                       f"got {name!r}")
    return platform, model, scenario


def _fleet_spec(platform: str) -> Tuple[int, str]:
    """'fleet/<n>x<base>' -> (n, base)."""
    m = _FLEET_SPEC.match(platform[len("fleet/"):])
    return int(m.group(1)), m.group(2)


def _models_of(platform: str) -> List[str]:
    fn = _MODELS.get(platform)
    if fn is None:
        return ["<model>"]
    return sorted(fn())


def _check_model(platform: str, model: str) -> None:
    """Fail early with the concrete model list when the platform knows it
    (builders still guard themselves for direct construction)."""
    fn = _MODELS.get(platform)
    if fn is not None and model not in fn():
        raise KeyError(f"unknown {platform} model {model!r}; "
                       f"available: {sorted(fn())}")


def _builder(name: str) -> Tuple[Callable, str, Tuple[str, str]]:
    platform, model, scenario = parse_name(name)
    if platform.startswith("fleet/"):
        n, base = _fleet_spec(platform)
        if (base, scenario) not in _BUILDERS:
            raise KeyError(f"no environment {base!r}/{scenario!r} to build "
                           f"a fleet from; available: {available_envs()}")
        _check_model(base, model)

        def fleet_builder(model, **kw):
            from repro.platform.fleet import make_fleet
            return make_fleet(n, base, model, scenario, **kw)

        return fleet_builder, model, (base, scenario)
    try:
        builder = _BUILDERS[(platform, scenario)]
    except KeyError:
        raise KeyError(f"no environment {platform!r}/{scenario!r}; "
                       f"available: {available_envs()}") from None
    _check_model(platform, model)
    return builder, model, (platform, scenario)


def make_env(name: str, **overrides):
    """Construct the environment `name` with constructor overrides."""
    builder, model, _ = _builder(name)
    return builder(model, **overrides)


def make_space(name: str, **overrides):
    """The ArmSpace matching environment `name` (same grid the paper uses
    for the platform, plus any extra knobs the scenario adds).  Fleet
    names use the base platform's space: all devices share one grid."""
    platform, _, scenario = parse_name(name)
    if platform.startswith("fleet/"):
        _, platform = _fleet_spec(platform)
    try:
        builder = _SPACES[(platform, scenario)]
    except KeyError:
        raise KeyError(f"no arm space for {platform!r}/{scenario!r}; "
                       f"available: {available_envs()}") from None
    return builder(**overrides)


def available_envs() -> Tuple[str, ...]:
    """All constructible names, with concrete model names where the
    platform registered a lister (fleets compose on top of any of these:
    'fleet/<n>x' + name)."""
    names = []
    for (p, s) in _BUILDERS:
        for m in _models_of(p):
            names.append(f"{p}/{m}/{s}")
    return tuple(sorted(names))


def pull_many(env, knobs_list: Sequence[dict], round_index: int = 0
              ) -> List[Observation]:
    """Batched-evaluation hook: use the environment's own `pull_many` when
    it has one, else pull sequentially.  Always returns Observations.

    Contract (both paths): slot i of `knobs_list` is evaluated as logical
    round ``round_index + i``.  The sequential fallback realizes this by
    calling ``pull(knobs, round_index + i)``; a batched override receives
    only the base `round_index` and must advance per slot itself wherever
    its dynamics depend on the round (e.g. the events scenario's trace
    seeds).  Round-independent backends (the closed-form landscapes) may
    ignore it, but their observation-noise streams must still advance
    exactly as K sequential pulls would.
    """
    fn = getattr(env, "pull_many", None)
    if fn is not None:
        return [Observation.of(o) for o in fn(knobs_list, round_index)]
    return [Observation.of(env.pull(k, round_index + i))
            for i, k in enumerate(knobs_list)]


def open_dispatcher(env, n_workers: int = None):
    """Open the asynchronous completion-queue path onto `env`.

    Uses the environment's own `open_dispatch()` hook when it defines one
    (third-party backends with real worker pools), else the simulated
    event-clock `AsyncDispatcher` with one worker per fleet device (or a
    single worker for plain environments)."""
    from repro.platform.base import AsyncDispatcher

    fn = getattr(env, "open_dispatch", None)
    if fn is not None:
        return fn() if n_workers is None else fn(n_workers=n_workers)
    return AsyncDispatcher(env, n_workers=n_workers)


def pull_async(env, knobs_list: Sequence[dict], round_index: int = 0,
               n_workers: int = None) -> List:
    """Asynchronous counterpart of `pull_many`: evaluate the batch through
    the completion queue and return `Completion`s in *finish order* (ties
    in submission order), not slot order.

    Contract: slot i is still logical round ``round_index + i`` — the
    delay path changes *when* an observation arrives, never *what* it
    observed.  Synchronous callers wanting slot order should keep using
    `pull_many`; this helper exists for callers that care about the
    completion timeline (`Completion.finished_at`)."""
    disp = open_dispatcher(env, n_workers=n_workers)
    for i, knobs in enumerate(knobs_list):
        disp.submit(knobs, round_index + i)
    out = []
    while disp.in_flight:
        out.extend(disp.pop_wave())
    return out


# ---------------------------------------------------------------------------
# Built-in backends (imports deferred so `import repro.platform` stays light
# and cycle-free; the heavy deps load only when a backend is constructed)
# ---------------------------------------------------------------------------


def _jetson_models() -> List[str]:
    from repro.serving import energy
    return list(energy.ORIN_WORKLOADS)


def _config_archs() -> List[str]:
    """Every name repro.configs resolves: the dashed public aliases AND
    the raw module names (configs.get accepts both, so both must pass
    validation and appear in listings)."""
    import repro.configs as configs_mod
    return sorted(set(configs_mod.ALIASES) | set(configs_mod.ALIASES.
                                                 values()))


def _orin_workload(model: str):
    from repro.serving import energy
    try:
        return energy.JETSON_AGX_ORIN, energy.ORIN_WORKLOADS[model]
    except KeyError:
        raise KeyError(f"unknown jetson model {model!r}; "
                       f"have {sorted(energy.ORIN_WORKLOADS)}") from None


@register_env("jetson", "landscape", space=paper_arm_space,
              models=_jetson_models)
def _jetson_landscape(model: str, **kw):
    from repro.serving import simulator
    board, work = _orin_workload(model)
    return simulator.LandscapeEnv(board, work, **kw)


@register_env("jetson", "events", space=paper_arm_space,
              models=_jetson_models)
def _jetson_events(model: str, **kw):
    from repro.serving import simulator
    board, work = _orin_workload(model)
    return simulator.EventEnvironment(board, work, **kw)


def _tpu_profile(arch: str, model_shards: int):
    import repro.configs as configs_mod
    from repro.models.registry import bundle_for
    from repro.serving import energy
    try:
        cfg = configs_mod.get(arch)
    except ModuleNotFoundError:
        raise KeyError(f"unknown TPU model {arch!r}; "
                       f"available: {sorted(configs_mod.ALIASES)}") from None
    bundle = bundle_for(cfg)
    kv_bytes = 2.0 * 2 * getattr(cfg, "n_kv_heads", 8) \
        * getattr(cfg, "head_dim", 128) * getattr(cfg, "n_layers", 32)
    model = energy.tpu_workload_from_config(
        arch, bundle.n_params, bundle.n_active_params, kv_bytes,
        model_shards=model_shards)
    return energy.TPUChip(), model


@register_env("tpu-v5e", "landscape", space=tpu_arm_space,
              models=_config_archs)
def _tpu_landscape(model: str, *, model_shards: int = 16, **kw):
    from repro.serving import simulator
    chip, served = _tpu_profile(model, model_shards)
    return simulator.TPULandscapeEnv(chip, served, **kw)


@register_env("tpu-v5e", "elastic", space=tpu_elastic_arm_space,
              models=_config_archs)
def _tpu_elastic(model: str, *, model_shards: int = 16, **kw):
    from repro.serving import simulator
    chip, served = _tpu_profile(model, model_shards)
    return simulator.TPUElasticEnv(chip, served, **kw)


#: Engine model presets: the reduced same-family model the CPU tests and
#: docs use, or the architecture at its published widths.
ENGINE_PRESETS = ("smoke", "published")


@register_env("engine", "live", space=paper_arm_space,
              models=_config_archs)
def _engine_live(arch: str, *, preset: str = "smoke", seed: int = 0,
                 max_batch: int = 28,
                 max_seq_len: int = 128, prompt_len: int = 16,
                 max_new_tokens: int = 8, arrival_rate: float = 1.0,
                 sensor=None, sample_hz: float = 20.0,
                 decode_impl: str = "fused", prompt_bucket: int = 16,
                 scheduler: str = "static",
                 requests_per_pull=None, eos_id=None, chunk: int = 16,
                 faults=None):
    import jax
    import repro.configs as configs_mod
    from repro.models.registry import bundle_for
    from repro.serving import energy
    from repro.serving.engine import EngineEnvironment, InferenceEngine
    get_cfg = {"smoke": configs_mod.get_smoke,
               "published": configs_mod.get}.get(preset)
    if get_cfg is None:
        raise ValueError(f"preset must be one of {ENGINE_PRESETS}, "
                         f"got {preset!r}")
    try:
        cfg = get_cfg(arch)
    except ModuleNotFoundError:
        raise KeyError(f"unknown engine model {arch!r}; "
                       f"available: {sorted(configs_mod.ALIASES)}") from None
    bundle = bundle_for(cfg)
    # Under jit the f32 init transients fuse into the bf16 result instead
    # of being materialized op by op (GBs at published widths).
    params = jax.jit(bundle.init_params)(jax.random.PRNGKey(seed))
    engine = InferenceEngine(bundle, params, max_batch=max_batch,
                             max_seq_len=max_seq_len,
                             decode_impl=decode_impl,
                             prompt_bucket=prompt_bucket)
    board = energy.JETSON_AGX_ORIN
    work = energy.ORIN_WORKLOADS["llama3.2-1b"]
    return EngineEnvironment(engine, board, work,
                             arrival_rate=arrival_rate,
                             prompt_len=prompt_len,
                             max_new_tokens=max_new_tokens, seed=seed,
                             sensor=sensor, sample_hz=sample_hz,
                             scheduler=scheduler,
                             requests_per_pull=requests_per_pull,
                             eos_id=eos_id, chunk=chunk, faults=faults)
