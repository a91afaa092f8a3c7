"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json`` at the checkout root; it
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``), and ``BENCHMARK.json`` lists the
metrics the cell reports.  Each metric is read by ``bench/metrics/<name>.py``
or, for a name split by cell kind (``decode_step_ms.chat``), by the reader
of the part before the first dot.

What depends on the architecture is family code, found through `family`
by the configuration's ``model["architectures"][0]`` in
``bench/families/<name>/``: the program's entry (``program``), the seeded
weights (``weights``), the float32 reference and its control
(``reference``) and the analytic counts (``counts``).  Everything else is
shared: this runner, the traffic generator, the seeded draw (`seeded`),
the sample and gap behind ``correct`` (`check`), the trace reductions and
the readers.

Adding a cell, mix or metric adds files and edits none.  A configuration
of a new family adds its four family files; its cells get the existing
readers by per-layer entries of their own named ``<reader>.<suffix>``
(``decode_roofline.mla-offline``) with their own ``workloads`` list, and
by appending the cell's name to the ``workloads`` of the end-to-end
metrics they report (``tokens_per_s``).

One run: refuse anything but enough TPU chips; build the served engine
from the configuration with weights made from the seed; warm up every
prompt bucket the mix can produce (seed-batch prefill and single-row
admission) and the decode program; then one ``generate_continuous`` call
serves the mix's requests (the window; any compilation inside it fails the
run).  ``--trace 0`` reports the end-to-end metrics.  ``--trace 1``
reports the per-layer ones: those of the host's clock and the program's
counters from the same untraced window, and those of the device trace
from a second window that serves the first ``TRACE_SECONDS`` of the same
traffic under the JAX profiler.  After the windows a sample of the
requests the (first) window served is checked against the float32
reference (`check`).  The last line of stdout is one JSON object; the
numbers compared for ``correct`` are the last lines on stderr too.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache: a fixed directory in the checkout.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
WINDOW = "bench.window"
#: The traced window serves the requests of this many seconds of the
#: cell's traffic (all of it for shorter windows): a trace of a longer
#: window takes minutes to write and reduce (about 11 MB per second).
TRACE_SECONDS = 10.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_plan(bench: dict, workload: str) -> dict:
    """The cell's entry, configuration, mix and metrics, found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell,
            "config": load_json(ROOT, [c for c in bench["configs"]
                                       if c["name"] == cell["config"]][0]
                                ["file"]),
            "mix": load_json(BENCH, "traffic", cell["traffic"] + ".json"),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def reader(name: str):
    """``read(ctx)`` of metric ``name``: its own file, else the reader of
    the quantity before the first dot."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r}")


def device_identity(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(ident: dict, chips: int) -> None:
    if ident["platform"] != "tpu" or ident["count"] < chips:
        raise SystemExit(
            f"bench: this cell needs {chips} TPU chip(s); JAX sees "
            f"{ident['count']} {ident['platform']!r} device(s) "
            f"({ident['kind']!r}) and never falls back")


def buckets(mix: dict, bucket: int) -> list:
    lo = -(-mix["prompt"]["min"] // bucket) * bucket
    hi = -(-mix["prompt"]["max"] // bucket) * bucket
    return list(range(lo, hi + 1, bucket))


def warm_up(engine, eng: dict, mix: dict, vocab: int) -> None:
    """Compile and run every shape the mix can produce: a seed batch at
    each prompt bucket (requests far apart on the engine's clock, so each
    reseeds the pool alone) and a single-row admission at each bucket
    (behind one long request that holds the clock at the largest bucket)."""
    import numpy as np

    from repro.serving.scheduler import EngineRequest

    bks = buckets(mix, eng["prompt_bucket"])
    arr = lambda n: (1 + np.arange(n, dtype=np.int32) % (vocab - 1))  # noqa: E731
    kw = dict(n_slots=eng["n_slots"], eos_id=None, chunk=eng["chunk"],
              time_scale=1.0)
    engine.generate_continuous(
        [EngineRequest(rid=i, prompt=arr(b), max_new_tokens=1,
                       arrival_s=1e6 * i) for i, b in enumerate(bks)], **kw)
    anchor = EngineRequest(rid=0, prompt=arr(bks[-1]),
                           max_new_tokens=2 * eng["chunk"])
    engine.generate_continuous(
        [anchor] + [EngineRequest(rid=1 + i, prompt=arr(b), max_new_tokens=2,
                                  arrival_s=1e-9)
                    for i, b in enumerate(bks)], **kw)


class CompileWatch:
    """Counts JAX tracing, compilation and cache loads while active."""

    def __init__(self, jax):
        self.jax, self.events = jax, []

    def _listen(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.events.append(event)

    def __enter__(self):
        self.jax.monitoring.register_event_duration_secs_listener(
            self._listen)
        return self

    def __exit__(self, *exc):
        self.jax.monitoring.unregister_event_duration_listener(self._listen)


def build(plan: dict, seed: int, seconds: float) -> dict:
    """The served engine with weights made from ``seed``, warmed up for
    the cell's mix, the configuration's family (`family.load`), and the
    window's requests (``requests`` as the
    generator made them, ``served`` as the engine takes them)."""
    import jax

    import family
    import traffic
    from repro.models.registry import bundle_for
    from repro.serving.engine import InferenceEngine

    config, mix = plan["config"], plan["mix"]
    m, eng = config["model"], config["engine"]
    fam = family.load(config)
    t_import = time.perf_counter()
    params = fam.weights.make(m, seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter()
    engine = InferenceEngine(bundle_for(fam.program.program_config(config)),
                             params,
                             max_batch=eng["n_slots"],
                             max_seq_len=eng["max_seq_len"],
                             prompt_bucket=eng["prompt_bucket"])
    requests = traffic.generate(mix, seed, seconds, m["vocab_size"])
    warm_up(engine, eng, mix, m["vocab_size"])
    t_warm = time.perf_counter()
    log(f"set-up: imports {t_import - T0:.3f} s, weights "
        f"{t_weights - t_import:.3f} s, engine + traffic + warm-up "
        f"{t_warm - t_weights:.3f} s")
    return {"engine": engine, "family": fam, "params": params,
            "requests": requests, "served": engine_requests(requests)}


def engine_requests(requests: list) -> list:
    """The generator's requests as the engine takes them."""
    from repro.serving.scheduler import EngineRequest

    return [EngineRequest(rid=r["rid"], prompt=r["prompt"],
                          max_new_tokens=r["max_new_tokens"],
                          arrival_s=r["arrival_s"]) for r in requests]


def window(engine, eng: dict, served: list):
    """The timed call: (outputs, stats, wall seconds).  Any tracing or
    compilation inside it raises."""
    import jax

    frozen = dict(engine.compile_counts)
    with CompileWatch(jax) as watch:
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW):
            outputs, stats = engine.generate_continuous(
                served, n_slots=eng["n_slots"], eos_id=None,
                chunk=eng["chunk"], time_scale=1.0)
        window_s = time.perf_counter() - t_start
    if watch.events or engine.compile_counts != frozen:
        raise RuntimeError(f"compiled inside the window: {watch.events}, "
                           f"{frozen} -> {engine.compile_counts}")
    return outputs, stats, window_s


def judge(fam, config: dict, params, requests: list, outputs: dict, stats,
          seed: int, control: bool = False) -> dict:
    """The numbers compared for ``correct``, each beside its limit, over a
    seeded sample of the window's served requests, against family
    ``fam``'s reference; with ``control`` also the fp8 control's widest
    gap over the same sample."""
    import check

    m = config["model"]
    bad = check.unserved(requests, outputs, m["vocab_size"])
    ok = [r for r in requests if r["rid"] not in bad]
    admit = {rec.rid: rec.admit_s for rec in stats.records}
    gap, ctl, n_cmp = (check.widest_gaps(
        fam, m, params, check.sample(ok, admit, seed),
        outputs, control) if ok else (None, None, 0))
    checks = {"widest_gap": {"value": gap,
                             "limit": config["correct"]["widest_gap"]},
              "unserved": {"value": len(bad), "limit": 0}}
    return {"checks": checks, "failed": len(bad), "compared": n_cmp,
            "control_gap": ctl,
            "correct": all(c["value"] is not None and c["value"] <= c["limit"]
                           for c in checks.values())}


def traced_window(engine, fam, plan: dict, seed: int,
                  seconds: float) -> dict:
    """Serve the first ``TRACE_SECONDS`` of the cell's traffic again, under
    the profiler; the trace's reduction, with the window's work under
    ``work`` (family ``fam``'s ``counts.window_work``)."""
    import jax

    import trace_reduce
    import traffic

    m = plan["config"]["model"]
    requests = traffic.generate(plan["mix"], seed, min(seconds, TRACE_SECONDS),
                                m["vocab_size"])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    try:
        outputs, stats, _ = window(engine, plan["config"]["engine"],
                                   engine_requests(requests))
    finally:
        jax.profiler.stop_trace()
    reduced = trace_reduce.reduce_file(trace_reduce.find_trace(TRACE_DIR),
                                       WINDOW)
    reduced["work"] = fam.counts.window_work(
        m, [(len(r["prompt"]), len(outputs.get(r["rid"], ())))
            for r in requests], stats.decode_steps)
    return reduced


def run_cell(plan: dict, seed: int, seconds: float, trace: bool,
             t0: float) -> dict:
    """One run of a cell on the default device; returns the result object."""
    import jax

    config = plan["config"]
    m, eng = config["model"], config["engine"]
    ident = device_identity(jax)
    peaks = peaks_for(ident["kind"])
    cell = build(plan, seed, seconds)
    setup_s = time.perf_counter() - t0
    outputs, stats, window_s = window(cell["engine"], eng, cell["served"])
    log(f"window: {len(cell['served'])} requests, {stats.tokens_out} tokens, "
        f"{window_s:.3f} s wall; the engine's clock covers "
        f"{stats.prefill_s + stats.decode_s:.3f} s of it (prefill "
        f"{stats.prefill_s:.3f} s in {stats.prefill_calls} calls, decode "
        f"{stats.decode_s:.3f} s in {stats.decode_steps} steps)")
    fam = cell["family"]
    reduced = (traced_window(cell["engine"], fam, plan, seed, seconds)
               if trace else None)

    mem = jax.devices()[0].memory_stats() or {}
    device = dict(ident, memory_peak_bytes=mem.get("peak_bytes_in_use"))
    log(f"memory: peak {mem.get('peak_bytes_in_use')} of "
        f"{mem.get('bytes_limit')} bytes")
    del cell["engine"]
    gc.collect()
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])

    # Correctness, after the windows and with the program's state freed.
    requests = cell["requests"]
    verdict = judge(fam, config, cell["params"], requests, outputs, stats,
                    seed)

    ctx = {"stats": stats, "records": stats.records, "n_slots": eng["n_slots"],
           "window_s": window_s, "setup_s": setup_s, "trace": reduced,
           "model": m, "peaks": peaks,
           "work": fam.counts.window_work(
               m, [(len(r["prompt"]), len(outputs.get(r["rid"], ())))
                   for r in requests], stats.decode_steps)}
    metrics = {}
    for spec in plan["per_layer" if trace else "end_to_end"]:
        value = reader(spec["name"])(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    result = {"correct": verdict["correct"], "attempted": len(requests),
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = verdict["checks"]
    log(f"compared {verdict['compared']} served tokens of the window against "
        f"the float32 reference")
    for name, c in verdict["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    plan = cell_plan(load_json(ROOT, "BENCHMARK.json"), args.workload)
    start_jax(plan)
    result = run_cell(plan, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)


def start_jax(plan: dict) -> dict:
    """Import JAX with the compile cache in this checkout and refuse
    anything but the cell's TPU chips; returns the device identity."""
    # JAX reads these as it is imported: the cache stays in this checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ident = device_identity(jax)
    require_chips(ident, plan["cell"]["chips"])
    peaks_for(ident["kind"])
    return ident


if __name__ == "__main__":
    main()
