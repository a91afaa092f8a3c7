"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per module:

    E1 landscape      Fig. 1   49-config (f, b) landscape + optimum
    E2/E3 search      Figs.3/5/6  Camel vs grid (cost/EDP/E, regret, arms)
    E4 validation     Fig. 4   optimal vs default corners, 2500 requests
    E5 sensitivity    Figs.7-10  alpha / interval / token-length / split
    E6 tpu_serving    DESIGN SS3  v5e adaptation landscapes + search
    E7 roofline       EXPERIMENTS SSRoofline  dry-run derived terms
    E8 kernels        kernel-vs-oracle checks + reference timings
    E10 fleet_scaling beyond-paper  batched-TS rounds/wall-clock vs K,
                      straggler tolerance (sync barrier vs async queue)
    E11 heterogeneity beyond-paper  shared vs device-contextual posterior
                      under persistent per-device speed offsets (same
                      module: benchmarks.fleet_scaling)
    E13 engine_continuous  continuous vs static batching goodput under
                      Poisson arrivals with ragged output lengths, plus
                      EOS early-exit (writes BENCH_continuous.json)
    E14 resilience    fault injection + graceful degradation: zero-fault
                      bit-identity, chaos-run convergence within 5% of
                      fault-free, hung-device deadline recovery (writes
                      BENCH_resilience.json)
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import time
import traceback


def main() -> None:
    from benchmarks import (ablations, config_search, engine_continuous,
                            fleet_scaling, kernels, landscape, resilience,
                            roofline, sensitivity, tpu_serving, validation)

    modules = [
        ("E1_landscape", landscape),
        ("E2_E3_config_search", config_search),
        ("E4_validation", validation),
        ("E5_sensitivity", sensitivity),
        ("E6_tpu_serving", tpu_serving),
        ("E7_roofline", roofline),
        ("E8_kernels", kernels),
        ("E9_ablations", ablations),
        ("E10_E11_fleet_scaling", fleet_scaling),
        ("E13_engine_continuous", engine_continuous),
        ("E14_resilience", resilience),
    ]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("filters", nargs="*",
                    help="run only modules whose name matches a filter")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a repro.obs JSONL trace of the benchmarked "
                         "runs (summarize with tools/trace_report.py)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.filters)
    if args.metrics_out:
        from repro import obs as obs_mod
        session = obs_mod.observing(args.metrics_out)
    else:
        session = contextlib.nullcontext()
    from repro.obs import tracing as obslog
    print("name,us_per_call,derived")
    failures = 0
    with session:
        for name, mod in modules:
            if only and not any(name.startswith(o) or o in name
                                for o in only):
                continue
            t0 = time.monotonic()
            rows = 0
            try:
                for row_name, us, derived in mod.run():
                    rows += 1
                    print(f"{row_name},{us:.1f},{derived}")
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"{name}_FAILED,0.0,{type(e).__name__}: {e}")
                traceback.print_exc(file=sys.stderr)
            # per-module span: the trace carries the sweep timeline even
            # for modules whose internals emit no events of their own
            obslog.emit("benchmark.module", dur_s=time.monotonic() - t0,
                        module=name, rows=rows, ok=rows > 0)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
