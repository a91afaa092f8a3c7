"""Find the highest arrival rate a chat cell sustains: one sweep, one process.

    python3 bench/knee.py --workload <cell> --seed 5 --seconds 20 --rates 4,6,8,10

Serves the cell's mix at each rate (every other parameter as in the mix
file) and reports, per rate, the mean queue wait (admit - arrival on the
engine's clock) of the first and last fifth of requests by arrival, the
time-to-first-token median and 95th percentile, and the 95th percentile of
the time per output token.  The knee is the highest rate whose backlog
does not grow over the window: last-fifth wait no longer than first-fifth.
A cell is then set at about four fifths of it.  Prints one JSON object.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

import run


def sweep(plan: dict, seed: int, seconds: float, rates) -> list:
    import traffic

    cell = run.build(plan, seed, seconds)
    engine, eng = cell["engine"], plan["config"]["engine"]
    vocab = plan["config"]["model"]["vocab_size"]
    rows = []
    for rate in rates:
        mix = dict(plan["mix"], rate_rps=rate)
        served = run.engine_requests(traffic.generate(mix, seed, seconds,
                                                      vocab))
        _, stats, wall = run.window(engine, eng, served)
        recs = sorted(stats.records, key=lambda r: r.arrival_s)
        fifth = max(1, len(recs) // 5)
        wait = lambda rs: float(np.mean([r.queue_wait_s for r in rs]))  # noqa: E731
        ttft = [r.admit_s - r.arrival_s for r in recs]
        tpot = [(r.finish_s - r.admit_s) / (r.n_tokens - 1) for r in recs
                if r.n_tokens > 1]
        rows.append({"rate_rps": rate, "requests": len(recs),
                     "wall_s": wall, "engine_s": stats.sim_s,
                     "wait_first_fifth_ms": 1e3 * wait(recs[:fifth]),
                     "wait_last_fifth_ms": 1e3 * wait(recs[-fifth:]),
                     "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
                     "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
                     "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)),
                     "occupancy": stats.mean_occupancy / eng["n_slots"]})
        run.log(json.dumps(rows[-1]))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args()
    plan = run.cell_plan(run.load_json(run.ROOT, "BENCHMARK.json"),
                         args.workload)
    ident = run.start_jax(plan)
    rows = sweep(plan, args.seed, args.seconds,
                 [float(r) for r in args.rates.split(",")])
    sustained = [r["rate_rps"] for r in rows
                 if r["wait_last_fifth_ms"] <= r["wait_first_fifth_ms"]]
    print(json.dumps({"workload": args.workload, "device": ident,
                      "knee_rps": max(sustained) if sustained else None,
                      "rows": rows}), flush=True)


if __name__ == "__main__":
    main()
