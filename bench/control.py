"""The readings behind the ``widest_gap`` limit, in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed: build the cell as `run.py` does, serve a window of the
cell's own traffic, then read, over the same seeded sample of served
requests, the program's widest gap and the control's (the family's
`reference`; for Qwen2 the float32 reference with both operands of every
matrix product rounded to e4m3).  The program's largest reading over the
seeds is the limit's lower end, the control's smallest its upper end.
Benchmark runs never run the control.  Prints one line per seed and, last, one JSON object.
"""

from __future__ import annotations

import argparse
import gc
import json

import run


def readings(plan: dict, seeds, seconds: float) -> list:
    """[(seed, program gap, control gap, tokens compared)] per seed."""
    out = []
    for seed in seeds:
        cell = run.build(plan, seed, seconds)
        outputs, stats, _ = run.window(cell["engine"], plan["config"]["engine"],
                                       cell["served"])
        del cell["engine"]
        gc.collect()
        v = run.judge(cell["family"], plan["config"], cell["params"],
                      cell["requests"], outputs, stats, seed, control=True)
        out.append((seed, v["checks"]["widest_gap"]["value"],
                    v["control_gap"], v["compared"]))
        run.log(f"seed {seed}: program {out[-1][1]:.6f}, control "
                f"{out[-1][2]:.6f}, {out[-1][3]} tokens compared")
        del cell
        gc.collect()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    plan = run.cell_plan(run.load_json(run.ROOT, "BENCHMARK.json"),
                         args.workload)
    ident = run.start_jax(plan)
    rows = readings(plan, [int(s) for s in args.seeds.split(",")],
                    args.seconds)
    prog = max(r[1] for r in rows)
    ctl = min(r[2] for r in rows)
    print(json.dumps({"workload": args.workload, "device": ident,
                      "program_max": prog, "control_min": ctl,
                      "ratio": ctl / prog if prog > 0 else None,
                      "rows": rows}), flush=True)


if __name__ == "__main__":
    main()
