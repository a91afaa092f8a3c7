"""Summarize a `repro.obs` JSONL trace: per-arm energy/latency/EDP tables.

Reads the trace a run wrote via ``--metrics-out`` (serve.py, benchmarks)
and renders:

* the per-arm pull summary — pulls, mean energy, latency, EDP, cost,
  mean power, mean staleness (async runs), with the committed arm marked;
* the per-request summary (continuous-batching runs): request count,
  wait, time to first token, latency (on the span clock) and tokens
  from ``engine.request`` spans;
* the fault summary (chaos runs, ``--faults``): injected faults,
  retries/backoff, quarantined workers, sensor degradations, cancelled
  requests — from the ``fault.*`` seams;
* span totals by name (where the run's wall-clock went);
* the closing metrics snapshot (counters / gauges / histograms);
* the run-level sensor measurement, when a non-simulated sensor ran.

    python tools/trace_report.py out.jsonl [more.jsonl ...]
    python tools/trace_report.py out.jsonl --analysis analysis_report.json

``--analysis`` joins the static-analyzer verdict (the JSON written by
``python -m repro.analysis --check --json ...``) into the report, so one
artifact answers both "how did the run perform" and "is the hot path
still trace-clean".

The input is plain JSONL (see docs/TELEMETRY.md for the schema), so any
other tool — jq, pandas, a notebook — can query the same file; this
report is just the quick look.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional


def load_rows(path: str) -> List[dict]:
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                print(f"!! skipping malformed line: {line[:80]}",
                      file=sys.stderr)
    return rows


def _fmt(value, width: int = 10) -> str:
    if value is None:
        return "-".rjust(width)
    if isinstance(value, float):
        return f"{value:.4g}".rjust(width)
    return str(value).rjust(width)


def _knobs_str(knobs: Optional[dict]) -> str:
    if not knobs:
        return "?"
    return " ".join(f"{k}={v}" for k, v in sorted(knobs.items()))


def _mean(values: List[float]) -> Optional[float]:
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def arm_table(rows: List[dict]) -> List[str]:
    pulls = [r for r in rows if r.get("name") == "pull"]
    if not pulls:
        return ["no pull events in trace"]
    commits = [r for r in rows if r.get("name") == "commit"]
    committed = commits[-1].get("attrs", {}).get("best_arm") \
        if commits else None
    by_arm: Dict[int, List[dict]] = defaultdict(list)
    for r in pulls:
        by_arm[r.get("attrs", {}).get("arm", -1)].append(
            r.get("attrs", {}))
    header = (f"{'':2}{'arm':>4} {'knobs':<28}{'pulls':>6}"
              f"{'mean_E_J':>10}{'mean_L_s':>10}{'mean_EDP':>10}"
              f"{'mean_cost':>10}{'mean_W':>10}{'mean_tok/s':>11}"
              f"{'mean_stale':>11}")
    lines = [f"per-arm summary ({len(pulls)} pulls, "
             f"{len(by_arm)} distinct arms; * = committed):", header]
    stats = []
    for arm, attrs in by_arm.items():
        stats.append({
            "arm": arm,
            "knobs": _knobs_str(attrs[0].get("knobs")),
            "pulls": len(attrs),
            "energy": _mean([a.get("energy_j") for a in attrs]),
            "latency": _mean([a.get("latency_s") for a in attrs]),
            "edp": _mean([a.get("edp") for a in attrs]),
            "cost": _mean([a.get("cost") for a in attrs]),
            "power": _mean([a.get("power_w") for a in attrs]),
            "tok_s": _mean([a.get("tokens_per_s") for a in attrs]),
            "stale": _mean([a.get("staleness") for a in attrs]),
        })
    # Missing metadata (e.g. pulls without cost) must render as blank
    # cells, never crash the report: sort strictly on non-None keys.
    stats.sort(key=lambda s: (s["cost"] is None,
                              s["cost"] if s["cost"] is not None else 0.0,
                              s["arm"]))
    for s in stats:
        mark = " *" if s["arm"] == committed else "  "
        lines.append(
            f"{mark}{s['arm']:>4} {s['knobs']:<28}{s['pulls']:>6}"
            f"{_fmt(s['energy'])}{_fmt(s['latency'])}{_fmt(s['edp'])}"
            f"{_fmt(s['cost'])}{_fmt(s['power'])}{_fmt(s['tok_s'], 11)}"
            f"{_fmt(s['stale'], 11)}")
    if committed is not None:
        knobs = _knobs_str(commits[-1].get("attrs", {}).get("knobs"))
        lines.append(f"committed: arm {committed} ({knobs})")
    return lines


def request_table(rows: List[dict], max_rows: int = 32) -> List[str]:
    """Per-request summary from `engine.request` spans (continuous
    batching).  Their times are on the span clock, from when the request
    was due: `wait_s` to its admission, `ttft_s` to its first token on
    the host, and the span's duration to its finish.  Missing attributes
    render as blank cells."""
    reqs = [dict(r.get("attrs", {}), dur_s=r.get("dur_s"),
                 rid=r.get("rid", r.get("attrs", {}).get("rid")))
            for r in rows if r.get("name") == "engine.request"]
    if not reqs:
        return []
    waits = [a.get("queue_wait_s") for a in reqs]
    ttfts = [a.get("ttft_s") for a in reqs]
    lats = [a.get("dur_s") for a in reqs]
    toks = [a.get("tokens") for a in reqs]
    lines = ["",
             f"per-request summary ({len(reqs)} requests): "
             f"mean wait {_fmt(_mean(waits), 1).strip()} s, "
             f"mean ttft {_fmt(_mean(ttfts), 1).strip()} s, "
             f"mean latency {_fmt(_mean(lats), 1).strip()} s, "
             f"mean tokens {_fmt(_mean(toks), 1).strip()}",
             f"{'rid':>6}{'slot':>6}{'prompt':>8}{'tokens':>8}"
             f"{'wait_s':>10}{'ttft_s':>10}{'latency_s':>11}"]
    shown = sorted(reqs, key=lambda a: (a.get("rid") is None,
                                        a.get("rid") or 0))[:max_rows]
    for a in shown:
        lines.append(f"{_fmt(a.get('rid'), 6)}{_fmt(a.get('slot'), 6)}"
                     f"{_fmt(a.get('prompt_len'), 8)}"
                     f"{_fmt(a.get('tokens'), 8)}"
                     f"{_fmt(a.get('queue_wait_s'), 10)}"
                     f"{_fmt(a.get('ttft_s'), 10)}"
                     f"{_fmt(a.get('dur_s'), 11)}")
    if len(reqs) > max_rows:
        lines.append(f"  ... {len(reqs) - max_rows} more")
    return lines


def fault_table(rows: List[dict]) -> List[str]:
    """Fault summary from the `fault.*` seams (repro.faults): what was
    injected, what the stack did about it (retries, quarantines,
    sensor degradations, cancelled requests)."""
    faults = [r for r in rows if str(r.get("name", "")).startswith("fault.")]
    if not faults:
        return []
    by_key: Dict[str, int] = defaultdict(int)
    for r in faults:
        a = r.get("attrs", {})
        detail = (a.get("fault") or a.get("reason") or a.get("action")
                  or "-")
        by_key[f"{r.get('name')} {detail}"] += 1
    lines = ["", f"fault summary ({len(faults)} fault events):",
             f"{'event':<44}{'count':>6}"]
    for key in sorted(by_key):
        lines.append(f"{key:<44}{by_key[key]:>6}")
    backoffs = [r["attrs"]["backoff_s"] for r in faults
                if r.get("name") == "fault.retry"
                and r.get("attrs", {}).get("backoff_s") is not None]
    if backoffs:
        lines.append(f"retries: {len(backoffs)}, mean backoff "
                     f"{_fmt(_mean(backoffs), 1).strip()} s")
    quarantined = sorted({w for r in faults
                          if r.get("name") == "fault.device"
                          for w in [r.get("attrs", {}).get("worker")]
                          if w is not None})
    if quarantined:
        lines.append(f"quarantined workers: {quarantined}")
    return lines


def span_table(rows: List[dict]) -> List[str]:
    spans = [r for r in rows if r.get("kind") == "span"]
    if not spans:
        return []
    by_name: Dict[str, List[float]] = defaultdict(list)
    for r in spans:
        by_name[r.get("name", "?")].append(float(r.get("dur_s", 0.0)))
    lines = ["", "span totals:",
             f"{'name':<20}{'count':>8}{'total_s':>12}{'mean_s':>12}"]
    for name in sorted(by_name, key=lambda n: -sum(by_name[n])):
        durs = by_name[name]
        lines.append(f"{name:<20}{len(durs):>8}{_fmt(sum(durs), 12)}"
                     f"{_fmt(sum(durs) / len(durs), 12)}")
    return lines


def metric_table(rows: List[dict]) -> List[str]:
    metrics = [r for r in rows if r.get("kind") == "metric"]
    if not metrics:
        return []
    lines = ["", "metrics snapshot:"]
    for m in metrics:
        if m.get("metric_type") == "histogram":
            lines.append(
                f"  {m.get('name'):<28} count={m.get('count')} "
                f"mean={_fmt(m.get('mean'), 1).strip()} "
                f"min={_fmt(m.get('min'), 1).strip()} "
                f"max={_fmt(m.get('max'), 1).strip()}")
        else:
            lines.append(f"  {m.get('name'):<28} "
                         f"{_fmt(m.get('value'), 1).strip()}")
    return lines


def sensor_lines(rows: List[dict]) -> List[str]:
    runs = [r for r in rows if r.get("name") == "sensor.run"]
    if not runs:
        return []
    a = runs[-1].get("attrs", {})
    return ["", f"sensor run measurement ({a.get('sensor')}): "
            f"{_fmt(a.get('joules'), 1).strip()} J over "
            f"{_fmt(a.get('duration_s'), 1).strip()} s, "
            f"avg {_fmt(a.get('avg_watts'), 1).strip()} W, "
            f"peak {_fmt(a.get('peak_watts'), 1).strip()} W "
            f"({a.get('n_samples')} samples)"]


def analysis_lines(path: str) -> List[str]:
    """Render the analyzer verdict from a `python -m repro.analysis
    --json` report: pass/fail, findings by rule, and any budget rows
    that drifted from their recorded observation."""
    try:
        with open(path) as fh:
            rep = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        return ["", f"analysis report {path}: unreadable ({e})"]
    findings = rep.get("findings", [])
    budgets = rep.get("budgets", {})
    by_rule: Dict[str, int] = defaultdict(int)
    for f in findings:
        by_rule[f.get("rule", "?")] += 1
    verdict = "CLEAN" if not findings else \
        f"{len(findings)} finding(s)"
    lines = ["", f"static analysis ({path}): {verdict}"]
    for rule in sorted(by_rule):
        lines.append(f"  {rule}: {by_rule[rule]}")
    for f in findings[:16]:
        loc = (f"{f.get('path')}:{f.get('line')}" if f.get("path")
               else f"<{f.get('entry', '?')}>")
        lines.append(f"    {f.get('rule')} {loc}  {f.get('message')}")
    if len(findings) > 16:
        lines.append(f"    ... {len(findings) - 16} more")
    drift = {e: b for e, b in budgets.items()
             if b.get("status") not in (None, "ok")}
    if drift:
        lines.append("  budget status (non-ok rows):")
        for entry in sorted(drift):
            b = drift[entry]
            lines.append(f"    {entry:<40} count={b.get('count')} "
                         f"observed={b.get('observed')} "
                         f"budget={b.get('budget')} [{b.get('status')}]")
    elif budgets:
        lines.append(f"  jaxpr budgets: {len(budgets)} entries, all ok")
    return lines


def report(path: str, analysis: Optional[str] = None) -> str:
    rows = load_rows(path)
    counts = defaultdict(int)
    for r in rows:
        counts[r.get("kind", "?")] += 1
    head = ", ".join(f"{n} {k}" for k, n in sorted(counts.items()))
    lines = [f"== {path}: {len(rows)} rows ({head})", ""]
    lines += arm_table(rows)
    lines += request_table(rows)
    lines += fault_table(rows)
    lines += span_table(rows)
    lines += sensor_lines(rows)
    lines += metric_table(rows)
    if analysis:
        lines += analysis_lines(analysis)
    return "\n".join(lines)


def main(argv: List[str]) -> int:
    analysis = None
    if "--analysis" in argv:
        i = argv.index("--analysis")
        if i + 1 >= len(argv):
            print("--analysis needs the analyzer JSON path")
            return 2
        analysis = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if not argv:
        print("usage: trace_report.py <trace.jsonl> ... "
              "[--analysis report.json]")
        return 2
    for path in argv:
        print(report(path, analysis=analysis))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
