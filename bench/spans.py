"""The program's ``engine.*`` spans in a traced window, on the device
trace's clock: where the device's idle time went.

The serving loop (``InferenceEngine.generate_continuous``) opens a
``jax.profiler.TraceAnnotation`` for each of its phases, so a traced
window's ``.xplane.pb`` holds them on a host plane, with their attributes
as event stats (``steps`` on ``engine.chunk.fetch``, ``live`` on
``engine.chunk.upload``).  This module reads them back:

* the device's busy union is aligned to host time as
  ``trace_reduce.py`` aligns it: one shift that puts the first device
  operation at the first host ``*Execute*`` event of the window;
* that shift is checked against every launch: a device ``XLA Modules``
  event and the host event that enqueued it (``DoEnqueueProgram``) share
  a ``run_id``, and a program cannot start before its launch.  If the
  least shift each launch allows drifts by more than 0.1 ms from the
  first third of the window to the last, each program gets its own
  shift instead: the largest that its neighbouring launches allow;
* each idle interval of the window (between busy intervals, and before
  the first and after the last device operation) is attributed to the
  innermost ``engine.*`` span over its midpoint, or to ``none``.

`analysis()` loads the newest trace under ``<checkout>/.bench_trace``
once per file and logs the idle time by span on stderr.  A trace of a
program that opens no ``engine.*`` span (no chunk) gives counts of zero,
and the readers that use it report nothing.
"""

from __future__ import annotations

import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(BENCH), ".bench_trace")
WINDOW = "bench.window"
PREFIX = "engine."
CHUNK = "engine.chunk.fetch"
LAUNCH = "DoEnqueueProgram"
NONE = "none"
#: Drift of the launch-bound shift across the window beyond which each
#: program is aligned on its own launch, in ns.
DRIFT_NS = 1e5
#: Launches on either side whose bound sets a program's own shift.
NEIGHBOURS = 8

HostEvent = Tuple[str, float, float, dict]     # name, start, end (ns), stats
Module = Tuple[float, float, Optional[int]]    # start, end (ns), run_id


def _innermost(mids: Sequence[float],
               spans: Sequence[Tuple[str, float, float]]) -> List[str]:
    """For each of the ascending `mids`, the name of the shortest span
    over it, or `NONE`: one sweep, whatever the number of gaps."""
    spans = sorted(spans, key=lambda sp: sp[1])
    out, open_, i = [], [], 0
    for mid in mids:
        while i < len(spans) and spans[i][1] <= mid:
            open_.append(spans[i])
            i += 1
        open_ = [sp for sp in open_ if sp[2] >= mid]
        out.append(min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_
                   else NONE)
    return out


def _launch_bounds(mods: Sequence[Module], launches: Dict[int, float],
                   ) -> List[Optional[float]]:
    """Per program, the least shift (host - device, ns) its launch allows,
    or None where the program's launch is not in the trace."""
    return [launches[r] - s if r in launches else None for s, _, r in mods]


def _drift(bounds: Sequence[Optional[float]]) -> float:
    """How far the launch-bound shift moves from the first third of the
    programs to the last (the upper envelope of each third)."""
    known = [b for b in bounds if b is not None]
    if len(known) < 3:
        return 0.0
    third = len(known) // 3
    return abs(max(known[-third:]) - max(known[:third]))


def _own_shifts(bounds: Sequence[Optional[float]]) -> List[float]:
    """Each program's shift: the largest bound among its neighbouring
    launches (a program queued behind another starts later than its own
    launch allows; the neighbours that found the device idle do not)."""
    known = [(i, b) for i, b in enumerate(bounds) if b is not None]
    out = []
    for i in range(len(bounds)):
        near = [b for j, b in known if abs(j - i) <= NEIGHBOURS]
        if not near:
            near = [min(known, key=lambda jb: abs(jb[0] - i))[1]]
        out.append(max(near))
    return out


def _to_host(ops: Sequence[Tuple[float, float]], mods: Sequence[Module],
             shifts: Sequence[float]) -> List[Tuple[float, float]]:
    """Device op intervals moved to host time by the shift of the program
    that holds each (the last program started before it)."""
    starts = [s for s, _, _ in mods]
    out, j = [], 0
    for s, e in sorted(ops):
        while j + 1 < len(starts) and starts[j + 1] <= s:
            j += 1
        out.append((s + shifts[j], e + shifts[j]))
    return out


def attribute(ops: Dict[int, List[Tuple[float, float]]],
              modules: Dict[int, List[Module]], host: List[HostEvent],
              window: Tuple[float, float]) -> dict:
    """The attribution on plain events: ``ops`` and ``modules`` map a
    device id to its operation and program intervals on the device's
    clock; ``host`` is every host event read (name, start, end, stats);
    ``window`` the host annotation's (start_ns, end_ns)."""
    lo, hi = window
    spans = [(n, s, e) for n, s, e, _ in host
             if n.startswith(PREFIX) and s < hi and e > lo]
    executes = [s for n, s, _, _ in host if "Execute" in n and lo <= s <= hi]
    launches: Dict[int, float] = {}
    for n, s, _, st in host:
        if n == LAUNCH and "run_id" in st:
            launches[st["run_id"]] = min(s, launches.get(st["run_id"], s))
    idle: Dict[str, float] = defaultdict(float)
    interior: Dict[str, float] = defaultdict(float)
    drift, per_launch = 0.0, False
    devices = [d for d in sorted(ops) if ops[d]]
    for dev in devices:
        first_op = min(s for s, _ in ops[dev])
        shift = (min(executes) if executes else lo) - first_op
        mods = sorted(modules.get(dev, []))
        bounds = _launch_bounds(mods, launches)
        drift = max(drift, _drift(bounds))
        if drift > DRIFT_NS:
            per_launch = True
            busy = trace_reduce.union(_to_host(ops[dev], mods,
                                               _own_shifts(bounds)))
        else:
            busy = [(s + shift, e + shift)
                    for s, e in trace_reduce.union(ops[dev])]
        gaps = [(s, e, True) for s, e in
                trace_reduce.gaps(busy, busy[0][0], busy[-1][1])]
        gaps += [(s, e, False) for s, e in ((lo, busy[0][0]),
                                            (busy[-1][1], hi)) if e > s]
        gaps.sort(key=lambda g: g[0] + g[1])
        names = _innermost([(s + e) / 2 for s, e, _ in gaps], spans)
        for (s, e, between_ops), name in zip(gaps, names):
            idle[name] += (e - s) * 1e-9
            if between_ops:
                interior[name] += (e - s) * 1e-9
    n_dev = max(len(devices), 1)
    chunks = [st for n, s, _, st in host if n == CHUNK and lo <= s <= hi]
    idle_s = {k: v / n_dev for k, v in idle.items()}
    interior_s = {k: v / n_dev for k, v in interior.items()}
    return {"idle_s": idle_s, "interior_idle_s": interior_s,
            "named_idle_s": sum(v for k, v in idle_s.items() if k != NONE),
            "chunks": len(chunks),
            "steps": sum(int(st.get("steps", 0)) for st in chunks),
            "drift_ms": drift * 1e-6,
            "alignment": "per launch" if per_launch else "one shift"}


def load(path: str):
    """(ops, modules, host) of an ``.xplane.pb`` file, as `attribute`
    takes them: of the host events only the window, ``*Execute*``,
    launch and ``engine.*`` ones, with stats for the last two."""
    import re

    from jax.profiler import ProfileData

    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    host: List[HostEvent] = []
    for plane in ProfileData.from_file(path).planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = [(e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    modules[dev] = [(e.start_ns, e.start_ns + e.duration_ns,
                                     dict(e.stats).get("run_id"))
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(PREFIX) or name == LAUNCH:
                        stats = dict(e.stats)
                    elif name == WINDOW or "Execute" in name:
                        stats = {}
                    else:
                        continue
                    host.append((name, e.start_ns,
                                 e.start_ns + e.duration_ns, stats))
    return ops, modules, host


_LOADED: Dict[Tuple[str, float], Optional[dict]] = {}


def analysis(trace_dir: Optional[str] = None) -> Optional[dict]:
    """`attribute` over the newest trace under ``trace_dir`` (default
    ``<checkout>/.bench_trace``), windowed on ``bench.window``; None when
    there is no trace or no device operation in it."""
    try:
        path = trace_reduce.find_trace(trace_dir or TRACE_DIR)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED[key] = _analyse(path)
    return _LOADED[key]


def _analyse(path: str) -> Optional[dict]:
    ops, modules, host = load(path)
    windows = [(s, e) for n, s, e, _ in host if n == WINDOW]
    if not windows or not any(ops.values()):
        return None
    out = attribute(ops, modules, host,
                    max(windows, key=lambda w: w[1] - w[0]))
    total = sum(out["idle_s"].values())
    inner = sum(out["interior_idle_s"].values())
    named = inner - out["interior_idle_s"].get(NONE, 0.0)
    print(f"spans: {out['chunks']} chunks, {out['steps']} decode steps; "
          f"device idle {1e3 * total:.3f} ms, {1e3 * inner:.3f} ms of it "
          f"between the first and last device operation, "
          f"{100 * named / inner if inner else 0.0:.2f}% of that under an "
          f"engine.* span; alignment {out['alignment']} (launch-bound "
          f"drift {out['drift_ms']:.4f} ms)", file=sys.stderr, flush=True)
    for name, sec in sorted(out["idle_s"].items(), key=lambda kv: -kv[1]):
        print(f"spans: idle under {name}: {1e3 * sec:.3f} ms (between "
              f"operations {1e3 * out['interior_idle_s'].get(name, 0):.3f}"
              f" ms)", file=sys.stderr, flush=True)
    return out
