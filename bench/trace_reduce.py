"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time.

What is read:

* device planes ``/device:TPU:<n>``: their ``XLA Ops`` line (one event per
  operation executed on the chip) and ``XLA Modules`` line (one event per
  compiled program executed);
* host planes (``/host:...``): every event, to name what the host was doing
  during an idle gap, and the harness's window annotation.

The profiler runs only around the window and nothing else runs on the
device meanwhile, so every device event belongs to the window.  The
device's clock is not the host's (on a v5e the device timeline reads about
2 ms early), so device events are never clipped to host times; to name
gaps, the device timeline is shifted so its first operation starts at the
first host ``*Execute*`` event inside the window.

What comes out:

* ``busy_s``: the union of the device's operation intervals, averaged over
  the chips used, so overlapping operations count once; ``window_s``, the
  host annotation's length; ``idle_share`` = 1 - busy / window;
* ``module_s``: device seconds per compiled program, keyed by its name
  without the trailing ``(id)``;
* ``device_ops``: the ten operations with the most device time, as
  ``[module/op, seconds]``;
* ``idle_gaps``: the ten longest gaps between busy intervals (and before
  the first / after the last), each named by the innermost host event that
  spans its midpoint, as ``[name, seconds]``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
Event = Tuple[str, float, float]        # (name, start_ns, end_ns)

_ID_SUFFIX = re.compile(r"\(\d+\)$")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals: overlapping or touching ones become one."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) around the merged ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def module_name(name: str) -> str:
    return _ID_SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _name_gap(mid: float, host: Sequence[Event], skip: str) -> str:
    inner = [(e - s, name) for name, s, e in host
             if s <= mid <= e and name != skip]
    return min(inner)[1] if inner else "no host event"


def reduce_events(ops: Dict[int, List[Event]],
                  modules: Dict[int, List[Event]], host: List[Event],
                  window: Interval, window_name: str = "window") -> dict:
    """The reduction on plain events: ``ops`` and ``modules`` map a device
    id to its events; ``host`` is every host event; ``window`` the host
    annotation's (start_ns, end_ns)."""
    lo, hi = window
    window_ns = hi - lo
    executes = [s for n, s, _ in host if "Execute" in n and lo <= s <= hi]
    busy_ns, all_gaps = 0.0, []
    op_time: Dict[str, float] = defaultdict(float)
    module_time: Dict[str, float] = defaultdict(float)
    for dev in sorted(ops):
        merged = union((s, e) for _, s, e in ops[dev])
        if not merged:
            continue
        busy_ns += sum(e - s for s, e in merged)
        # device time -> host time
        shift = (min(executes) if executes else lo) - merged[0][0]
        first, last = merged[0][0] + shift, merged[-1][1] + shift
        all_gaps += [(s + shift, e + shift) for s, e in
                     gaps(merged, merged[0][0], merged[-1][1])]
        all_gaps += [g for g in ((lo, first), (last, hi)) if g[1] > g[0]]
        mods = sorted(modules.get(dev, []), key=lambda x: x[1])
        for name, s, e in mods:
            module_time[module_name(name)] += e - s
        j = 0
        for name, s, e in sorted(ops[dev], key=lambda x: x[1]):
            while j < len(mods) and mods[j][2] <= s:
                j += 1
            owner = (module_name(mods[j][0])
                     if j < len(mods) and mods[j][1] <= s else "?")
            op_time[f"{owner}/{op_name(name)}"] += e - s
    n_dev = max(len(ops), 1)
    busy_s = busy_ns * 1e-9 / n_dev
    top_gaps = sorted(all_gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": window_ns * 1e-9,
        "idle_share": 1.0 - busy_s / (window_ns * 1e-9),
        "module_s": {k: v * 1e-9 / n_dev for k, v in module_time.items()},
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_name_gap((g[0] + g[1]) / 2, host, window_name),
                       (g[1] - g[0]) * 1e-9] for g in top_gaps],
    }


def load(path: str):
    """(ops, modules, host) events of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    host: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    dst = ops if line.name == "XLA Ops" else modules
                    dst[dev] = [(e.name, e.start_ns, e.start_ns
                                 + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return ops, modules, host


def find_trace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_file(path: str, window_name: str = "window") -> dict:
    """`reduce_events` over a trace file, windowed on the host event
    ``window_name``; raises if the trace holds no device operation."""
    ops, modules, host = load(path)
    spans = [(s, e) for n, s, e in host if n == window_name]
    if not spans:
        raise ValueError(f"no host event {window_name!r} in {path}")
    if not any(ops.values()):
        raise ValueError(f"no device operation in {path}")
    return reduce_events(ops, modules, host, max(spans, key=lambda x: x[1]
                                                 - x[0]), window_name)
