"""Span/event tracing with a buffered JSONL exporter, plus the
process-wide observation session the instrumented seams report to.

Trace schema (one JSON object per line, in emission order):

    {"kind": "event", "name": "pull", "ts": 1.234, "attrs": {...}}
    {"kind": "span",  "name": "engine.chunk.wait", "ts": 0.31,
     "start": 0.30, "dur_s": 0.0121, "id": 7, "parent": null,
     "attrs": {...}}
    {"kind": "span",  "name": "round", "ts": ..., "dur_s": 0.08,
     "attrs": {...}}
    {"kind": "metric", "name": "pulls_total", "metric_type": "counter",
     "value": 49.0}

`ts` is seconds since the session opened (monotonic clock).  `span` rows
are appended at the span's END, so a trace is ordered by end time.  Rows
of `span(...)` carry their `start`, their own `id`, the `parent` id (the
innermost span open on the same thread when it started) and, for
per-request spans, `rid`; rows of `emit(..., dur_s=...)` carry only the
duration.  `metric` rows are the registry snapshot.  The session keeps
every row in memory and writes them, then the snapshot, when it closes,
so a single file holds both the timeline and the run totals
(`tools/trace_report.py` renders both).

Instrumentation contract — why this is safe on hot paths
--------------------------------------------------------
`span(name, acc=..., **attrs)` always enters a
`jax.profiler.TraceAnnotation` (about a microsecond, and a no-op unless
the profiler runs: then the span lands on the profiler's host plane, on
the clock the device trace is aligned to), adds its duration to `acc`
when one is given, and records a row only while a session is open.  The
point-event seams call the module-level `emit(...)` / `active()`
helpers: with no session open, `active()` is one global read and `emit`
returns immediately.  Observability is strictly additive and cannot
perturb numerics, RNG streams, or control flow, which is what keeps
default runs bit-identical to the uninstrumented code.

The well-known event names and the per-event metrics they drive live in
`_EVENT_METRICS` / `ObsSession.emit`; new seams can emit any name — every
event and span row also bumps a generic ``events_total.<name>`` counter.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from typing import IO, Dict, Optional, Union

from repro.obs.metrics import MetricsRegistry

#: The span clock: `Span.t0`/`t1` and, by default, the session's `ts`.
CLOCK = time.monotonic


def _json_default(value):
    """Serialize numpy/jax scalars and other strays without importing
    either library: anything with .item() unwraps, the rest reprs."""
    item = getattr(value, "item", None)
    if callable(item):
        try:
            return item()
        except Exception:  # noqa: BLE001 - fall through to repr
            pass
    return repr(value)


class ObsSession:
    """One observation session: a buffered JSONL trace sink + a metrics
    registry sharing one clock.  Open via `observing(path)` (the
    module-level context manager below) so instrumented seams see it.
    Rows are kept in memory and written when the session closes."""

    def __init__(self, sink: Union[str, IO[str], None], clock=CLOCK):
        self._own_sink = isinstance(sink, str)
        self._sink = open(sink, "w") if self._own_sink else sink
        self._clock = clock
        self.t0 = clock()
        self.metrics = MetricsRegistry()
        # Event rows (dicts) and spans (tuples, made rows at close), in
        # emission order.
        self._rows: list = []
        self._ids = itertools.count(1)
        self._open = threading.local()     # per-thread stack of span ids
        self.closed = False

    # -- per-event metric fan-out ------------------------------------------
    # event name -> list of (metric kind, metric name, attr key or None).
    # None attr key means "count the event"; histograms read the attr.
    _EVENT_METRICS = {
        "pull": [("counter", "pulls_total", None),
                 ("histogram", "pull_energy_j", "energy_j"),
                 ("histogram", "pull_latency_s", "latency_s"),
                 ("histogram", "pull_edp", "edp"),
                 ("histogram", "pull_cost", "cost")],
        "round.start": [("counter", "rounds_total", None)],
        "update": [("counter", "updates_total", None)],
        "update.stale": [("counter", "updates_stale_total", None),
                         ("histogram", "update_staleness", "staleness")],
        "commit": [("counter", "commits_total", None)],
        "dispatch.submit": [("counter", "dispatch_submits_total", None)],
        "dispatch.wave": [("counter", "dispatch_waves_total", None),
                          ("gauge", "dispatch_clock_s", "clock_s")],
        "engine.prefill": [("counter", "engine_prefills_total", None),
                           ("histogram", "engine_prefill_s", "dur_s")],
        "engine.decode": [("counter", "engine_decodes_total", None),
                          ("histogram", "engine_decode_s", "dur_s")],
        "engine.request": [("counter", "engine_requests_total", None),
                           ("histogram", "engine_request_latency_s",
                            "dur_s"),
                           ("histogram", "engine_queue_wait_s",
                            "queue_wait_s"),
                           ("histogram", "engine_request_tokens",
                            "tokens")],
        "sensor.run": [("gauge", "sensor_joules", "joules"),
                       ("gauge", "sensor_avg_w", "avg_watts"),
                       ("gauge", "sensor_peak_w", "peak_watts")],
        # Fault injection/degradation seams (repro.faults + the resilient
        # dispatcher/sensors/engine): injections vs responses count
        # separately so a chaos run's trace answers both "what was
        # injected" and "what did the stack do about it".
        "fault.inject": [("counter", "faults_injected_total", None)],
        "fault.sensor": [("counter", "sensor_faults_total", None)],
        "fault.pull": [("counter", "pull_faults_total", None)],
        "fault.retry": [("counter", "retries_total", None),
                        ("histogram", "retry_backoff_s", "backoff_s")],
        "fault.device": [("counter", "device_faults_total", None)],
        "fault.request": [("counter", "request_faults_total", None)],
    }

    def now(self) -> float:
        return self._clock() - self.t0

    def emit(self, name: str, kind: str = "event",
             dur_s: Optional[float] = None, **attrs) -> None:
        if self.closed:
            return
        row = {"kind": "span" if dur_s is not None else kind,
               "name": name, "ts": round(self.now(), 9)}
        if dur_s is not None:
            row["dur_s"] = float(dur_s)
        if attrs:
            row["attrs"] = attrs
        self._rows.append(row)
        self._fan_out(name, dur_s, attrs)

    def record_span(self, name: str, start: float, end: float, *,
                    rid=None, **attrs) -> None:
        """Append a span with no parent from two readings of the
        session's clock (`start`, `end`: absolute, as the clock returned
        them).  A span's row and metrics are made when the session
        closes, which keeps a span to a tuple append while the traced
        code runs."""
        if not self.closed:
            self._rows.append((name, start, end, next(self._ids), None, rid,
                               attrs))

    def _span_row(self, name, start, end, span_id, parent, rid,
                  attrs) -> dict:
        row = {"kind": "span", "name": name,
               "ts": round(end - self.t0, 9),
               "start": round(start - self.t0, 9),
               "dur_s": end - start, "id": span_id, "parent": parent}
        if rid is not None:
            row["rid"] = rid
        if attrs:
            row["attrs"] = attrs
        self._fan_out(name, end - start, attrs)
        return row

    def _stack(self) -> list:
        stack = getattr(self._open, "ids", None)
        if stack is None:
            stack = self._open.ids = []
        return stack

    def _fan_out(self, name: str, dur_s: Optional[float],
                 attrs: dict) -> None:
        self.metrics.counter(f"events_total.{name}").inc()
        for mkind, mname, key in self._EVENT_METRICS.get(name, ()):
            if mkind == "counter":
                self.metrics.counter(mname).inc()
            else:
                value = dur_s if key == "dur_s" else attrs.get(key)
                if value is None:
                    continue
                if mkind == "gauge":
                    self.metrics.gauge(mname).set(float(value))
                else:
                    self.metrics.histogram(mname).observe(float(value))

    def close(self) -> None:
        """Write the buffered rows and the metrics snapshot, and close
        the sink (idempotent)."""
        if self.closed:
            return
        rows = [r if isinstance(r, dict) else self._span_row(*r)
                for r in self._rows]
        ts = round(self.now(), 9)
        rows += [{"kind": "metric", "ts": ts, **snap}
                 for snap in self.metrics.snapshot()]
        if self._sink is not None:
            self._sink.write("".join(
                json.dumps(row, default=_json_default) + "\n"
                for row in rows))
            self._sink.flush()
            if self._own_sink:
                self._sink.close()
        self.closed = True


class PhaseTimes:
    """Per-call accumulator of span time: seconds and counts by span
    name (`span(..., acc=...)` adds to it, session or not)."""

    __slots__ = ("s", "n")

    def __init__(self):
        self.s: Dict[str, float] = {}
        self.n: Dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        self.s[name] = self.s.get(name, 0.0) + seconds
        self.n[name] = self.n.get(name, 0) + 1


_TraceAnnotation = None


def _annotation(name: str, attrs: dict):
    global _TraceAnnotation
    if _TraceAnnotation is None:        # jax is imported on first use only
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, **attrs)


class Span:
    """What `span(...)` returns: a context manager whose `t0`/`t1` are
    the readings of `CLOCK` at its start and end."""

    __slots__ = ("name", "acc", "rid", "attrs", "t0", "t1", "_ann",
                 "_sess", "_stack", "_id", "_parent")

    def __init__(self, name: str, acc: Optional[PhaseTimes], rid,
                 attrs: dict, start: Optional[float] = None):
        self.name, self.acc, self.rid, self.attrs = name, acc, rid, attrs
        self.t0, self.t1 = start, 0.0

    def __enter__(self) -> "Span":
        # The clock is read first on entry and last on exit, so the
        # annotation's own cost falls inside the span.
        if self.t0 is None:
            self.t0 = CLOCK()
        attrs = self.attrs if self.rid is None else dict(self.attrs,
                                                         rid=self.rid)
        self._ann = _annotation(self.name, attrs)
        self._ann.__enter__()
        sess = self._sess = _SESSION
        if sess is not None:
            stack = self._stack = sess._stack()
            self._parent = stack[-1] if stack else None
            self._id = next(sess._ids)
            stack.append(self._id)
        return self

    def __exit__(self, *exc) -> bool:
        sess = self._sess
        if sess is not None:
            stack = self._stack
            if stack and stack[-1] == self._id:
                stack.pop()
        self._ann.__exit__(*exc)
        self.t1 = CLOCK()
        if self.acc is not None:
            self.acc.add(self.name, self.t1 - self.t0)
        if sess is not None and not sess.closed:
            sess._rows.append((self.name, self.t0, self.t1, self._id,
                               self._parent, self.rid, self.attrs))
        return False


def span(name: str, *, acc: Optional[PhaseTimes] = None, rid=None,
         start: Optional[float] = None, **attrs) -> Span:
    """A span around the enclosed code: a profiler annotation named
    `name` with `attrs` (and `rid`) as its stats, its duration added to
    `acc` under `name`, and a row in the open session, if any.  `start`,
    a reading of `CLOCK` such as the previous span's `t1`, begins the
    span there instead of at entry, so consecutive phases tile an
    interval with no time between them left out."""
    return Span(name, acc, rid, attrs, start)


def record_span(name: str, start: float, end: float, *, rid=None,
                **attrs) -> None:
    """A span row from two readings of `CLOCK` into the active session,
    for a span known only once it ended (no-op when none is open)."""
    if _SESSION is not None:
        _SESSION.record_span(name, start, end, rid=rid, **attrs)


# ---------------------------------------------------------------------------
# The process-wide active session (None = observability disabled, the
# default: `active()` is a single global read on hot paths)
# ---------------------------------------------------------------------------

_SESSION: Optional[ObsSession] = None


def session() -> Optional[ObsSession]:
    """The active observation session, or None when disabled."""
    return _SESSION


def active() -> bool:
    """Cheap hot-path guard: is an observation session open?"""
    return _SESSION is not None


def set_session(sess: Optional[ObsSession]) -> Optional[ObsSession]:
    """Install `sess` as the active session; returns the previous one."""
    global _SESSION
    prev, _SESSION = _SESSION, sess
    return prev


def emit(name: str, kind: str = "event", dur_s: Optional[float] = None,
         **attrs) -> None:
    """Emit an event/span into the active session (no-op when none)."""
    if _SESSION is not None:
        _SESSION.emit(name, kind=kind, dur_s=dur_s, **attrs)


@contextlib.contextmanager
def observing(sink: Union[str, IO[str], None]):
    """Open an observation session writing JSONL to `sink` (a path or a
    file-like object), install it for the instrumented seams, and close
    it (appending the metrics snapshot) on exit.  Yields the session.

    Nesting restores the previous session on exit, so a benchmark
    harness can observe a whole sweep while an inner tool observes one
    run.
    """
    sess = ObsSession(sink)
    prev = set_session(sess)
    try:
        yield sess
    finally:
        set_session(prev)
        sess.close()
