"""repro.obs — pluggable power sensing, metrics, and tracing.

The observability subsystem behind the `Platform.power` contract:

* `sensors` — `PowerSensor` implementations (`SimulatedSensor` wrapping
  the analytical `Platform.power`, Jetson `SysfsRailsSensor`,
  `NVMLSensor`, deterministic `ReplaySensor` / `RecordingSensor` JSONL
  traces) and `make_sensor("replay:<path>")`-style spec parsing.
* `meter` — `EnergyMeter`: background sampling at a configurable rate,
  trapezoidal integration, `measure()` context manager returning
  joules / avg watts / peak watts.
* `metrics` — counters, gauges, histograms in a `MetricsRegistry`.
* `tracing` — spans and events with a buffered JSONL exporter and the
  process-wide observation session: `observing(path)` opens a session;
  `span(name, acc=..., **attrs)` marks a phase on the profiler's host
  plane, adds its time to a per-call `PhaseTimes` and records a row with
  its start, end and parent while a session is open; point events go
  through `emit(...)` (a no-op when no session is open, so default runs
  stay bit-identical); closing writes the rows and the metrics snapshot
  to the same file.

Import-light by design (stdlib only at import time): the controller,
platform, and serving layers all emit through this package, so it must
never import them back.  See docs/TELEMETRY.md for the sensor matrix,
trace schema, and capture/replay workflow.
"""

from repro.obs.meter import EnergyMeter, Measurement
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry)
from repro.obs.sensors import (FallbackSensor, NVMLSensor, PowerSensor,
                               RecordingSensor, ReplaySensor,
                               SensorUnavailable, SimulatedSensor,
                               SysfsRailsSensor, autodetect_sensor,
                               make_sensor)
from repro.obs.tracing import (ObsSession, PhaseTimes, active, emit,
                               observing, record_span, session,
                               set_session, span)

__all__ = [
    "EnergyMeter", "Measurement",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "FallbackSensor", "NVMLSensor", "PowerSensor", "RecordingSensor",
    "ReplaySensor", "SensorUnavailable", "SimulatedSensor",
    "SysfsRailsSensor", "autodetect_sensor", "make_sensor",
    "ObsSession", "PhaseTimes", "active", "emit", "observing",
    "record_span", "session", "set_session", "span",
]
