"""repro.obs: zero-sync observability for the propagation engines.

Three halves, one subsystem (docs/OBSERVABILITY.md):

* ``obs.telemetry`` -- the DEVICE half: a fixed-capacity
  :class:`TelemetryPlane` carried through every fixed-point while_loop
  (per-round progress ring, round/early-stop/infeasibility counters), read
  back only where the host already syncs.  Telemetry-on is bitwise
  identical to telemetry-off by construction.
* ``obs.trace`` -- the HOST half: a :class:`Tracer` of structured spans
  in a bounded ring, each also a ``jax.profiler`` trace annotation, with
  per-name totals and per-site counters; :data:`PROGRAM` is the program's
  own, always on (entry points, uploads, launches), and the service takes
  one for its pump/admit/readback cycle and per-ticket lifecycles.
* ``obs.metrics`` -- the AGGREGATION half: a :class:`MetricsRegistry`
  putting every ad-hoc source (LRU cache_info, compile counts, fill
  histograms, service counters) behind one pinned-schema ``snapshot()``,
  plus :func:`run_metadata` for attributable bench merges.

``obs.timing`` carries the shared fenced-timing utilities (block-until-
ready fencing, paired-trials median) the benches build their rows from.
"""
from .metrics import (
    SNAPSHOT_KEYS,
    SNAPSHOT_SCHEMA_VERSION,
    MetricsRegistry,
    default_registry,
    run_metadata,
)
from .telemetry import (
    DEFAULT_CAPACITY,
    TelemetryPlane,
    TelemetrySnapshot,
    device_plane,
    host_snapshot,
    record_round,
    reset_rows,
)
from .timing import (
    fence,
    median_of,
    median_ratio,
    paired_trials,
    time_fenced,
    time_phases,
)
from .trace import (
    DEFAULT_SPAN_CAPACITY,
    NULL_TRACER,
    PROGRAM,
    SPAN_KEYS,
    SPAN_SCHEMA_VERSION,
    NullTracer,
    Span,
    Tracer,
)

__all__ = [
    "DEFAULT_CAPACITY",
    "DEFAULT_SPAN_CAPACITY",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "PROGRAM",
    "SNAPSHOT_KEYS",
    "SNAPSHOT_SCHEMA_VERSION",
    "SPAN_KEYS",
    "SPAN_SCHEMA_VERSION",
    "Span",
    "TelemetryPlane",
    "TelemetrySnapshot",
    "Tracer",
    "default_registry",
    "device_plane",
    "fence",
    "host_snapshot",
    "median_of",
    "median_ratio",
    "paired_trials",
    "record_round",
    "reset_rows",
    "run_metadata",
    "time_fenced",
    "time_phases",
]
