"""repro.obs: observability — span tracing, metrics, drift monitoring.

The measurement counterpart of the repo's three predictors (analytic
telemetry, wall-clock simulator, calibrated presets):

  * :mod:`repro.obs.trace`   — low-overhead span tracer (context-manager
    API, monotonic clocks, parent and round per span, thread-safe ring
    buffer, no-op when disabled) with Chrome trace-event JSON export
    (Perfetto-loadable); each span is mirrored as a ``jax.profiler``
    annotation, and synthetic spans let the simulator replay onto the
    same timeline.
  * :mod:`repro.obs.metrics` — process-wide registry of counters / gauges
    / histograms with exact, version-pinned quantiles and JSONL export.
  * :mod:`repro.obs.drift`   — per-round measured-vs-predicted ratio
    ledger with configurable warn thresholds (the regression oracle every
    perf PR checks against).
  * :mod:`repro.obs.profile` — opt-in ``jax.profiler`` traces and
    compile-event capture onto the tracer.

The process-wide tracer starts DISABLED: instrumented hot paths
(``core/rounds.py``, ``serve/engine.py``, ``sim/events.py``) pay one
attribute check until an entry point opts in (``--trace-out``,
``--jax-profile`` or ``repro.obs.enable()``).
"""

from repro.obs.drift import (DriftMonitor, DriftRecord, from_history,
                             measured_round_s, predicted_round_s)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               load_jsonl, quantile, registry, summary_stats)
from repro.obs.profile import capture_compiles, jax_profile, record_compile
from repro.obs.trace import (NULL_SPAN, PID_MEASURED, PID_SIM, SpanEvent,
                             Tracer, disable, enable, get_tracer, instant,
                             span)

__all__ = [
    "Counter", "DriftMonitor", "DriftRecord", "Gauge", "Histogram",
    "MetricsRegistry", "NULL_SPAN", "PID_MEASURED", "PID_SIM", "SpanEvent",
    "Tracer", "capture_compiles", "disable", "enable", "from_history",
    "get_tracer", "instant", "jax_profile", "load_jsonl",
    "measured_round_s", "predicted_round_s", "quantile", "record_compile",
    "registry", "span", "summary_stats",
]
