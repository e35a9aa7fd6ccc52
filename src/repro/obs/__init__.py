"""repro.obs — the unified metrics/tracing layer.

One emission protocol (``Tracker``: counters, gauges, timer histograms,
events, ``scope`` context tags) with pluggable sinks, and one
process-wide seam (``configure()`` / ``current_tracker()``) that every
instrumented subsystem — ``SamplingService``, ``SpectralCache``,
``LearningEngine``/``learning.fit``, the ``kernels.ops`` dispatch, the
``Mesh`` runtime — emits through.

On top of the flat metrics sit two request-level subsystems:

  * ``repro.obs.spans`` — causal span traces (``start_span`` /
    ``Span`` / ``emit_span``) riding the same sinks as ``event("span",
    ...)`` records; export a JSONL run log to ``chrome://tracing`` with
    ``repro.obs.export.ChromeTraceExporter`` or summarize it with
    ``python -m repro.obs.report``;
  * ``repro.obs.health`` — numerics sentinels (PSD margins, condition
    numbers, backtrack/truncation streaks, nonfinite-LL flags) folded
    into a ``healthy/degraded/failing`` verdict by ``HealthMonitor``,
    surfaced as ``health.*`` gauges, one ``health.report`` event, and
    the ``FitReport.health`` / ``ServiceStats.health`` fields.

The default sink is the zero-overhead ``NullTracker``: uninstrumented
behavior and throughput are bit-identical to not having this package
(pinned by ``tests/test_obs.py``; ``start_span`` against it returns one
shared inert span). Turning observability on is one line:

    from repro import obs
    obs.configure(jsonl="run_log.jsonl")        # append-only run log
    # or, for programmatic inspection:
    t = obs.InMemoryTracker()
    obs.configure(t)
    ...
    print(t.snapshot())

See the README "Observability" section for the metric namespaces
(``service.*``, ``spectral_cache.*``, ``learning.*``, ``kernels.*``,
``runtime.mesh.*``, ``health.*``), the span model, reading a JSONL run
log or a Chrome trace, seeing live spans in a ``jax.profiler`` trace,
and the benchmark regression gate (``python -m benchmarks.regression``).
"""

from . import export, health, spans
from .export import ChromeTraceExporter, read_run_log
from .health import HealthMonitor, HealthThresholds
from .spans import (NULL_SPAN, Span, current_span, emit_span, new_trace_id,
                    start_span)
from .tracker import (InMemoryTracker, JsonlTracker, NullTracker, TeeTracker,
                      Tracker, configure, current_tracker, enabled, tee, use)

__all__ = [
    "Tracker", "NullTracker", "InMemoryTracker", "JsonlTracker",
    "TeeTracker", "configure", "current_tracker", "enabled", "tee", "use",
    "spans", "Span", "start_span", "current_span", "emit_span",
    "new_trace_id", "NULL_SPAN",
    "health", "HealthMonitor", "HealthThresholds",
    "export", "ChromeTraceExporter", "read_run_log",
]
