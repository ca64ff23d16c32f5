"""repro.obs — structured tracing, metrics, and trace export for the GenFV
round pipeline.

Three pieces (DESIGN.md §Observability):

* `trace.Obs` — a span/event tracer with an explicit injectable clock and
  JIT-aware timing: spans fence with `jax.block_until_ready` only at span
  boundaries (via `Span.sync`) and tag the first call through each
  (name, key) pair as ``stage="compile"`` vs steady-state ``"execute"``.
* `metrics.MetricsRegistry` — counters / gauges / distributions fed by what
  the pipeline already computes (planner convergence, bucket padding waste,
  the fault ledger, realized-vs-planned round delay, sweep cache hits).
* `sinks` — a Chrome/Perfetto ``trace.json`` exporter and the versioned
  ``repro.obs/metrics/v1`` artifact written alongside the `repro.exp`
  outputs under ``artifacts/``.

The hard invariant: the disabled path (`NULL_OBS`) is a no-op that never
touches RNG streams or jitted programs, and the ENABLED path only reads —
so runs with obs on and off are bitwise-identical (tests/test_obs.py pins
this on both planner backends, with and without fault injection).
"""
from repro.obs.metrics import MetricsRegistry
from repro.obs.sinks import (BENCH_SCHEMA, METRICS_SCHEMA, host_meta,
                             list_metrics_artifacts, load_metrics_artifact,
                             save_metrics_artifact)
from repro.obs.trace import (NULL_OBS, NullObs, Obs, ProgressLogger, Span,
                             Stopwatch, VirtualClock, log_line, stopwatch)

__all__ = [
    "BENCH_SCHEMA", "METRICS_SCHEMA", "MetricsRegistry", "NULL_OBS",
    "NullObs", "Obs", "ProgressLogger", "Span", "Stopwatch", "VirtualClock",
    "host_meta", "list_metrics_artifacts", "load_metrics_artifact",
    "log_line", "save_metrics_artifact", "stopwatch",
]
