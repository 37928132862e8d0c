"""repro.obs — unified tracing, metrics, and profiling for the stack.

* :mod:`repro.obs.trace` — span tracer (virtual clock + host ms of each
  launch call) with a no-op default so the untraced path stays
  overhead-free, and ``span`` for spans on the profiler's clock;
* :mod:`repro.obs.chrome_trace` — ``chrome://tracing`` / Perfetto export;
* :mod:`repro.obs.metrics` — counters/gauges/histograms and the
  schema-versioned RunReport both protocol drivers emit;
* :mod:`repro.obs.report` — ``python -m repro.obs.report run.json`` CLI
  (summary + A/B diff, ``--json`` for machines);
* :mod:`repro.obs.ledger` — append-only JSONL run-history store (every
  driver completion + bench row; env fingerprint, core signature);
* :mod:`repro.obs.sentinel` — ``python -m repro.obs.sentinel``: flags
  perf/correctness/convergence regressions vs the ledger baseline;
* :mod:`repro.obs.health` — live in-run watchers (MSE divergence/stall,
  quantizer saturation, stale/death storms, queue blowup) firing
  ``alert`` spans; NullMonitor default keeps the hot path free.

See docs/observability.md for the span categories, the RunReport schema,
the ledger record schema, and worked examples.
"""
from .trace import NULL, CATEGORIES, NullTracer, Span, Tracer, as_tracer
from .metrics import (REPORT_SCHEMA_VERSION, Histogram, Registry,
                      build_run_report, diff_reports, mse_trajectory,
                      profile_snapshot, record_profile, report_core,
                      reports_equal_modulo_timing, summary)
from .health import (NULL_MONITOR, HealthMonitor, NullMonitor, Thresholds,
                     as_monitor)
from .ledger import core_signature, env_fingerprint, record_run

__all__ = [
    "NULL", "CATEGORIES", "NullTracer", "Span", "Tracer", "as_tracer",
    "REPORT_SCHEMA_VERSION", "Histogram", "Registry", "build_run_report",
    "diff_reports", "mse_trajectory", "profile_snapshot", "record_profile",
    "report_core", "reports_equal_modulo_timing", "summary",
    "NULL_MONITOR", "HealthMonitor", "NullMonitor", "Thresholds",
    "as_monitor", "core_signature", "env_fingerprint", "record_run",
]
