"""Structured observability for the TB pipeline (DESIGN.md §7).

Three layers, one import:

  spans    nestable wall-clock spans + Chrome-trace export
           (`span`, `annotate`, `enable`, `collector`);
  metrics  named counters/gauges/histograms behind a process registry
           (`registry().counter("plan_cache.hits").inc()`);
  drift    predicted-vs-measured ledger for the cost model
           (`predict_plan_terms`, `DriftLedger`, `last_drift`).

Spans are OFF until `enable()` (the `--telemetry` flag); metrics are
always on (a counter bump is one lock); drift is explicit bookkeeping.
This package deliberately imports nothing from kernels/distributed/survey
so every layer can instrument itself without import cycles (`drift`
depends only on `core.temporal_blocking`).
"""
from repro.telemetry import metrics  # noqa: F401  (submodule re-export)
from repro.telemetry.drift import (DEFAULT_PATH as DRIFT_PATH,  # noqa: F401
                                   DriftLedger, last_drift,
                                   predict_hier_terms, predict_plan_terms)
from repro.telemetry.metrics import MetricsRegistry, registry  # noqa: F401
from repro.telemetry.spans import (SpanCollector, active,  # noqa: F401
                                   annotate, collector, disable, enable,
                                   span)

__all__ = [
    "DRIFT_PATH", "DriftLedger", "MetricsRegistry", "SpanCollector",
    "active", "annotate", "collector", "disable", "enable",
    "last_drift", "metrics", "predict_hier_terms",
    "predict_plan_terms", "registry", "span",
]
