"""Structured observability for the TB pipeline (DESIGN.md §7).

Two layers, one import:

  spans    nestable wall-clock spans + Chrome-trace export
           (`span`, `annotate`, `enable`, `collector`);
  metrics  named counters/gauges/histograms behind a process registry
           (`registry().counter("plan_cache.hits").inc()`).

Spans are OFF until `enable()` (the `--telemetry` flag); metrics are
always on (a counter bump is one lock).  This package deliberately imports
nothing from kernels/distributed/survey so every layer can instrument
itself without import cycles.
"""
from repro.telemetry import metrics  # noqa: F401  (submodule re-export)
from repro.telemetry.metrics import MetricsRegistry, registry  # noqa: F401
from repro.telemetry.spans import (SpanCollector, active,  # noqa: F401
                                   annotate, collector, disable, enable,
                                   span)

__all__ = [
    "MetricsRegistry", "SpanCollector", "active", "annotate", "collector",
    "disable", "enable", "metrics", "registry", "span",
]
