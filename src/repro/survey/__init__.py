"""Multi-shot survey engine (DESIGN.md §6).

A seismic survey fires thousands of independent shots over ONE model; the
Devito lesson (Luporini et al., PAPERS.md) is that the winning systems
amortize everything shot-invariant — the autotune sweep, the compiled
executable — across those invocations.  This package is that layer:

  plan_cache   memory+disk cache over the `(tile, T, outer_T, overlap)`
               autotune sweeps of `core.temporal_blocking`, keyed by the
               full pricing configuration — one sweep per configuration,
               ever.
  shots        `Shot`/`Survey` descriptions plus bucketing by padded
               (nsrc, nrec) so the number of distinct compiled shapes is
               bounded regardless of survey size.
  engine       `SurveyEngine`: one jitted executable per (physics,
               bucket), mapping the single-device TB propagator
               (`kernels/ops.tb_propagate_prepared`) over a shot axis,
               with receiver-trace host transfer double-buffered against
               device compute.
"""
from repro.survey.plan_cache import (CacheInfo, PlanCache,  # noqa: F401
                                     cached_plan_for_physics,
                                     cached_plan_hierarchy, default_cache,
                                     plan_cache_key)
from repro.survey.shots import Shot, Survey, bucket_shots  # noqa: F401
from repro.survey.engine import (RUN_STATS_KEYS, SurveyEngine,  # noqa: F401
                                 SurveyResult)
