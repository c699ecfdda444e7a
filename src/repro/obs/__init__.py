"""Runtime observability tier.

Three pieces, all dependency-free (numpy + stdlib only — safe to import
from device-side modules without pulling in jax):

- :mod:`repro.obs.histogram` — fixed-bucket log-scale latency histograms
  with exact merge algebra, giving streaming p50/p95/p99/p99.9 without
  storing raw samples.
- :mod:`repro.obs.trace` — a low-overhead ``Span``/``Tracer`` API for
  host-side per-phase wall-clock (dispatch, device step, unpack, journal
  flush, checkpoint, compaction tick, hot-swap pause), with optional
  structured JSONL export; a ``Tracer`` also annotates the JAX
  profiler's trace (it imports ``jax.profiler`` when made).
- :mod:`repro.obs.metrics` — the per-owner/per-stage device metrics
  block that rides the serving step's existing stacked all-reduce
  (field order contract + host-side attribution helpers, including the
  cache hit-locality signal for the future cache-locality router).

:mod:`repro.obs.telemetry` composes the three into ``ServeTelemetry``,
the serve-loop aggregator used by ``repro.launch.serve``;
:mod:`repro.obs.schema` validates the emitted JSONL trace events
(``python -m repro.obs.validate trace.jsonl``).

See ``docs/OBSERVABILITY.md`` for the trace format and how to read one.
"""

from repro.obs.histogram import LatencyHistogram
from repro.obs.metrics import (
    OWNER_STAGE_FIELDS,
    attribute_step_seconds,
    hit_locality,
    owner_stage_rows,
)
from repro.obs.trace import NULL_TRACER, JsonlTraceWriter, NullTracer, Tracer

__all__ = [
    "LatencyHistogram",
    "OWNER_STAGE_FIELDS",
    "attribute_step_seconds",
    "hit_locality",
    "owner_stage_rows",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "JsonlTraceWriter",
]
