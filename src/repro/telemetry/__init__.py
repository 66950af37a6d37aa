"""Observability for the whole sim stack: metrics, traces, dashboards.

Zero-cost when disabled — the ambient registry defaults to a no-op
singleton, and inside jit the solver paths carry only named scopes and an
integer round counter.
See ``docs/observability.md`` for the metrics catalog and usage.
"""

from .metrics import (MetricsRegistry, NullRegistry, NULL_METRICS,
                      get_metrics, collecting, span)
from .trace import (LinkSeriesPolicy, TraceRecorder, get_recorder,
                    recording, validate_trace)

__all__ = [
    "MetricsRegistry", "NullRegistry", "NULL_METRICS", "get_metrics",
    "collecting", "span",
    "LinkSeriesPolicy", "TraceRecorder", "get_recorder", "recording",
    "validate_trace",
]
