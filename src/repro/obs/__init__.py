"""Observability: span tracing, engine metrics and plan EXPLAIN reporting.

Wire-in point: ``ExecutionConfig(trace=True)`` (or a custom
:class:`~repro.obs.trace.TraceConfig`) — every runtime then records
per-stage spans and device-side engine metrics, surfaced uniformly through
``RegisteredQuery.last_stats`` and ``RegisteredQuery.explain()``.  With
tracing off (the default) the runtimes compile the exact pre-observability
programs — pinned by tests/test_obs.py.  The engine-layer scopes
(``dscep.<layer>`` in each device op's ``op_name``) are always on; they
change no program.
"""
from .trace import (
    LAYERS, TraceConfig, Tracer, in_layer, layer_scope, resolve_trace,
    span_or_null,
)
from .metrics import (
    CATALOG, finalize_stats, merge_stats, reduce_stats, saturation,
    stat_add, stat_max,
)
from .report import (
    attach_saturation, bottleneck_stage, format_explain,
    format_metrics_table, format_stage_table, to_json,
)

__all__ = [
    "LAYERS", "TraceConfig", "Tracer", "in_layer", "layer_scope",
    "resolve_trace", "span_or_null",
    "CATALOG", "finalize_stats", "merge_stats", "reduce_stats",
    "saturation", "stat_add", "stat_max",
    "attach_saturation", "bottleneck_stage", "format_explain",
    "format_metrics_table", "format_stage_table", "to_json",
]
