"""repro.obs — the training telemetry plane.

Structured metrics sinks (JSONL canonical / in-memory) behind a
non-blocking :class:`MetricsLogger`, the in-jit per-layer-group gradient
statistics collector (:class:`StatsPolicy` — the paper's Fig. 4/10
quantities as live metrics), host-side step-time + device-memory
accounting, and profiler trace hooks. See README.md in this package for
the metric catalogue and schema.
"""
from .metrics import (SCHEMA, JSONLSink, MemorySink, MetricsLogger,
                      jsonable, validate_jsonl, validate_record)
from .profile import ProfileWindow
from .stats import StatsPolicy, make_stats_fn, split_stats, stats_keys
from .timing import StepTimer, device_memory

__all__ = [
    "SCHEMA", "JSONLSink", "MemorySink", "MetricsLogger",
    "jsonable", "validate_jsonl", "validate_record",
    "ProfileWindow",
    "StatsPolicy", "make_stats_fn", "split_stats", "stats_keys",
    "StepTimer", "device_memory",
]
