"""Serving telemetry subsystem.

Host-side, low-overhead observability for the paged SPLS serving stack:
typed metrics (:mod:`metrics`), per-request lifecycle tracing as Chrome
trace events (:mod:`trace`), SPLS sparsity instruments
(:mod:`sparsity`), the engine-facing facade (:mod:`telemetry`), and the
``BENCH_serving.json`` report builder/validator (:mod:`report`).  See
``serving/README.md`` ("Observability") for the instrument table and
how to open traces in Perfetto.
"""

from .metrics import (Counter, CounterDictView, Gauge, Histogram,
                      MetricsRegistry, NullInstrument, percentile)
from .trace import ENGINE_TRACK, PHASE_PID, TraceRecorder
from .sparsity import SparsityInstruments, tree_bytes
from .telemetry import RequestRecord, Telemetry
from .report import (SCHEMA_VERSION, latency_ms, serving_report,
                     validate_report, write_report)

__all__ = [
    "Counter", "CounterDictView", "Gauge", "Histogram", "MetricsRegistry",
    "NullInstrument", "percentile", "ENGINE_TRACK", "PHASE_PID",
    "TraceRecorder",
    "SparsityInstruments", "tree_bytes",
    "RequestRecord", "Telemetry",
    "SCHEMA_VERSION", "latency_ms", "serving_report", "validate_report",
    "write_report",
]
