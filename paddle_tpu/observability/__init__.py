"""paddle_tpu.observability — metrics, tracing, debug server, flight recorder.

The measurement layer the north star requires (ROADMAP: serve heavy
traffic, run as fast as the hardware allows — neither is checkable
without numbers). Four parts:

- metrics: Counter / Gauge / Histogram families with labels, one
  process-wide ``MetricRegistry`` (the superset of the reference's
  platform/monitor.h StatRegistry, which ``core.monitor`` now fronts);
- tracing: request/step-scoped ``Span`` trees (ids, parent links,
  attributes, events) in a bounded process-wide table — the causal
  view the aggregates can't give ("why was THIS request 40x p50");
  off by default, near-zero overhead when disabled;
- exporters: Prometheus text exposition, chrome://tracing JSON merging
  spans + profiler host annotations onto one timeline, a periodic
  JSONL file reporter (atexit-flushed), jax device-memory gauges;
- goodput: the wall-clock time ledger (``/goodputz``) — every second
  since arming attributed to one bucket (productive vs the badput
  classification), reconciled with an explicit unattributed residual, with
  SLO-trip watermark forensics and fleet federation;
- memory: the HBM attribution ledger (``/memz``) — owners register
  reservations at allocation boundaries, reads reconcile against
  ``device.memory_stats()`` with an explicit unattributed residual,
  and RESOURCE_EXHAUSTED becomes a flight dump carrying the
  per-owner table;
- server + flight: a live HTTP debug surface (``/metrics /healthz
  /statusz /tracez /perfz /memz`` + ``POST /profilez``) and a crash
  flight recorder that dumps the recent-span ring to JSONL on
  unhandled exceptions, SIGTERM, and elastic preemption.

Hot paths ship instrumented: ``inference.llm`` (metrics + a span tree
per request: queue → prefill chunks → first token → decode),
``hapi.Model`` (metrics + epoch/dispatch/metric-drain spans),
``io.checkpoint``, ``distributed.elastic``, and the DataLoader
prefetch path. Metric names and the span table are tabled in
docs/OBSERVABILITY.md.
"""

from .metrics import (BYTE_BUCKETS, DEFAULT_BUCKETS,  # noqa: F401
                      RATE_BUCKETS, RATIO_BUCKETS, CounterChild,
                      GaugeChild, HistogramChild, MetricFamily,
                      MetricRegistry, default_registry)
from .exporters import (JSONLReporter, export_chrome_tracing,  # noqa: F401
                        prometheus_text, sample_device_memory,
                        write_prometheus)
from . import audit  # noqa: F401
from . import goodput  # noqa: F401
from . import memory  # noqa: F401
from . import perf  # noqa: F401
from . import propagation  # noqa: F401
from . import tracing  # noqa: F401
from .tracing import Span, SpanContext, start_span  # noqa: F401
from .tracing import span as trace_span  # noqa: F401
from .propagation import (TRACEPARENT_HEADER,  # noqa: F401
                          format_traceparent, parse_traceparent)
from .server import (DebugServer, get_debug_server,  # noqa: F401
                     register_status_provider, start_debug_server,
                     stop_debug_server, unregister_status_provider)
from .slo import SLOTracker  # noqa: F401
from .flight import (FlightRecorder, dump_flight_record,  # noqa: F401
                     get_flight_recorder, install_flight_recorder)

enable_tracing = tracing.enable
disable_tracing = tracing.disable
tracing_enabled = tracing.enabled

__all__ = [
    "BYTE_BUCKETS", "DEFAULT_BUCKETS", "RATE_BUCKETS", "RATIO_BUCKETS",
    "CounterChild", "GaugeChild", "HistogramChild",
    "MetricFamily", "MetricRegistry", "default_registry",
    "JSONLReporter", "export_chrome_tracing", "prometheus_text",
    "sample_device_memory", "write_prometheus",
    "goodput", "memory", "perf",
    "tracing", "Span", "SpanContext", "start_span", "trace_span",
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "propagation", "TRACEPARENT_HEADER", "format_traceparent",
    "parse_traceparent", "SLOTracker",
    "DebugServer", "start_debug_server", "get_debug_server",
    "stop_debug_server", "register_status_provider",
    "unregister_status_provider",
    "FlightRecorder", "install_flight_recorder", "get_flight_recorder",
    "dump_flight_record",
]
