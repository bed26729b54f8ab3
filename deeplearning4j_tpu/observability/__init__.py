"""Always-on, low-overhead telemetry for the TPU port.

The reference's observability stack (listeners + StatsStorage + training UI)
is event-push per iteration; this package adds the aggregate layer the
TPU-native failure modes need — silent jit recompiles, host/device skew,
HBM growth, collective traffic — exposed as Prometheus text on the UI
server's ``/metrics`` route and as JSONL snapshots via ``--telemetry-out``.

    from deeplearning4j_tpu.observability import (
        global_registry, global_tracker, span, TelemetryListener)
"""
from . import names
from .metrics import (MetricsRegistry, global_registry, DEFAULT_BUCKETS,
                      tree_nbytes)
from .compile_tracker import CompileTracker, global_tracker
from .spans import span
from .tracing import (TraceStore, Span, SpanRef, trace_span, start_span,
                      current_span, parse_traceparent, format_traceparent,
                      global_trace_store, set_global_trace_store,
                      TRACEPARENT_HEADER)
from .slo import SLO, SLOEngine, default_serve_objectives
from .federation import (FederatedRegistry, MetricsPublisher, FleetCollector,
                         merge_snapshots, global_federation,
                         set_global_federation, global_fleet_collector,
                         set_global_fleet_collector, register_status_provider,
                         fleet_status, fleet_metrics_text,
                         trigger_fleet_dump)
from .listener import TelemetryListener, record_hbm_gauges
from .flight_recorder import (FlightRecorder, global_recorder,
                              dump_on_unhandled, install_signal_handlers,
                              uninstall_signal_handlers)
from .health import (HealthMonitor, NanAlertListener, TrainingDivergedError,
                     is_invalid_score, health_terms)
from .watchdog import (StepWatchdog, install_watchdog, uninstall_watchdog,
                       global_watchdog, beat)
from .profiler import (TraceSession, StepAnomalyWatcher, global_trace_session,
                       install_anomaly_watcher, uninstall_anomaly_watcher,
                       note_dispatch)
from . import xplane

__all__ = [
    "MetricsRegistry", "global_registry", "DEFAULT_BUCKETS", "tree_nbytes",
    "CompileTracker", "global_tracker",
    "span", "names",
    "TraceStore", "Span", "SpanRef", "trace_span", "start_span",
    "current_span", "parse_traceparent", "format_traceparent",
    "global_trace_store", "set_global_trace_store", "TRACEPARENT_HEADER",
    "SLO", "SLOEngine", "default_serve_objectives",
    "FederatedRegistry", "MetricsPublisher", "FleetCollector",
    "merge_snapshots", "global_federation", "set_global_federation",
    "global_fleet_collector", "set_global_fleet_collector",
    "register_status_provider", "fleet_status", "fleet_metrics_text",
    "trigger_fleet_dump",
    "TelemetryListener", "record_hbm_gauges",
    "FlightRecorder", "global_recorder", "dump_on_unhandled",
    "install_signal_handlers", "uninstall_signal_handlers",
    "HealthMonitor", "NanAlertListener", "TrainingDivergedError",
    "is_invalid_score", "health_terms",
    "StepWatchdog", "install_watchdog", "uninstall_watchdog",
    "global_watchdog", "beat",
    "TraceSession", "StepAnomalyWatcher", "global_trace_session",
    "install_anomaly_watcher", "uninstall_anomaly_watcher", "note_dispatch",
    "xplane",
]
