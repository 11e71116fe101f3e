"""Runtime observability — always-on (but switchable) view of what the
communicators, iterators, and trainer are doing while a job runs.

**Beyond-reference addition** (the reference had only after-the-fact nvprof
captures; `utils/trace.py` is the post-hoc analogue here).  Three layers:

* :mod:`registry` — a low-overhead process-wide metrics registry
  (counters, gauges, histograms with labels, monotonic-clock timers);
* :mod:`instrument` — instrumented communicators: per-collective call
  counts, payload bytes, wire dtype, and host-side latency for
  ``allreduce_grad`` / ``bcast_data`` / object-plane send/recv, plus
  ``jax.profiler.TraceAnnotation`` spans so profiler captures line up
  with the ``utils/trace.py`` tables;
* :mod:`straggler` + :class:`MetricsReport` (training/extensions) —
  per-step breakdown (data-load / dispatch / blocked-on-device time,
  examples/sec) and a periodic cross-rank straggler report allgathered
  through the communicator's control plane.

The master switch is process-wide: :func:`enable` / :func:`disable` /
:func:`enabled`, or the ``CHAINERMN_TPU_OBSERVABILITY`` env var (any
non-empty value other than ``0``).  Every data-path seam checks it ONCE
at construction time, so a disabled run makes zero observability calls
per iteration on the hot path.
"""

from chainermn_tpu.observability.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    StreamingHistogram,
    disable,
    enable,
    enabled,
    get_registry,
)
from chainermn_tpu.observability.sinks import (
    append_jsonl,
    atomic_write_json,
    prometheus_text,
    read_jsonl,
    write_prometheus,
    write_snapshot_jsonl,
)
from chainermn_tpu.observability.instrument import (
    InstrumentedCommunicator,
    instrument_communicator,
)
from chainermn_tpu.observability.straggler import (
    AttributionWatch,
    StepTelemetry,
    StragglerDetector,
    straggler_report,
    summarize_durations,
)
from chainermn_tpu.observability.spans import (
    Span,
    build_step_trees,
)
from chainermn_tpu.observability.attribution import (
    BUCKETS,
    attribute_step,
    attribution_report,
    clock_handshake,
    critical_path,
    merge_ranks,
    offset_from_samples,
    span_summary,
    to_trace_events,
)
from chainermn_tpu.observability.flight_recorder import (
    FlightRecorder,
    get_flight_recorder,
    identify_desync,
    install_flight_recorder,
    reset_flight_recorder,
)
from chainermn_tpu.observability.contention import (
    attribution_consistency,
    contention_report,
    feed_link_observations,
    leaf_comm_spans,
    link_rates,
    occupancy_from_events,
    occupancy_timelines,
    overlap_matrix,
    plan_identity,
    span_link,
    span_owner,
)
from chainermn_tpu.observability.streaming import (
    TelemetryAggregator,
)
from chainermn_tpu.observability.ledger import (
    RunLedger,
    build_manifest,
    classify_artifact,
    ingest_artifacts,
    iter_artifacts,
    stamp_envelope,
)
from chainermn_tpu.observability.diffing import (
    diff_histograms,
    diff_manifests,
    diff_profiles,
    diff_runs,
    load_run,
    run_profile,
)
from chainermn_tpu.observability.watchdog import (
    Watchdog,
    WatchdogConfig,
    start_watchdog,
    watchdog_thread_count,
)

__all__ = [
    "AttributionWatch",
    "BUCKETS",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "InstrumentedCommunicator",
    "MetricsRegistry",
    "RunLedger",
    "Span",
    "StepTelemetry",
    "StragglerDetector",
    "StreamingHistogram",
    "TelemetryAggregator",
    "Watchdog",
    "WatchdogConfig",
    "append_jsonl",
    "atomic_write_json",
    "attribute_step",
    "attribution_consistency",
    "attribution_report",
    "build_manifest",
    "build_step_trees",
    "classify_artifact",
    "clock_handshake",
    "contention_report",
    "critical_path",
    "diff_histograms",
    "diff_manifests",
    "diff_profiles",
    "diff_runs",
    "disable",
    "enable",
    "enabled",
    "feed_link_observations",
    "get_flight_recorder",
    "get_registry",
    "identify_desync",
    "ingest_artifacts",
    "install_flight_recorder",
    "instrument_communicator",
    "iter_artifacts",
    "leaf_comm_spans",
    "link_rates",
    "load_run",
    "merge_ranks",
    "occupancy_from_events",
    "occupancy_timelines",
    "offset_from_samples",
    "overlap_matrix",
    "plan_identity",
    "prometheus_text",
    "read_jsonl",
    "reset_flight_recorder",
    "run_profile",
    "span_link",
    "span_owner",
    "span_summary",
    "stamp_envelope",
    "start_watchdog",
    "straggler_report",
    "summarize_durations",
    "to_trace_events",
    "watchdog_thread_count",
    "write_prometheus",
    "write_snapshot_jsonl",
]
