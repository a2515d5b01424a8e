#
# telemetry/ — the unified observability layer.  Four PRs of machinery
# (staging engine, device cache, retry, elastic recovery) each grew a
# module-level metric dict and timestamp-less trace events; this package
# gives them one queryable surface:
#
#   registry.py   typed process-global metrics registry
#                 (Counter/Gauge/Histogram with labels, snapshot/reset).
#                 The legacy dicts — `mesh.STAGE_COUNTS`,
#                 `device_cache.CACHE_METRICS`,
#                 `elastic.RECOVERY_METRICS` — are now thin views over it
#                 (`dict_view`), so every old caller keeps working while
#                 the registry exports everything.
#   exporters.py  Chrome trace-event JSON (loads in Perfetto: one track
#                 per thread + an instant-marker track for resilience
#                 events) and Prometheus text format (`dump_prometheus`,
#                 plus the opt-in stdlib HTTP endpoint gated by the
#                 `telemetry_port` conf).
#   report.py     per-fit JSON reports (stage timing tree, bytes staged,
#                 cache hits, retries/recoveries, solver loss curve) —
#                 written under `telemetry_dir` and reachable as
#                 `model.fit_report()`.
#   heartbeat.py  progress heartbeat for long iterative solvers
#                 (iteration/loss/throughput every
#                 `heartbeat_interval_s`).
#   memory.py     HBM accounting: per-device live/peak byte gauges
#                 (`device.memory_stats()` where the backend has it, a
#                 deterministic `jax.live_arrays()` census elsewhere),
#                 per-fit peak watermarks, and the
#                 `budget_drift_ratio{est=}` feedback that checks the
#                 byte model's predictions against the chips.
#   compile.py    compile observability: `compile_seconds{fn=,phase=}`
#                 from a jax.monitoring listener (explicit span wrappers
#                 where the hooks are absent) and `recompiles_total` for
#                 every dropped-and-re-lowered program (elastic shrink,
#                 precision flips), with `recompile[...]` markers inside
#                 the interrupted fit's span tree.
#
# Span correlation lives in tracing.py: every span/instant carries
# absolute t0/t1, the recording thread id, and the `run_id` core.py
# mints per fit/transform — so retries, device-loss recoveries and
# checkpoint resumes land inside the spans they interrupted.
#
# Like resilience/, this package imports neither jax nor numpy at module
# scope: reading a counter must not pay the accelerator import.
#
from .aggregate import (  # noqa: F401
    dump_merged,
    merge_prometheus,
    scrape_endpoints,
)
from .compile import (  # noqa: F401
    compile_label,
    compile_span,
    install_jax_listener,
    note_recompile,
)
from .exporters import (  # noqa: F401
    chrome_trace,
    dump_chrome_trace,
    dump_prometheus,
    maybe_start_http_server,
    parse_prometheus,
    parse_prometheus_families,
    render_families,
    start_http_server,
    stop_http_server,
)
from .flight_recorder import (  # noqa: F401
    RECORDER,
    FlightRecorder,
    note_failure,
)

# pod observatory — the cross-rank correlation layer (stdlib-only at
# module scope, like everything else in this package)
from .fleet import (  # noqa: F401
    begin_pod_pass,
    clock_offsets,
    complete_pod_pass,
    merge_chrome_traces,
    mint_incident_id,
)
from .hang_doctor import (  # noqa: F401
    DOCTOR,
    HangDoctor,
    all_thread_stacks,
    build_wait_graph,
    find_cycles,
)
from .heartbeat import Heartbeat  # noqa: F401
from .locks import (  # noqa: F401
    LOCK_CATALOG,
    lock_table,
    named_lock,
    publish_lock_metrics,
)
from .memory import (  # noqa: F401
    FitMemoryWatermark,
    SimulatedMemoryProvider,
    get_provider,
    record_budget_decision,
    record_prediction,
    reset_memory_telemetry,
    sample_devices,
)
from .registry import (  # noqa: F401
    METRIC_CATALOG,
    REGISTRY,
    DictView,
    Metric,
    MetricsRegistry,
    check_cardinality,
    counter,
    delta,
    dict_view,
    gauge,
    histogram,
    reset_metrics,
    snapshot,
)
from .report import FitTelemetry, solver_summary, span_tree  # noqa: F401
from .utilization import (  # noqa: F401
    note_interval,
    summarize_utilization,
)

# the flight recorder is ALWAYS-ON by design: hook it onto the tracing
# tap as soon as the telemetry package loads (every fit/serving path
# imports it), so the black box is recording before the first span.  The
# `flight_recorder` conf gates recording itself, re-read cheaply inside
# record().
from .flight_recorder import install as _install_flight_recorder  # noqa: E402

_install_flight_recorder()

# the hang doctor rides the same tap (always-on, `hang_doctor` conf):
# its watchdog thread spawns lazily on the first recorded event, so
# importing the package starts no threads
from .hang_doctor import install as _install_hang_doctor  # noqa: E402

_install_hang_doctor()

__all__ = [
    "DOCTOR",
    "DictView",
    "FitMemoryWatermark",
    "FitTelemetry",
    "FlightRecorder",
    "HangDoctor",
    "Heartbeat",
    "LOCK_CATALOG",
    "METRIC_CATALOG",
    "Metric",
    "MetricsRegistry",
    "RECORDER",
    "REGISTRY",
    "SimulatedMemoryProvider",
    "begin_pod_pass",
    "check_cardinality",
    "chrome_trace",
    "clock_offsets",
    "compile_label",
    "compile_span",
    "complete_pod_pass",
    "counter",
    "delta",
    "dict_view",
    "dump_chrome_trace",
    "dump_merged",
    "dump_prometheus",
    "gauge",
    "get_provider",
    "histogram",
    "install_jax_listener",
    "all_thread_stacks",
    "build_wait_graph",
    "find_cycles",
    "lock_table",
    "maybe_start_http_server",
    "merge_chrome_traces",
    "merge_prometheus",
    "mint_incident_id",
    "named_lock",
    "note_failure",
    "note_interval",
    "note_recompile",
    "publish_lock_metrics",
    "summarize_utilization",
    "parse_prometheus",
    "parse_prometheus_families",
    "record_budget_decision",
    "record_prediction",
    "render_families",
    "reset_memory_telemetry",
    "reset_metrics",
    "sample_devices",
    "scrape_endpoints",
    "snapshot",
    "solver_summary",
    "span_tree",
    "start_http_server",
    "stop_http_server",
]
