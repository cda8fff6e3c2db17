"""repro.obs — runtime telemetry + numerics health for the whole stack.

Seven small pieces (see README "Observability"):

  * :mod:`repro.obs.metrics` — counters / gauges / nested wall-clock timers
    with ``block_until_ready`` discipline; zero-overhead no-op when
    disabled, enabled via ``enable()`` / ``using()`` / ``REPRO_METRICS=1``.
  * :mod:`repro.obs.health`  — jit-safe on-device field probes
    (``field_stats``: NaN/Inf counts, min/max/mean, global L2, mesh-aware
    via ``axis_names``) and the cadence/policy ``HealthMonitor`` that makes
    long forecasts blow-up-safe.
  * :mod:`repro.obs.events`  — the flight recorder: bounded ring of
    structured events, ``REPRO_EVENT_LOG`` JSONL sink and a crash dump
    that flushes the ring on abort; and spans, the one timing primitive:
    an always-on ring of named, nested, timed spans that also annotate
    any profiler capture.
  * :mod:`repro.obs.export`  — Prometheus-style text exposition of the
    metrics snapshot (health gauges included).
  * :mod:`repro.obs.drift`   — model-vs-measured drift detection (the
    standing form of the repo's measured/model == 1.000 wire claims).
  * :mod:`repro.obs.report`  — structured JSON run reports + the
    ``runtime_metadata()`` stamp every ``BENCH_fig*.json`` carries.
  * :mod:`repro.obs.profile` — env-gated ``jax.profiler`` trace capture
    (``REPRO_TRACE_DIR``), with per-IR-op ``named_scope`` labels.

Everything downstream (``ir`` lowerings, ``dist.halo``, ``serve.forecast``,
``train.assimilate``, ``checkpoint.store``, the benchmark suite) reports through
this package; it imports jax lazily and nothing here initialises a backend
at import time.
"""

from repro.obs import events, metrics
from repro.obs.drift import DEFAULT_TOLERANCE, DriftResult, check_drift
from repro.obs.events import EVENT_LOG_ENV, Event, FlightRecorder
from repro.obs.export import prometheus_text, sanitize_metric_name
from repro.obs.health import (
    HealthMonitor,
    NumericsError,
    field_stats,
    host_stats,
    is_healthy,
)
from repro.obs.metrics import (
    METRICS_ENV,
    MetricsRegistry,
    TimerStat,
    instrument_call,
)
from repro.obs.profile import TRACE_DIR_ENV, maybe_trace, profiler_trace
from repro.obs.report import MATCH_KEYS, RunReport, git_commit, runtime_metadata

__all__ = [
    "DEFAULT_TOLERANCE",
    "DriftResult",
    "EVENT_LOG_ENV",
    "Event",
    "FlightRecorder",
    "HealthMonitor",
    "MATCH_KEYS",
    "METRICS_ENV",
    "MetricsRegistry",
    "NumericsError",
    "RunReport",
    "TRACE_DIR_ENV",
    "TimerStat",
    "check_drift",
    "events",
    "field_stats",
    "git_commit",
    "host_stats",
    "instrument_call",
    "is_healthy",
    "maybe_trace",
    "metrics",
    "profiler_trace",
    "prometheus_text",
    "runtime_metadata",
    "sanitize_metric_name",
]
