"""paddle_tpu.observability — the framework-wide telemetry layer.

Three legs, one surface (reference: platform/profiler/ +
platform/monitor.h grown into a production observability stack):

- :mod:`.metrics` — thread-safe Counter/Gauge/Histogram with label
  support, a process-wide default :class:`MetricsRegistry`, JSON
  ``snapshot()`` and Prometheus text exposition.  ``serving.metrics``
  is a thin client.
- :mod:`.compile_watchdog` — opt-in wrapper around the repo's
  ``jax.jit`` entry points (hapi train step, the serving unified step,
  hybrid-engine step, inference predictors, jit.to_static): counts
  compilations, records compile wall-time + HLO cost analysis, and
  WARNs with the argument shape/dtype diff on post-warmup recompiles —
  the ragged-shape regression detector.  It also keeps each engine
  step's jitted function and abstract arguments, and
  ``instruction_table(name)`` maps every instruction of the compiled
  step to the scopes that made it (a profile's operation names).
- :mod:`.tracing` — the flight recorder: a thread-safe
  :class:`Span`/:class:`Tracer` model with a bounded ring of completed
  traces.  The serving engine records every request's lifecycle
  (``queued → chunk[i] → decode[i] → finished|evicted|shed``) and hapi
  ``Model.fit`` opens a per-step span, so training and serving share
  one timeline vocabulary; traces export as chrome-trace tracks or
  JSON.
- :mod:`.exporter` — strictly opt-in live endpoints:
  :func:`start_telemetry_server` serves ``/metrics`` (Prometheus),
  ``/varz`` (JSON snapshot + watchdog report), ``/healthz`` (shedding
  state + drain estimate) and ``/traces``; :class:`ResourceSampler`
  polls RSS / fds / GC / JAX live-buffer bytes into gauges.  Importing
  paddle_tpu starts neither (tier-1 enforced).
- :mod:`.goodput` — the training health monitor's accounting leg:
  :class:`GoodputMonitor` partitions every ``Model.fit`` step into
  data-wait / compile / checkpoint / eval / compute phases
  (``training_step_breakdown_seconds{phase=...}``), publishes the
  ``training_goodput_ratio`` and ``training_mfu`` gauges (HLO
  cost-analysis FLOPs over step wall time and the per-device-kind
  :data:`~paddle_tpu.observability.goodput.PEAK_FLOPS` table).
- :mod:`.health` — :class:`HealthMonitor`: NaN/Inf loss, gradient-norm
  spikes (rolling z-score), loss plateaus and step-time outliers, with
  warn/gauge/raise actions, the ``training_healthy`` gauge,
  ``training_anomalies_total{kind=...}`` and a flight-recorder span per
  event.
- :mod:`.aggregate` — cross-rank aggregation over the TCPStore:
  every rank publishes its registry snapshot
  (:class:`RankMetricsPublisher`), rank 0 merges with ``rank=`` labels,
  ages out stale ranks, and computes the straggler skew gauge
  (:class:`ClusterAggregator`); the telemetry server serves the merged
  exposition fleet-wide.
- :mod:`.flight` — the *distributed* flight recorder: every public
  collective op records into a bounded per-process ring
  (:class:`FlightRecorder` — seq numbers, shapes/bytes, latency,
  ``collective::<op>`` spans + ``collective_*`` metrics), and the
  :class:`HangWatchdog` publishes per-rank progress heartbeats over
  the TCPStore, localizes cross-rank hangs (desync report naming the
  lagging rank and the first divergent seq/op) and dumps atomic debug
  bundles; the telemetry server's ``/flight`` endpoint and the
  ``TrainingSupervisor``'s ``on_hang`` escalation ride it.
- :mod:`.timeseries` — the in-process time-series store:
  :class:`TimeSeriesStore` scrapes a :class:`MetricsRegistry` into
  fixed-budget per-series rings on an injectable clock (opt-in thread,
  nothing on import), detects counter resets (a
  ``register(replace=True)`` engine rebuild mid-soak never reads as
  negative traffic), and answers the windowed queries raw lifetime
  counters cannot: ``rate``/``delta``/``avg``/``slope`` and
  histogram-bucket-delta ``quantile``/``good_below`` — "TTFT p99 over
  the LAST minute", not since process start.  Served at
  ``/timeseries``.
- :mod:`.slo` — the governing layer over the store: declarative
  :class:`SLO` objectives (availability / goodput / latency-threshold
  forms), error-budget tracking, and :class:`BurnRateAlert`
  multi-window multi-burn-rate alerts (fast-burn page + slow-burn
  ticket, fire-once/sticky with clear hysteresis — the SRE-workbook
  shape).  :class:`SLOEngine` emits ``slo_*`` metrics, tail-retained
  ``slo::<name>`` transition spans, the ``/slo`` endpoint payload, the
  ``/healthz`` page fold, and the autoscaler's escalation/scale-down
  inputs.  Severities come from the fixed :data:`SEVERITIES` enum.
- :mod:`.slo_gossip` — the fleet leg of the SLO layer: each replica's
  :class:`SLOStatusPublisher` rides the :class:`StorePublisher`
  machinery to publish its engine's ``/slo`` status under one TCPStore
  key, and rank 0 folds every replica's view into ``/slo?fleet=1``
  (:func:`collect_fleet_slo` / :func:`merge_fleet_slo`): fleet
  ``page_active`` is the OR, the worst remaining budget wins per
  objective, and the transition logs interleave into one timeline.
  Advisory and staleness-tolerant — each replica's own engine keeps
  paging regardless.
- :mod:`.profiling` — the continuous sampling profiler:
  :class:`StackSampler` keeps a low-rate ``sys._current_frames`` walk
  always on (collapsed flamegraph stacks in a fixed-budget windowed
  store; under 1% of wall time at the default rate, a CPU ratio
  ``tests/test_profiling.py`` asserts), tags every sample with the sampled thread's
  :func:`phase` marker (``admission`` / ``prefill_chunk`` / ``decode``
  / ``checkpoint`` / ``scrape``) or its ambient tracer span — a
  window's phase slices sum exactly to its sampled wall time — and
  escalates to a high-rate capture window when an anomaly fires (SLO
  page, ``health::`` event, hang watchdog), emitting the finished
  capture as a tail-retained ``profiling::capture`` span *continuing*
  the anomaly's trace.  Served at ``/profilez`` (JSON or collapsed
  stacks); :func:`diff_profiles` subtracts two windows to localize a
  regression.  The ``profiling_*`` series set is a pinned contract
  (:data:`~paddle_tpu.observability.profiling.PROFILING_SERIES`,
  mirrored by the metric-names lint).
- the step-aware :class:`~paddle_tpu.profiler.Profiler` (re-exported
  here lazily to avoid an import cycle): ``make_scheduler`` windows,
  step-boundary instant events, and registry gauges emitted as
  chrome-trace counter events into one Perfetto timeline.
"""
from __future__ import annotations

from .aggregate import (  # noqa: F401
    ClusterAggregator,
    RankMetricsPublisher,
    StorePublisher,
)
from .compile_watchdog import (  # noqa: F401
    CompileWatchdog,
    abstract_like,
    default_watchdog,
    disable_compile_watchdog,
    enable_compile_watchdog,
    innermost_scope,
    instruction_table,
    leaf_primitive,
    named_scopes,
    parse_instruction_table,
    pass_of,
    watch,
    watchdog_enabled,
)
from .exporter import (  # noqa: F401
    ResourceSampler,
    TelemetryServer,
    start_telemetry_server,
)
from .flight import (  # noqa: F401
    CollectiveRecord,
    FlightRecorder,
    HangWatchdog,
    default_flight_recorder,
    record_collective,
    use_flight_recorder,
)
from .goodput import (  # noqa: F401
    PEAK_FLOPS,
    GoodputMonitor,
    device_peak_flops,
    mfu,
)
from .health import (  # noqa: F401
    HealthMonitor,
    TrainingHealthError,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from .profiling import (  # noqa: F401
    PROFILING_SERIES,
    StackSampler,
    diff_profiles,
)
from .profiling import phase as profiling_phase  # noqa: F401
from .slo import (  # noqa: F401
    SEVERITIES,
    SLO,
    BurnRateAlert,
    SLOEngine,
)
from .slo_gossip import (  # noqa: F401
    SLOStatusPublisher,
    collect_fleet_slo,
    merge_fleet_slo,
)
from .timeseries import (  # noqa: F401
    TimeSeriesStore,
)
from .tracing import (  # noqa: F401
    Span,
    Tracer,
    default_tracer,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "CompileWatchdog", "default_watchdog", "watch",
    "enable_compile_watchdog", "disable_compile_watchdog",
    "instruction_table", "parse_instruction_table", "abstract_like",
    "named_scopes", "innermost_scope", "pass_of", "leaf_primitive",
    "watchdog_enabled",
    "Span", "Tracer", "default_tracer",
    "ResourceSampler", "TelemetryServer", "start_telemetry_server",
    "GoodputMonitor", "PEAK_FLOPS", "device_peak_flops", "mfu",
    "HealthMonitor", "TrainingHealthError",
    "RankMetricsPublisher", "ClusterAggregator", "StorePublisher",
    "CollectiveRecord", "FlightRecorder", "HangWatchdog",
    "default_flight_recorder", "use_flight_recorder",
    "record_collective",
    "TimeSeriesStore",
    "SEVERITIES", "SLO", "BurnRateAlert", "SLOEngine",
    "SLOStatusPublisher", "collect_fleet_slo", "merge_fleet_slo",
    "StackSampler", "profiling_phase", "diff_profiles",
    "PROFILING_SERIES",
    # lazy (profiler leg)
    "Profiler", "RecordEvent", "ProfilerState", "make_scheduler",
    "export_chrome_tracing",
]

_PROFILER_NAMES = {"Profiler", "RecordEvent", "ProfilerState",
                   "make_scheduler", "export_chrome_tracing"}


def __getattr__(name):
    # profiler imports observability.metrics; re-export its surface
    # lazily so the two packages don't import-cycle at module load
    if name in _PROFILER_NAMES:
        from .. import profiler

        return getattr(profiler.profiler, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
