"""Telemetry subsystem (first-class observability for the training stack).

Grown from the 76-line ``train/observe.py`` into four pillars:

- **in-scan metric streaming** (``stream.StepStream``): a
  ``jax.debug.callback``-based tap staged INSIDE jitted step/scan bodies
  that rings per-step scalars (loss, grad-norm, update-norm, NaN/Inf
  counts, steps/s) out to the host without fetching — the whole-epoch
  ``lax.scan`` dispatch path (``ScanEpochDriver``) stays donated and
  trajectory-identical, but per-step signals land in ``metrics.jsonl``
  as they happen instead of vanishing into epoch aggregates.
- **host span tracing** (``spans.SpanTracer``): nested wall-clock spans
  (staging, compile, device_put, warmup, epoch, eval, checkpoint)
  exported as Chrome-trace/Perfetto JSON (``trace.json``).
- **gauges/counters** (``gauges``): per-bucket padding efficiency and
  occupancy from ``PaddingStats``, per-device HBM via
  ``device.memory_stats()`` with a device-kind table fallback, loader
  wait time, and scan-vs-per-step dispatch share.
- **run manifest** (``manifest``): config, mesh/device inventory, git
  SHA, versions — written once per run (``manifest.json``).

Everything hangs off one ``Telemetry`` facade behind the train.py
``--telemetry {off,epoch,step}`` flag; the default (``epoch``) matches
the pre-existing behavior (epoch records in ``metrics.jsonl``) and
stages NO callbacks into any compiled program — only ``step`` does.
"""

from cgnn_tpu.observe.export import (
    LiveMetricsWriter,
    MetricsRegistry,
    RollingSeries,
    parse_prometheus_text,
)
from cgnn_tpu.observe.flightrec import FlightRecorder
from cgnn_tpu.observe.hist import (
    LATENCY_MS_BOUNDS,
    OCCUPANCY_BOUNDS,
    QUEUE_WAIT_MS_BOUNDS,
    Histogram,
    log_bounds,
    merge_snapshot_maps,
    quantile_from_snapshot,
    snapshots_from_family,
)
from cgnn_tpu.observe.gauges import (
    hbm_gauges,
    padding_gauges,
)
from cgnn_tpu.observe.manifest import write_manifest
from cgnn_tpu.observe.metrics_io import (
    MetricsLogger,
    enable_debug_nans,
    jsonfinite,
    profile_trace,
    read_jsonl,
)
from cgnn_tpu.observe.log import (
    bind_trace,
    current_trace_id,
    json_log_fn,
    setup_json_logging,
)
from cgnn_tpu.observe.profile import ProfileBusy, ProfileCapture, install_sigusr2
from cgnn_tpu.observe.slo import (
    BurnRateRule,
    SLOEngine,
    SLOObjective,
    default_rules,
)
from cgnn_tpu.observe.spans import SpanTracer
from cgnn_tpu.observe.stream import StepStream
from cgnn_tpu.observe.telemetry import Telemetry
from cgnn_tpu.observe.tsdb import TimeSeriesStore, TsdbCollector
from cgnn_tpu.observe.tracectx import (
    TRACE_PARENT_HEADER,
    format_parent,
    mint_span_id,
    parse_parent,
)

__all__ = [
    "BurnRateRule",
    "FlightRecorder",
    "Histogram",
    "LATENCY_MS_BOUNDS",
    "OCCUPANCY_BOUNDS",
    "QUEUE_WAIT_MS_BOUNDS",
    "TRACE_PARENT_HEADER",
    "LiveMetricsWriter",
    "MetricsLogger",
    "MetricsRegistry",
    "ProfileBusy",
    "ProfileCapture",
    "RollingSeries",
    "SLOEngine",
    "SLOObjective",
    "SpanTracer",
    "StepStream",
    "Telemetry",
    "TimeSeriesStore",
    "TsdbCollector",
    "bind_trace",
    "current_trace_id",
    "default_rules",
    "format_parent",
    "log_bounds",
    "merge_snapshot_maps",
    "quantile_from_snapshot",
    "snapshots_from_family",
    "install_sigusr2",
    "json_log_fn",
    "mint_span_id",
    "parse_parent",
    "parse_prometheus_text",
    "setup_json_logging",
    "enable_debug_nans",
    "hbm_gauges",
    "jsonfinite",
    "padding_gauges",
    "profile_trace",
    "read_jsonl",
    "write_manifest",
]
