"""Observability subsystem: metrics, phase tracing, flight recorder.

Three pillars (docs/observability.md), one switch (``AUTODIST_TELEMETRY``,
default on):

* :mod:`~autodist_tpu.observability.metrics` — a low-overhead registry
  (counters, gauges, time-window histograms) fed by the Runner step loop
  (step latency, examples/sec, compile/AOT time, padding bytes, host
  batch transfers) and by the strategy-ship / checkpoint paths;
* :mod:`~autodist_tpu.observability.tracing` — context-manager spans
  around every framework phase (capture -> strategy build -> transform
  -> compile -> ship -> restore -> step loop), emitted as Chrome
  trace-event JSON into ``DEFAULT_TRACE_DIR`` (Perfetto-loadable); every
  span is also an ``autodist.<name>`` annotation in whatever
  ``jax.profiler`` trace is being taken, on the device's clock;
* :mod:`~autodist_tpu.observability.recorder` — a bounded JSONL flight
  recorder unifying the resilience event trail with compile/checkpoint/
  ship/worker lifecycle events, shipped per-worker to the chief over the
  coordination-service KV store (:mod:`~autodist_tpu.observability.
  cluster`) for the report's cluster-wide section.

On top of the pillars:

* :mod:`~autodist_tpu.observability.attribution` — the step-time
  attribution ledger: reconciles measured wall step time into
  ``data_wait + host_dispatch + device_compute + exposed_comms +
  residual`` (``attr.*`` gauges, the report's "Where the step goes"
  section) and feeds per-term tuner calibration;
* :mod:`~autodist_tpu.observability.monitor` — the opt-in live cluster
  monitor (``AUTODIST_MONITOR_PORT``): Prometheus ``/metrics`` + JSON
  ``/status`` on the chief, with rolling straggler/anomaly detection;
* :mod:`~autodist_tpu.observability.profile` — the per-layer device-time
  profiler (``AUTODIST_PROFILE``): scope provenance from ``named_scope``
  through jaxpr/HLO, reconciled against the attribution ledger
  (``profile.*`` gauges, the report's "Per-layer profile" section);
* :mod:`~autodist_tpu.observability.goodput` — the run-level goodput &
  MFU ledger (docs/goodput.md): total wall-clock classified into
  productive step time vs enumerated badput classes, stitched across
  elastic re-exec generations via ``AUTODIST_RUN_ID`` (``goodput.*``
  gauges, the report's "Run goodput" section);
* :mod:`~autodist_tpu.observability.memory` — the HBM memory ledger
  (docs/memory.md): predicted per-device peak split into named classes
  (``tuner/cost_model.strategy_memory``) reconciled against
  ``memory_stats``/``live_arrays`` boundary samples, feasibility
  pruning for tuner/Automap/pipeline/serve candidates, and OOM
  forensics (``mem.*`` gauges, ``logs/oom_report.json``, the report's
  "Where the HBM goes" section);
* :mod:`~autodist_tpu.observability.skew` — cross-host clock sync +
  skew-decomposed comms attribution (``AUTODIST_CLOCK_SYNC`` /
  ``AUTODIST_SKEW_RING``): NTP-style offsets over the KV store, the
  chief's wire-vs-skew-wait split of ``exposed_comms`` with a named,
  cause-blamed straggler (``skew.*`` gauges, the report's "Cluster
  timeline" block, ``python -m autodist_tpu.tools.timeline``).

Contract: **off-path cheap** (the Runner's hot loop batches host-side
observations and flushes on the StepGuard cadence; with telemetry
disabled the step loop makes ZERO telemetry calls) and **fail-open**
(no telemetry error may ever kill a run — every filesystem/KV touch is
guarded).
"""
from autodist_tpu import const
from autodist_tpu.observability import (attribution, cluster, goodput,
                                        memory, metrics, monitor, profile,
                                        recorder, skew, tracing)

_enabled_cache = None


def enabled():
    """Whether telemetry is on (``AUTODIST_TELEMETRY``; cached — call
    :func:`refresh` after flipping the env var mid-process)."""
    global _enabled_cache
    if _enabled_cache is None:
        _enabled_cache = bool(const.ENV.AUTODIST_TELEMETRY.val)
    return _enabled_cache


def refresh():
    """Re-read the telemetry env knobs (test harness hook)."""
    global _enabled_cache
    _enabled_cache = None
    tracing.refresh()


def span(name, **args):
    """Phase span context manager; a shared no-op when telemetry is off."""
    if not enabled():
        return tracing.NULL_SPAN
    return tracing.Span(name, args)


def annotate(name):
    """``autodist.<name>`` in the profiler's trace only (the hot loop's
    span: no ring record); a shared no-op when telemetry is off."""
    if not enabled():
        return tracing.NULL_SPAN
    return tracing.annotate(name)


def record_event(kind, detail="", **fields):
    """Append to the flight recorder (no-op when telemetry is off)."""
    if enabled():
        recorder.record(kind, detail, **fields)


def registry():
    """The process-global metrics registry (callers on hot paths must
    gate on :func:`enabled` themselves — see Runner.run)."""
    return metrics.registry()


def phase_timings():
    """{phase: {"start_ms", "total_ms", "count"}} (the report's waterfall)."""
    return tracing.phase_summary()


def flush_trace(path=None):
    """Flush buffered spans to a Chrome-trace JSON file; returns the path
    (or ``None`` when tracing is off / nothing buffered / unwritable)."""
    if not enabled():
        return None
    return tracing.flush(path)


def sync_cluster(timeout_ms=None):
    """Exchange per-worker snapshots (chief gathers); fail-open.  The
    gathered set also feeds the rolling anomaly detector (monitor.py) —
    newly-raised anomalies land on the flight recorder.  The clock-sync
    ping runs first (SPMD-symmetric — every process reaches this at the
    same point), then the chief decomposes the gathered dispatch windows
    into wire vs skew-wait (observability/skew.py)."""
    if not enabled():
        return []
    skew.maybe_sync_clocks()
    snaps = cluster.sync(timeout_ms=timeout_ms)
    skew.update_from_snapshots(snaps)
    monitor.observe_cluster(snaps)
    return snaps


def snapshot():
    """This process's telemetry snapshot (JSON-serializable)."""
    return cluster.local_snapshot()


def reset():
    """Clear metrics, spans, and the event bus (test harness hook)."""
    metrics.registry().reset()
    tracing.clear()
    recorder.clear()
    cluster._ingest([])
    attribution.reset()
    profile.reset()
    goodput.reset()
    memory.reset()
    skew.reset()
    monitor.reset_detector()


__all__ = [
    "enabled", "refresh", "span", "annotate", "record_event", "registry",
    "phase_timings", "flush_trace", "sync_cluster", "snapshot", "reset",
    "metrics", "tracing", "recorder", "cluster", "attribution", "monitor",
    "profile", "goodput", "memory", "skew",
]
