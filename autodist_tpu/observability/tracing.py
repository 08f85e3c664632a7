"""Span-based phase tracing emitted as Chrome trace events.

Every framework phase (capture -> strategy build -> transform -> compile
-> ship -> restore -> step loop) runs under a :class:`Span`; completed
spans land in a bounded in-memory ring and flush to
``DEFAULT_TRACE_DIR/autodist_trace_<pid>.json`` in the Chrome
trace-event format — drag the file into https://ui.perfetto.dev (or
chrome://tracing) for the waterfall.

One clock: every span also enters a ``jax.profiler.TraceAnnotation``
named ``autodist.<span name>``, so whenever a device trace is being
taken (``Runner.run(trace_dir=...)``, ``jax.profiler.start_trace``) the
framework's phases lie on the host plane of that trace, on the device's
clock, with no knob to set.  :func:`annotate` is the hot loop's form: the
annotation alone, no ring record.  With no trace session open an
annotation is a flag check.

JAX's own compile timings (``jax.monitoring``) become spans too, once
:func:`watch_jax_compiles` has registered its listeners: ``jax-trace``,
``jax-lower`` and ``xla-compile`` (backend compile or cache read), each
placed at (now - duration), and the counters ``compile.count``,
``compile.cache_hits`` and ``compile.cache_misses``.  They fire for
every jit in the process; a reader tells the Runner's by nesting inside
a ``compile`` span, and sums only the outermost span of each kind (JAX
traces and lowers inner functions inside outer ones).

Overhead discipline: a span costs two ``time.perf_counter()`` calls, the
annotation and one deque append; the ring is bounded (old events drop)
so tracing never grows with job length; flushing is explicit (end of
``Runner.run``, ``flush()``) plus a best-effort ``atexit`` — and
everything is fail-open (a broken filesystem degrades tracing to
in-memory only).
"""
import atexit
import json
import os
import threading
import time

from collections import deque

import jax

from autodist_tpu import const
from autodist_tpu.observability import metrics

#: Prefix of every annotation this module writes into a profiler trace.
ANNOTATION_PREFIX = "autodist."

#: ``jax.monitoring`` duration events -> span names.
_JAX_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax-trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax-lower",
    "/jax/core/compile/backend_compile_duration": "xla-compile",
}
#: ``jax.monitoring`` events counted as ``compile.cache_hits`` / ``_misses``
#: (a miss is recorded when the compiled program is written to the cache).
_JAX_CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                     "/jax/compilation_cache/cache_misses")

_MAX_EVENTS = 20_000

_events = deque(maxlen=_MAX_EVENTS)
_lock = threading.Lock()
# Phase accumulator: name -> [first_start_us, total_us, count].  Kept
# separately from the ring so phase totals survive event eviction (the
# report's waterfall reads these, not the ring).
_phase = {}
# Spans entered and not yet left: id(span) -> (name, start us).  A reader
# that runs inside a phase (the goodput ledger, persisted while the step
# loop drains) sees that phase here; completed spans are in the ring.
_open = {}
_origin = time.perf_counter()
# Wall-clock epoch of the perf_counter origin: trace ts 0 corresponds to
# this absolute moment.  Captured back-to-back so per-host traces are
# alignable on wall clocks (tools/timeline) even without the KV clock
# estimator; the residual pairing error is sub-microsecond.
_origin_epoch = time.time() - (time.perf_counter() - _origin)
_mode_cache = None


def _mode():
    """Effective AUTODIST_TRACE mode: "chrome" | "" (no trace file)."""
    global _mode_cache
    if _mode_cache is None:
        raw = str(const.ENV.AUTODIST_TRACE.val).strip().lower()
        _mode_cache = "" if raw in ("0", "off", "false", "none") else "chrome"
    return _mode_cache


def refresh():
    """Re-read the AUTODIST_TRACE knob (test harness hook)."""
    global _mode_cache
    _mode_cache = None


def _now_us():
    return (time.perf_counter() - _origin) * 1e6


def perf_to_epoch(t_perf):
    """A ``perf_counter`` reading -> wall-clock epoch seconds (the skew
    ring converts dispatch windows with this, off the hot loop)."""
    return _origin_epoch + (t_perf - _origin)


def to_perf_counter(ts_us):
    """An event's ``ts`` (microseconds since this module's origin) as a
    ``time.perf_counter()`` reading, so that a reader can order a span
    against a host-clock instant of its own."""
    return _origin + ts_us * 1e-6


def epoch_anchor_us():
    """Wall-clock epoch (microseconds) of trace timestamp 0 — stamped
    into every flushed trace so per-host files are alignable."""
    return _origin_epoch * 1e6


class Span:
    """Context manager recording one complete ("ph": "X") trace event."""

    __slots__ = ("name", "args", "_t0", "_annotation")

    def __init__(self, name, args=None):
        self.name = name
        self.args = args or {}
        self._t0 = None
        self._annotation = None

    def __enter__(self):
        self._t0 = _now_us()
        with _lock:
            _open[id(self)] = (self.name, self._t0)
        try:
            self._annotation = annotate(self.name)
            self._annotation.__enter__()
        except Exception:  # noqa: BLE001 - telemetry must never kill a run
            self._annotation = None
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _now_us()
        with _lock:
            _open.pop(id(self), None)
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001
                pass
        record_complete(self.name, self._t0, t1 - self._t0, self.args)
        return False


class _NullSpan:
    """Shared no-op span for the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


def annotate(name):
    """``autodist.<name>`` in the profiler's trace and nowhere else: the
    hot loop's form of a span (no clock read, no ring record)."""
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


_jax_watched = False


def _telemetry_on():
    from autodist_tpu import observability
    return observability.enabled()


def _on_jax_duration(event, duration_secs, **kwargs):
    name = _JAX_DURATION_SPANS.get(event)
    if name is None or not _telemetry_on():
        return
    dur_us = duration_secs * 1e6
    ts_us = _now_us() - dur_us
    _drop_nested_tail(name, ts_us)
    record_complete(name, ts_us, dur_us,
                    {"fun_name": kwargs.get("fun_name", "")})
    if name == "xla-compile":
        metrics.registry().counter("compile.count").inc()


def _drop_nested_tail(name, ts_us, slack_us=20.0):
    """JAX traces every inner function inside the outer one (hundreds of
    ``jax-trace`` events a layer), and reports the inner ones first.  Only
    the outermost is kept: the events of this name and thread at the
    ring's tail that began after ``ts_us`` lie inside the one about to be
    recorded, and would otherwise push the run's phases out of the ring.
    An instant recorded while the outer one ran (a ``flash`` or
    ``grad_sync`` event at trace time) stays and does not end the walk."""
    tid = threading.get_ident() & 0xFFFF
    with _lock:
        i = len(_events) - 1
        while i >= 0:
            ev = _events[i]
            if ev["ts"] < ts_us - slack_us:
                break
            if ev["ph"] == "X":
                if ev["name"] != name or ev["tid"] != tid:
                    break
                del _events[i]
                acc = _phase[name]
                acc[1] -= ev["dur"]
                acc[2] -= 1
            i -= 1


def _on_jax_event(event, **kwargs):
    if event not in _JAX_CACHE_EVENTS or not _telemetry_on():
        return
    if event.endswith("cache_hits"):
        metrics.registry().counter("compile.cache_hits").inc()
    else:
        metrics.registry().counter("compile.cache_misses").inc()


def watch_jax_compiles():
    """Register the ``jax.monitoring`` listeners, once a process.  Called
    where telemetry is known to be on (the Runner's construction), never
    at import."""
    global _jax_watched
    with _lock:
        if _jax_watched:
            return
        _jax_watched = True
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    jax.monitoring.register_event_listener(_on_jax_event)


def record_complete(name, ts_us, dur_us, args=None):
    """Append one complete event and fold it into the phase accumulator."""
    ev = {"name": name, "cat": "autodist", "ph": "X",
          "ts": round(ts_us, 1), "dur": round(dur_us, 1),
          "pid": os.getpid(), "tid": threading.get_ident() & 0xFFFF}
    if args:
        ev["args"] = {k: str(v) for k, v in args.items()}
    with _lock:
        _events.append(ev)
        acc = _phase.get(name)
        if acc is None:
            _phase[name] = [ts_us, dur_us, 1]
        else:
            acc[1] += dur_us
            acc[2] += 1


def record_instant(name, args=None):
    """Append one instant ("ph": "i") event — flight-recorder bridge."""
    ev = {"name": name, "cat": "autodist", "ph": "i", "s": "p",
          "ts": round(_now_us(), 1), "pid": os.getpid(),
          "tid": threading.get_ident() & 0xFFFF}
    if args:
        ev["args"] = {k: str(v) for k, v in args.items()}
    with _lock:
        _events.append(ev)


def events():
    """Snapshot of buffered trace events (oldest may have been evicted)."""
    with _lock:
        return list(_events)


def open_spans():
    """``[(name, start_us)]`` of the spans entered and not yet left."""
    with _lock:
        return list(_open.values())


def phase_summary():
    """{phase: {"start_ms", "total_ms", "count"}} — the report's
    waterfall reads this, not the raw ring."""
    with _lock:
        return {name: {"start_ms": round(s / 1e3, 3),
                       "total_ms": round(d / 1e3, 3), "count": n}
                for name, (s, d, n) in _phase.items()}


def clear():
    """Drop buffered events and phase totals (test harness hook)."""
    with _lock:
        _events.clear()
        _phase.clear()
        _open.clear()


def default_trace_path():
    return os.path.join(const.DEFAULT_TRACE_DIR,
                        f"autodist_trace_{os.getpid()}.json")


def flush(path=None):
    """Write buffered events as one Chrome-trace JSON file.

    Returns the path written, or ``None`` when there was nothing to write
    or the filesystem refused (fail-open: in-memory events are kept, so a
    later flush to a writable path still has them).
    """
    if _mode() == "":
        return None
    evs = events()
    if not evs:
        return None
    path = path or default_trace_path()
    # Alignment metadata (docs/observability.md "Cluster timeline"):
    # the epoch anchor pins trace ts 0 to a wall-clock moment, and the
    # clock estimate (when the KV exchange ran) corrects that wall clock
    # onto the chief's — tools/timeline merges per-host files with it.
    meta = {"epoch_anchor_us": round(epoch_anchor_us(), 1),
            "pid": os.getpid(), "host": 0}
    try:
        import jax
        meta["host"] = jax.process_index()
    except Exception:  # noqa: BLE001 - pre-init / broken backend
        pass
    try:
        from autodist_tpu.observability import skew
        est = skew.local_offset()
        if est is not None:
            meta["clock_offset_ms"] = est.get("offset_ms", 0.0)
            meta["clock_uncertainty_ms"] = est.get("uncertainty_ms", 0.0)
    except Exception:  # noqa: BLE001 - alignment metadata is best-effort
        pass
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": evs, "displayTimeUnit": "ms",
                       "metadata": meta}, f)
    except OSError:
        return None
    return path


def _flush_at_exit():
    try:
        from autodist_tpu import observability
        if observability.enabled():
            flush()
    except Exception:  # noqa: BLE001 - interpreter teardown is hostile
        pass


atexit.register(_flush_at_exit)
