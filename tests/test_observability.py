"""Observability subsystem on the 8-device CPU mesh: Chrome-trace spans
for every framework phase, wall-clock-consistent step metrics, chief-side
snapshot aggregation, and the AUTODIST_TELEMETRY=0 zero-call fast path.
"""
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist, const, observability
from autodist_tpu.strategy import AllReduce

BATCH = 16


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    """Every test starts with default (on) telemetry and empty buffers."""
    monkeypatch.delenv("AUTODIST_TELEMETRY", raising=False)
    monkeypatch.delenv("AUTODIST_TRACE", raising=False)
    observability.refresh()
    observability.reset()
    yield
    observability.refresh()
    observability.reset()


def _loss_fn(params, batch):
    x, y = batch
    h = jax.nn.relu(x @ params["w1"])
    return jnp.mean((h @ params["w2"] - y) ** 2)


def _fixture():
    rng = np.random.RandomState(0)
    params = {"w1": jnp.zeros((8, 16)), "w2": jnp.zeros((16, 4))}
    batch = (rng.randn(BATCH, 8).astype(np.float32),
             rng.randn(BATCH, 4).astype(np.float32))
    return params, batch


def _build():
    params, batch = _fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(_loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    return runner, batch


def _repeat(batch):
    while True:
        yield batch


# ---------------------------------------------------------------------------
# pillar 2: phase tracing


def test_full_loop_emits_chrome_trace_with_all_phases(tmp_path):
    runner, batch = _build()
    state = runner.create_state()
    state, _ = runner.run(state, _repeat(batch), 8)

    path = observability.flush_trace(str(tmp_path / "trace.json"))
    assert path is not None
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events, "trace flushed but empty"
    spans = [e for e in events if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    for phase in ("capture", "strategy-build", "transform", "compile",
                  "step-loop"):
        assert phase in names, f"missing span for phase {phase!r}"
    for e in spans:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert e["cat"] == "autodist" and "pid" in e and "tid" in e
    # Nesting sanity: compile happens inside the step-loop span (first
    # step triggers it), and capture precedes strategy-build.
    by_name = {e["name"]: e for e in spans}
    assert by_name["capture"]["ts"] <= by_name["strategy-build"]["ts"]
    loop = by_name["step-loop"]
    comp = by_name["compile"]
    assert loop["ts"] <= comp["ts"] <= loop["ts"] + loop["dur"]


def test_run_flushes_trace_into_default_trace_dir():
    runner, batch = _build()
    default = observability.tracing.default_trace_path()
    if os.path.exists(default):
        os.remove(default)
    state = runner.create_state()
    runner.run(state, _repeat(batch), 2)
    assert os.path.exists(default), \
        "Runner.run did not flush a trace into DEFAULT_TRACE_DIR"
    with open(default) as f:
        assert json.load(f)["traceEvents"]


# ---------------------------------------------------------------------------
# pillar 1: metrics registry


def test_step_metrics_consistent_with_wall_clock():
    runner, batch = _build()
    state = runner.create_state()
    state, _ = runner.step(state, batch)  # compile outside the timed loop

    observability.registry().reset()
    steps = 12
    t0 = time.perf_counter()
    state, _ = runner.run(state, _repeat(batch), steps)
    wall_ms = (time.perf_counter() - t0) * 1e3

    snap = observability.registry().snapshot()
    assert snap["counters"]["step.count"] == steps
    assert snap["counters"]["step.examples"] == steps * BATCH
    assert snap["counters"]["host_transfer.batches"] == steps
    hist = snap["histograms"]["step.latency_ms"]
    assert hist["count"] == steps
    assert snap["histograms"]["step.data_wait_ms"]["count"] == steps
    # The histogram's total is the sum of the loop's own host deltas, each
    # taken inside the surrounding interval: it cannot exceed it.  How much
    # of the interval it accounts for depends on what else the host runs
    # (end-of-loop bookkeeping under six test workers), so no lower bound:
    # the sums are held to each other instead.
    assert 0 < hist["total"] <= wall_ms
    assert hist["min"] <= hist["p50"] <= hist["p90"] <= hist["max"]
    assert steps * hist["min"] <= hist["total"] * (1 + 1e-9)
    assert hist["total"] <= steps * hist["max"] * (1 + 1e-9)
    # The throughput gauge is the last flushed window's examples over that
    # window's deltas: a mean of recorded steps, so it lies between the
    # rates of the slowest and the fastest one (the gauge rounds to 0.1).
    eps = snap["gauges"]["step.examples_per_sec"]
    assert BATCH / (hist["max"] / 1e3) - 0.1 <= eps
    assert eps <= BATCH / (hist["min"] / 1e3) + 0.1


def test_step_data_wait_metric_populated():
    """The observed loop times next(data_iter) into step.data_wait_ms —
    an artificially slow iterator must show up there, step for step."""
    runner, batch = _build()
    state = runner.create_state()
    state, _ = runner.step(state, batch)  # compile outside the loop

    def slow_iter():
        while True:
            time.sleep(0.02)
            yield batch

    observability.registry().reset()
    steps = 6
    runner.run(state, slow_iter(), steps)
    snap = observability.registry().snapshot()
    wait = snap["histograms"]["step.data_wait_ms"]
    assert wait["count"] == steps
    # Every fetch slept 20ms; the recorded waits must account for it.
    assert wait["min"] >= 15.0
    assert wait["total"] >= steps * 15.0
    # Data-wait is a component of step latency, never more than the loop.
    lat = snap["histograms"]["step.latency_ms"]
    assert wait["total"] <= lat["total"] * 1.05


def test_aggregate_labels_input_vs_compute_bound():
    """A host whose median data-wait dominates step latency is labeled
    input-bound (with a warning); a fed host is compute-bound."""
    now = 1_000_000.0
    base_hist = {"count": 50, "total": 500.0, "window": 50, "mean": 10.0,
                 "min": 9.0, "max": 12.0, "p50": 10.0, "p90": 11.0}
    starved = {"host": 0, "pid": 1, "time": now,
               "counters": {"step.count": 50}, "gauges": {},
               "histograms": {"step.latency_ms": dict(base_hist),
                              "step.data_wait_ms": dict(base_hist, p50=8.0,
                                                        mean=8.0)},
               "phases": {}, "events": []}
    fed = {"host": 1, "pid": 2, "time": now,
           "counters": {"step.count": 50}, "gauges": {},
           "histograms": {"step.latency_ms": dict(base_hist),
                          "step.data_wait_ms": dict(base_hist, p50=0.2,
                                                    mean=0.2)},
           "phases": {}, "events": []}
    no_wait = {"host": 2, "pid": 3, "time": now,
               "counters": {"step.count": 50}, "gauges": {},
               "histograms": {"step.latency_ms": dict(base_hist)},
               "phases": {}, "events": []}
    agg = observability.cluster.aggregate([starved, fed, no_wait], now=now)
    assert agg["hosts"][0]["bound"] == "input"
    assert agg["hosts"][1]["bound"] == "compute"
    assert agg["hosts"][2]["bound"] is None  # no data-wait recorded
    warnings = "\n".join(agg["warnings"])
    assert "host 0 input-bound" in warnings
    assert "host 1" not in warnings


def test_report_shows_data_wait_and_bound_label():
    runner, batch = _build()
    state = runner.create_state()

    def slow_iter():
        while True:
            time.sleep(0.01)
            yield batch

    runner.run(state, slow_iter(), 4)
    observability.cluster._ingest([observability.snapshot()])
    path = runner.write_report(batch)
    text = open(path).read()
    assert "data-wait p50" in text
    assert "-bound" in text  # input-/compute-bound badge rendered


def test_compile_and_padding_metrics_populated():
    runner, batch = _build()
    state = runner.create_state()
    runner.step(state, batch)
    snap = observability.registry().snapshot()
    assert snap["gauges"].get("compile.ms", 0) > 0
    # No uneven shardings in this fixture: padding gauge reads zero,
    # but must exist (set at Runner construction).
    assert snap["gauges"].get("padding.bytes") == 0


# ---------------------------------------------------------------------------
# pillar 3: flight recorder + cluster aggregation


def test_flight_recorder_unifies_resilience_events():
    from autodist_tpu import resilience
    resilience.record_event("rollback", "divergence at step 7")
    kinds = [e["kind"] for e in observability.recorder.events()]
    assert "rollback" in kinds
    ev = [e for e in observability.recorder.events()
          if e["kind"] == "rollback"][-1]
    assert ev.get("source") == "resilience"
    sidecar = observability.recorder.sidecar_path()
    if sidecar:  # fail-open: absent on read-only filesystems
        lines = [json.loads(l) for l in open(sidecar) if l.strip()]
        assert any(e["kind"] == "rollback" for e in lines)


def test_sync_single_process_returns_local_snapshot():
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, _repeat(batch), 3)
    snaps = observability.cluster.gathered()
    assert len(snaps) == 1
    assert snaps[0]["host"] == 0
    assert snaps[0]["counters"]["step.count"] >= 3
    assert "phases" in snaps[0]


def test_worker_snapshots_aggregate_on_chief():
    now = 1_000_000.0
    chief = {"host": 0, "pid": 100, "time": now - 1,
             "counters": {"step.count": 50},
             "gauges": {"step.examples_per_sec": 1000.0},
             "histograms": {"step.latency_ms": {
                 "count": 50, "total": 500.0, "window": 50, "mean": 10.0,
                 "min": 9.0, "max": 12.0, "p50": 10.0, "p90": 11.0}},
             "phases": {}, "events": []}
    straggler = dict(chief, host=1, pid=101,
                     histograms={"step.latency_ms": {
                         "count": 50, "total": 2500.0, "window": 50,
                         "mean": 50.0, "min": 40.0, "max": 70.0,
                         "p50": 50.0, "p90": 60.0}})
    silent = dict(chief, host=2, pid=102, time=now - 600,
                  histograms={"step.latency_ms": {
                      "count": 50, "total": 520.0, "window": 50,
                      "mean": 10.4, "min": 9.0, "max": 12.0,
                      "p50": 10.4, "p90": 11.0}})
    agg = observability.cluster.aggregate([chief, straggler, silent],
                                          now=now)
    assert set(agg["hosts"]) == {0, 1, 2}
    assert agg["cluster_step_ms_median"] == pytest.approx(10.4)
    warnings = "\n".join(agg["warnings"])
    assert "host 1 straggling" in warnings
    assert "host 2 heartbeat stale" in warnings
    assert "host 0" not in warnings


def test_report_renders_cluster_telemetry_section():
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, _repeat(batch), 3)
    local = observability.snapshot()
    # Three hosts so the median-of-medians is a healthy host's, not the
    # straggler's own: local, a clone, and a 1000ms/step straggler.
    peer = dict(local, host=2)
    worker = dict(local, host=1,
                  histograms={"step.latency_ms": {
                      "count": 3, "total": 3000.0, "window": 3,
                      "mean": 1000.0, "min": 900.0, "max": 1100.0,
                      "p50": 1000.0, "p90": 1100.0}})
    observability.cluster._ingest([local, worker, peer])
    path = runner.write_report(batch)
    text = open(path).read()
    assert "Telemetry (3 hosts)" in text
    assert "Per-host step time" in text
    assert "Phase waterfall" in text
    assert "straggling" in text  # the synthetic worker is 1000ms/step


# ---------------------------------------------------------------------------
# the off switch


def test_disabled_step_loop_makes_zero_telemetry_calls(monkeypatch,
                                                       tmp_path):
    monkeypatch.setenv("AUTODIST_TELEMETRY", "0")
    observability.refresh()
    assert not observability.enabled()
    runner, batch = _build()  # Runner caches the disabled handle
    state = runner.create_state()
    state, _ = runner.step(state, batch)  # compile before measuring

    calls = []

    def spy(label):
        def _record(*a, **k):
            calls.append(label)
        return _record

    monkeypatch.setattr(observability.tracing.Span, "__enter__",
                        spy("span"))
    monkeypatch.setattr(observability.tracing, "record_complete",
                        spy("trace"))
    monkeypatch.setattr(observability.tracing, "record_instant",
                        spy("instant"))
    monkeypatch.setattr(observability.recorder, "record", spy("recorder"))
    monkeypatch.setattr(observability.metrics.Counter, "inc",
                        spy("counter"))
    monkeypatch.setattr(observability.metrics.Gauge, "set", spy("gauge"))
    monkeypatch.setattr(observability.metrics.WindowHistogram,
                        "observe_many", spy("histogram"))
    monkeypatch.setattr(observability.cluster, "sync", spy("sync"))
    monkeypatch.setattr(observability.tracing, "flush", spy("flush"))
    # ISSUE 8 contract extension: attribution makes zero step-loop calls
    # and the monitor never starts, even with a port configured.
    monkeypatch.setenv("AUTODIST_MONITOR_PORT", "18907")
    monkeypatch.setattr(observability.attribution.Ledger, "observe",
                        spy("attribution"))
    monkeypatch.setattr(observability.attribution, "terms_for_runner",
                        spy("attribution-terms"))
    monkeypatch.setattr(observability.attribution, "finalize",
                        spy("attribution-finalize"))
    monkeypatch.setattr(observability.monitor, "start", spy("monitor"))
    # ISSUE 9 contract extension: the per-layer profiler makes zero
    # calls too — no provenance scan, no HLO parse, no finalize.
    monkeypatch.setattr(observability.profile, "profile_runner",
                        spy("profile-runner"))
    monkeypatch.setattr(observability.profile, "model_scope_costs",
                        spy("profile-model-costs"))
    monkeypatch.setattr(observability.profile, "hlo_scope_costs",
                        spy("profile-hlo-costs"))
    monkeypatch.setattr(observability.profile, "finalize",
                        spy("profile-finalize"))
    # ISSUE 11 contract extension: the goodput ledger makes zero calls —
    # no classification pass, no gauges, no segment file, no re-exec env.
    monkeypatch.setattr(const, "DEFAULT_LOG_DIR", str(tmp_path / "logs"))
    monkeypatch.setattr(observability.goodput, "collect",
                        spy("goodput-collect"))
    monkeypatch.setattr(observability.goodput, "finalize",
                        spy("goodput-finalize"))
    monkeypatch.setattr(observability.goodput, "persist_segment",
                        spy("goodput-persist"))
    # ISSUE 13 contract extension: the skew layer makes zero calls —
    # no KV clock ping, no ring append, no decomposition, no summary
    # file.
    monkeypatch.setattr(observability.skew, "maybe_sync_clocks",
                        spy("skew-clock-sync"))
    monkeypatch.setattr(observability.skew, "observe_dispatches",
                        spy("skew-ring"))
    monkeypatch.setattr(observability.skew, "update_from_snapshots",
                        spy("skew-decompose"))
    monkeypatch.setattr(observability.skew, "persist_summary",
                        spy("skew-persist"))
    # ISSUE 14 contract extension: the pipeline bubble accounting makes
    # zero calls — no shape probe, no pipeline.* gauges.
    from autodist_tpu.pipeline import observe as pipe_observe
    monkeypatch.setattr(pipe_observe, "finalize", spy("pipeline-finalize"))
    monkeypatch.setattr(pipe_observe, "pipeline_shape",
                        spy("pipeline-shape"))
    # ISSUE 15 contract extension: the online re-tuning controller is
    # never constructed with telemetry off, even with the retune knob
    # set — no controller, no re-pricing passes, no retune.* gauges.
    monkeypatch.setenv("AUTODIST_RETUNE", "1")
    from autodist_tpu import retune as retune_mod
    monkeypatch.setattr(retune_mod, "controller_for",
                        spy("retune-controller"))
    monkeypatch.setattr(retune_mod.Controller, "observe_window",
                        spy("retune-observe"))
    monkeypatch.setattr(retune_mod.Controller, "apply", spy("retune-apply"))
    # ISSUE 17 contract extension: the HBM memory ledger makes zero calls
    # — no predicted pricing pass, no MemoryLedger, no memory_stats /
    # live_arrays sampling, no finalize, no memory.json sidecar.
    monkeypatch.setattr(observability.memory, "MemoryLedger",
                        spy("memory-ledger"))
    monkeypatch.setattr(observability.memory, "predicted_for_runner",
                        spy("memory-predict"))
    monkeypatch.setattr(observability.memory, "measured_sample",
                        spy("memory-sample"))
    monkeypatch.setattr(observability.memory, "finalize",
                        spy("memory-finalize"))

    # ISSUE 23 contract extension: the hot loop's profiler annotations
    # (Runner.step's dispatch, Remapper.shard_batch, the prefetcher's
    # data_wait) and the jax.monitoring listeners make zero calls.
    monkeypatch.setattr(observability.tracing, "annotate", spy("annotate"))
    monkeypatch.setattr(observability.tracing, "watch_jax_compiles",
                        spy("watch-jax-compiles"))

    state, metrics_out = runner.run(state, _repeat(batch), 5)
    from autodist_tpu.data import DevicePrefetcher
    for placed in DevicePrefetcher(iter([batch] * 2), runner.remapper,
                                   depth=1, pull_in_background=False):
        state, metrics_out = runner.step(state, placed, shard_inputs=False)
    assert calls == [], f"telemetry calls on disabled step loop: {calls}"
    assert metrics_out is not None  # the loop itself still works
    assert not observability.monitor.running()
    segment_files = (list((tmp_path / "logs").glob("goodput_*.json"))
                     if (tmp_path / "logs").exists() else [])
    assert segment_files == [], "goodput segments written with telemetry off"
    assert observability.skew.ring() == [], \
        "skew ring fed with telemetry off"
    skew_files = (list((tmp_path / "logs").glob("skew_*.json"))
                  if (tmp_path / "logs").exists() else [])
    assert skew_files == [], "skew summary written with telemetry off"
    mem_files = (list((tmp_path / "logs").glob("*.json"))
                 if (tmp_path / "logs").exists() else [])
    assert not [p for p in mem_files
                if p.name in ("memory.json", "oom_report.json")], \
        "memory ledger sidecar written with telemetry off"


def test_disabled_runner_records_no_spans(monkeypatch):
    monkeypatch.setenv("AUTODIST_TELEMETRY", "0")
    observability.refresh()
    observability.reset()
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, _repeat(batch), 2)
    assert observability.tracing.events() == []
    assert observability.registry().snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}


# ---------------------------------------------------------------------------
# satellite: flight-recorder rotation (bounded on-disk growth)


def test_flight_recorder_rotation_bounds_disk(tmp_path, monkeypatch):
    """A long chaos-heavy run must not grow logs/flight_*.jsonl without
    bound: the sidecar rolls to segments and evicts the oldest files
    until the directory total fits AUTODIST_FLIGHT_MAX_MB."""
    from autodist_tpu import const
    logdir = tmp_path / "logs"
    monkeypatch.setattr(const, "DEFAULT_LOG_DIR", str(logdir))
    monkeypatch.setenv("AUTODIST_FLIGHT_MAX_MB", "1")
    observability.recorder._reset_sidecar_for_tests()
    try:
        payload = "x" * 400
        # ~3 MiB of events against a 1 MiB cap.
        for i in range(8000):
            observability.recorder.record("chaos", payload, i=i)
        files = sorted(logdir.glob("flight_*.jsonl"))
        assert files, "sidecar never opened"
        assert len(files) > 1, "sidecar never rolled to a new segment"
        total = sum(f.stat().st_size for f in files)
        cap = 1 << 20
        # Bound: the cap plus one live segment of slack (eviction works
        # in whole files and never touches the live segment).
        assert total <= cap + (cap // 8) + (1 << 14), (
            f"flight files grew to {total} bytes against a {cap} cap: "
            f"{[f.name for f in files]}")
        # Eviction really dropped the oldest segment (the base file).
        names = {f.name for f in files}
        assert f"flight_{os.getpid()}.jsonl" not in names, \
            "oldest segment was never evicted"
    finally:
        observability.recorder._reset_sidecar_for_tests()


def test_flight_recorder_rotation_keeps_newest_events(tmp_path,
                                                      monkeypatch):
    from autodist_tpu import const
    logdir = tmp_path / "logs"
    monkeypatch.setattr(const, "DEFAULT_LOG_DIR", str(logdir))
    monkeypatch.setenv("AUTODIST_FLIGHT_MAX_MB", "1")
    observability.recorder._reset_sidecar_for_tests()
    try:
        for i in range(8000):
            observability.recorder.record("ev", "x" * 400, i=i)
        newest = max(logdir.glob("flight_*.jsonl"),
                     key=lambda f: f.stat().st_mtime)
        lines = [json.loads(l) for l in open(newest) if l.strip()]
        assert lines and lines[-1]["i"] == 7999, \
            "the newest events must survive rotation"
    finally:
        observability.recorder._reset_sidecar_for_tests()


# ---------------------------------------------------------------------------
# satellite: logging hardening


def test_logger_rebuild_does_not_duplicate_handlers():
    from autodist_tpu.utils import logging as alog
    lg = alog.get_logger()
    n = len(lg.handlers)
    assert n >= 1
    alog._build_logger()  # simulates a post-fork / reset rebuild
    assert len(alog.get_logger().handlers) == n


def test_logger_formatter_uses_live_pid():
    from autodist_tpu.utils import logging as alog
    lg = alog.get_logger()
    fmts = [h.formatter._fmt for h in lg.handlers if h.formatter]
    assert fmts and all("%(process)d" in f for f in fmts)
    assert all(str(os.getpid()) not in f for f in fmts)
