"""Goodput & MFU ledger (ISSUE 11 tentpole): run-level wall-clock
classification (goodput vs badput classes summing to the measured wall),
MFU/HFU from the flops estimate against the peak-flops table, run
identity across re-exec, segment persistence + cross-generation
stitching, and the monitor/report/calibration surfacing.
"""
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist, const, observability
from autodist_tpu.observability import goodput, tracing
from autodist_tpu.strategy import AllReduce
from autodist_tpu.tuner.calibration import Calibration

BATCH = 16


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch, tmp_path):
    monkeypatch.delenv("AUTODIST_TELEMETRY", raising=False)
    monkeypatch.delenv("AUTODIST_RUN_ID", raising=False)
    monkeypatch.delenv("AUTODIST_RUN_GENERATION", raising=False)
    monkeypatch.delenv("AUTODIST_PEAK_TFLOPS", raising=False)
    # Isolate segment files and the calibration the finalize path writes.
    monkeypatch.setattr(const, "DEFAULT_LOG_DIR", str(tmp_path / "logs"))
    monkeypatch.setenv("AUTODIST_TUNER_CALIBRATION",
                       str(tmp_path / "cal.json"))
    observability.refresh()
    observability.reset()
    yield
    observability.refresh()
    observability.reset()


def _loss_fn(params, batch):
    x, y = batch
    h = jax.nn.relu(x @ params["w1"])
    return jnp.mean((h @ params["w2"] - y) ** 2)


def _build():
    rng = np.random.RandomState(0)
    params = {"w1": jnp.zeros((8, 16)), "w2": jnp.zeros((16, 4))}
    batch = (rng.randn(BATCH, 8).astype(np.float32),
             rng.randn(BATCH, 4).astype(np.float32))
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(_loss_fn, params, optax.sgd(0.1), example_batch=batch)
    return ad.create_distributed_session(item), batch


def _repeat(batch):
    while True:
        yield batch


# ---------------------------------------------------------------------------
# classification unit: synthetic telemetry state


def test_collect_classifies_and_sums_to_wall():
    reg = observability.registry()
    reg.histogram("step.latency_ms").observe_many([2.0] * 10)
    reg.counter("step.count").inc(10)
    reg.histogram("step.data_wait_ms").observe_many([0.5] * 10)
    # 25ms step-loop span containing a 3ms compile; a 50ms compile and a
    # 7ms restore outside any loop.
    tracing.record_complete("step-loop", 0.0, 25_000.0)
    tracing.record_complete("compile", 1_000.0, 3_000.0)
    tracing.record_complete("compile", 100_000.0, 50_000.0)
    tracing.record_complete("restore", 160_000.0, 7_000.0)
    tracing.record_complete("capture", 200_000.0, 4_000.0)
    s = goodput.collect()
    c = s["classes"]
    # goodput = billed 20ms - 5ms data wait - 3ms in-loop compile
    assert s["goodput_ms"] == pytest.approx(12.0, abs=0.01)
    assert c["data_wait_ms"] == pytest.approx(5.0, abs=0.01)
    assert c["compile_ms"] == pytest.approx(53.0, abs=0.01)  # full totals
    assert c["restore_ms"] == pytest.approx(7.0, abs=0.01)
    assert c["startup_ms"] == pytest.approx(4.0, abs=0.01)
    # unbilled loop remainder: 25 - 20 billed = 5ms of rollback/replay
    assert c["rollback_ms"] == pytest.approx(5.0, abs=0.01)
    # The invariant: goodput + classes == wall, the remainder surfaced.
    total = s["goodput_ms"] + sum(c.values())
    assert total == pytest.approx(s["wall_ms"], abs=0.05)


def test_collect_carves_reshard_and_emergency_out():
    reg = observability.registry()
    tracing.record_complete("restore", 0.0, 30_000.0)
    reg.gauge("checkpoint.reshard_ms").set(21.0)
    tracing.record_complete("emergency-save", 50_000.0, 9_000.0)
    tracing.record_complete("checkpoint-save", 51_000.0, 8_000.0)  # nested
    s = goodput.collect()
    c = s["classes"]
    assert c["reshard_ms"] == pytest.approx(21.0, abs=0.01)
    assert c["restore_ms"] == pytest.approx(9.0, abs=0.01)
    assert c["emergency_save_ms"] == pytest.approx(9.0, abs=0.01)
    # the nested periodic-save span does not double count
    assert c["checkpoint_save_ms"] == pytest.approx(0.0, abs=0.01)


def test_empty_process_is_all_other():
    s = goodput.collect()
    assert s["goodput_ms"] == 0.0
    assert s["steps"] == 0
    nonzero = {k: v for k, v in s["classes"].items()
               if k != "other_ms" and v}
    assert nonzero == {}
    assert s["classes"]["other_ms"] == pytest.approx(s["wall_ms"], abs=0.05)


# ---------------------------------------------------------------------------
# peak flops + MFU


def test_peak_tflops_env_override(monkeypatch):
    monkeypatch.setenv("AUTODIST_PEAK_TFLOPS", "123.5")
    assert goodput.peak_flops_per_device() == pytest.approx(123.5e12)


def test_peak_table_matches_device_kinds():
    class Dev:
        def __init__(self, kind, platform):
            self.device_kind = kind
            self.platform = platform
    assert goodput.peak_flops_per_device(
        Dev("TPU v4", "tpu")) == pytest.approx(275e12)
    assert goodput.peak_flops_per_device(
        Dev("TPU v5 lite", "tpu")) == pytest.approx(197e12)
    assert goodput.peak_flops_per_device(
        Dev("NVIDIA H100 80GB", "gpu")) == pytest.approx(989e12)
    assert goodput.peak_hbm_bytes_per_device(
        Dev("TPU v5 lite", "tpu")) == 16 * (1 << 30)
    # the forced-device CPU test mesh keeps its nominal entry
    assert goodput.peak_flops_per_device(
        Dev("host", "cpu")) == pytest.approx(0.05e12)
    # an accelerator the table does not list is an error, not a default
    for lookup in (goodput.peak_flops_per_device,
                   goodput.peak_hbm_bytes_per_device):
        with pytest.raises(ValueError, match="TPU v99"):
            lookup(Dev("TPU v99", "tpu"))


# ---------------------------------------------------------------------------
# run identity


def test_run_id_minted_once_and_env_wins(monkeypatch):
    a = goodput.run_id()
    assert a == goodput.run_id()  # stable within the process
    monkeypatch.setenv("AUTODIST_RUN_ID", "operator-named")
    assert goodput.run_id() == "operator-named"


def test_reexec_env_carries_identity_forward(monkeypatch):
    monkeypatch.setenv("AUTODIST_RUN_ID", "elastic-run")
    monkeypatch.setenv("AUTODIST_RUN_GENERATION", "2")
    env = goodput.reexec_env()
    assert env["AUTODIST_RUN_ID"] == "elastic-run"
    assert env["AUTODIST_RUN_GENERATION"] == "3"


def test_reform_now_preserves_run_identity_and_persists_segment(
        monkeypatch, tmp_path):
    from autodist_tpu.coordinator import Coordinator
    monkeypatch.setenv("AUTODIST_RUN_ID", "reform-run")
    execs = []
    co = Coordinator(None, None)
    monkeypatch.setattr(co, "_exec", lambda *a: execs.append(a))
    co._world_size = 4
    co.request_reform(3, reason="test")
    co.reform_now()
    (_exe, _argv, env), = execs
    assert env["AUTODIST_RUN_ID"] == "reform-run"
    assert env["AUTODIST_RUN_GENERATION"] == "1"
    segs = goodput.segments_for("reform-run")
    assert len(segs) == 1 and segs[0]["end_reason"] == "re-exec"
    assert segs[0]["generation"] == 0


def test_worker_env_contract_shares_chief_run_id(monkeypatch):
    from autodist_tpu.coordinator import Coordinator
    monkeypatch.setenv("AUTODIST_RUN_ID", "shared-run")
    co = Coordinator(None, None)
    env = co._env_contract(1, 2, "127.0.0.1:15500", "proc-1")
    assert env["AUTODIST_RUN_ID"] == "shared-run"


# ---------------------------------------------------------------------------
# runner end to end (the e2e acceptance: classes reconcile, MFU in (0,1])


@pytest.mark.parametrize("unroll", [1, 4])
def test_runner_goodput_reconciles_and_mfu_sane(unroll, monkeypatch):
    monkeypatch.setenv("AUTODIST_RUN_ID", f"e2e-u{unroll}")
    runner, batch = _build()
    state = runner.create_state()
    state, _ = runner.run(state, _repeat(batch), 8, unroll=unroll)
    s = goodput.last_summary()
    assert s is not None and s["steps"] == 8
    # Sum invariant, on the ledger's own figures: goodput and the badput
    # classes are built to add up to the wall it read, so they differ from
    # it by the rounding of thirteen figures to 1e-3 and by no share of a
    # clock.
    total = s["goodput_ms"] + sum(s["classes"].values())
    assert total == pytest.approx(s["wall_ms"], abs=0.05)
    assert set(s["classes"]) == set(goodput.BADPUT_CLASSES)
    assert s["goodput_ms"] > 0
    assert s["mfu"] is not None and 0 < s["mfu"] <= 1
    assert s["hfu"] is not None and 0 < s["hfu"]
    # Gauges published.
    gauges = observability.registry().snapshot()["gauges"]
    for name in ("goodput.pct", "goodput.wall_ms", "goodput.goodput_ms",
                 "goodput.mfu", "goodput.hfu", "run.generation"):
        assert name in gauges, f"{name} gauge missing"
    for cls in goodput.BADPUT_CLASSES:
        assert f"goodput.{cls}" in gauges
    # The goodput slice carries the PR 8 attribution split.
    assert set(s["goodput_breakdown"]) == {
        "data_wait_ms", "host_dispatch_ms", "device_compute_ms",
        "exposed_comms_ms", "residual_ms"}
    # Chief persisted this generation's segment next to the flight log.
    segs = goodput.segments_for()
    assert len(segs) == 1 and segs[0]["steps"] == 8
    # MFU fed to calibration as a sanity anchor, as the ledger computed
    # it.  Compared relatively: the toy's is about 5e-7 against the CPU
    # table's peak, so any fixed number of kept decimals would decide it.
    assert Calibration.load().last_mfu == pytest.approx(s["mfu"], rel=1e-9)


def test_goodput_ships_with_cluster_snapshot():
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, _repeat(batch), 4)
    snap = observability.snapshot()
    assert snap["goodput"]["goodput_ms"] > 0
    assert snap["goodput"]["run_id"] == goodput.run_id()


def test_goodput_json_sidecar_under_dump_graphs(monkeypatch, tmp_path):
    monkeypatch.setattr(const, "DEFAULT_GRAPH_DUMP_DIR",
                        str(tmp_path / "graphs"))
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, _repeat(batch), 2)
    monkeypatch.setenv("AUTODIST_DUMP_GRAPHS", "1")
    goodput.finalize(runner, observability.registry())
    doc = json.load(open(tmp_path / "graphs" / "goodput.json"))
    assert doc["steps"] == 2 and "classes" in doc


# ---------------------------------------------------------------------------
# stitching


def _seg(gen, start, end, goodput_ms, steps=10, flops=1000.0,
         peak=1e12, **classes):
    base = {k: 0.0 for k in goodput.BADPUT_CLASSES}
    base.update(classes)
    return {"run_id": "stitch", "generation": gen, "pid": 1,
            "start": start, "end": end,
            "wall_ms": round((end - start) * 1e3, 3),
            "goodput_ms": goodput_ms, "classes": base, "steps": steps,
            "model_flops": flops * steps, "flops_per_step": flops,
            "peak_flops_total": peak, "devices": 8,
            "mfu": None, "hfu": None}


def test_stitch_prices_reexec_gap_and_sums(tmp_path):
    d = tmp_path / "segs"
    d.mkdir()
    # gen0: 10s of wall, ends at t=110; gen1 starts 2s later (the gap).
    segs = [_seg(0, 100.0, 110.0, 6000.0, compile_ms=1000.0,
                 other_ms=3000.0),
            _seg(1, 112.0, 120.0, 5000.0, reshard_ms=500.0,
                 other_ms=2500.0)]
    for i, s in enumerate(segs):
        with open(d / f"goodput_stitch_g{i}.json", "w") as f:
            json.dump(s, f)
    st = goodput.stitch_run("stitch", log_dir=str(d))
    assert st["generations"] == [0, 1]
    assert st["classes"]["reexec_gap_ms"] == pytest.approx(2000.0, abs=1.0)
    assert st["reexec_gaps_ms"] == [pytest.approx(2000.0, abs=1.0)]
    assert st["goodput_ms"] == pytest.approx(11000.0)
    assert st["classes"]["compile_ms"] == pytest.approx(1000.0)
    assert st["classes"]["reshard_ms"] == pytest.approx(500.0)
    # wall = last end - first start = 20s; classes + goodput == wall.
    assert st["wall_ms"] == pytest.approx(20_000.0, abs=1.0)
    total = st["goodput_ms"] + sum(st["classes"].values())
    assert total == pytest.approx(st["wall_ms"], rel=0.05)
    assert st["steps"] == 20
    # MFU: 20k model flops over (18s of segment wall + 2s gap) x 1 TF/s.
    assert st["mfu"] == pytest.approx(20_000.0 / (20.0 * 1e12))


def test_stitch_returns_none_without_segments(tmp_path):
    assert goodput.stitch_run("nope", log_dir=str(tmp_path)) is None


# ---------------------------------------------------------------------------
# surfacing: monitor + report


def test_monitor_status_exposes_run_identity_and_goodput(monkeypatch):
    from autodist_tpu.observability import monitor
    monkeypatch.setenv("AUTODIST_RUN_ID", "status-run")
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, _repeat(batch), 4)
    st = monitor.status()
    assert st["run"]["run_id"] == "status-run"
    assert st["run"]["generation"] == 0
    assert st["run"]["generations_observed"] == 1
    assert st["goodput"]["goodput_ms"] > 0
    assert st["goodput"]["mfu"] is not None
    assert set(st["goodput"]["classes"]) == set(goodput.BADPUT_CLASSES)
    json.dumps(st, default=str)  # the whole document stays serializable


def test_report_renders_run_goodput_section(monkeypatch, tmp_path):
    from autodist_tpu import report
    monkeypatch.setenv("AUTODIST_RUN_ID", "report-run")
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, _repeat(batch), 4)
    path = report.render_report(runner.program,
                                out_path=str(tmp_path / "r.html"))
    text = open(path).read()
    assert "Run goodput" in text
    assert "MFU" in text
    assert "re-exec gap" in text  # the class legend names the gap
    assert "report-run" in text   # run identity in the header


# ---------------------------------------------------------------------------
# calibration sanity input


def test_calibration_note_mfu_roundtrips_and_warns(tmp_path, monkeypatch):
    import autodist_tpu.tuner.calibration as cal_mod
    msgs = []
    monkeypatch.setattr(cal_mod.logging, "warning",
                        lambda fmt, *a: msgs.append(fmt % a if a else fmt))
    cal = Calibration(path=str(tmp_path / "c.json"))
    cal.note_mfu(0.41, context="test")
    assert Calibration.load(str(tmp_path / "c.json")).last_mfu == \
        pytest.approx(0.41)
    cal.note_mfu(None)  # no-op
    assert cal.last_mfu == pytest.approx(0.41)
    assert not msgs  # a sane MFU never warns
    cal.note_mfu(1.7, context="broken peak")
    assert msgs and "peak-flops" in msgs[-1]
