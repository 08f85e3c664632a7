"""Self-healing fleet runs (ISSUE 16, docs/retuning.md).

Covers the acceptance contracts:

* decision shipping is deterministic — identical decisions serialize to
  byte-identical canonical blobs with byte-identical fingerprints, and a
  chief + follower over one stubbed KV store materialize the SAME switch
  at the SAME megastep boundary (bitwise-consistent re-serialization);
* any disagreement — corrupted blob, wrong fingerprint echo, mismatched
  boundary — raises ``ShipMismatch`` loudly instead of splitting the
  fleet;
* a multi-process job WITHOUT a KV byte channel is declined: the warning
  logs once per process, every declined resolution bumps the
  ``retune.declined`` counter (the regression that used to warn every
  window);
* the ``slow_host`` chaos fault is deterministic, spares the chief, and
  records its injection event once;
* the healer's hysteresis: a transient straggler blip never evicts a
  host; a persistent verdict prices the eviction against remaining-steps
  payoff and either pins a shrink challenger + requests the re-form or
  refuses with a priced event (once per host);
* ``goodput.stitch_run`` reclassifies a self-heal generation's drain +
  re-exec gap under ``selfheal_ms`` with classes still summing to the
  stitched wall;
* end-to-end: a chaos-degraded host is detected through the straggler
  verdict, priced, evicted through emergency-save + (stubbed) re-exec
  with the challenger pinned, and the run resumes at N-1 devices with
  decreasing loss, a stitched ``selfheal_ms`` timeline, and the report's
  Re-tuning section listing the episode.
"""
import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from autodist_tpu import AutoDist, const, observability, retune
from autodist_tpu.observability import goodput, monitor, recorder, skew
from autodist_tpu.resilience import chaos
from autodist_tpu.retune import controller as controller_mod
from autodist_tpu.retune import selfheal, shipping
from autodist_tpu.strategy import AllReduce

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    """Fresh telemetry, retune state, chaos, and shipping sequence per
    test — plus an isolated log dir so flight events and goodput segments
    never leak across tests (the report's self-heal fallback scans the
    whole log dir)."""
    monkeypatch.setenv("AUTODIST_TUNER_CALIBRATION",
                       str(tmp_path / "cal.json"))
    for var in ("AUTODIST_RETUNE", "AUTODIST_CHAOS", "AUTODIST_SELFHEAL",
                "AUTODIST_SELFHEAL_PATIENCE", "AUTODIST_RUN_ID",
                "AUTODIST_RUN_GENERATION"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(const, "DEFAULT_LOG_DIR", str(tmp_path / "logs"))
    recorder._reset_sidecar_for_tests()
    observability.refresh()
    observability.reset()
    retune.reset()
    selfheal.reset()
    shipping.reset_seq()
    chaos.reset()
    skew.set_last_summary(None)
    yield
    recorder._reset_sidecar_for_tests()
    observability.refresh()
    observability.reset()
    retune.reset()
    selfheal.reset()
    shipping.reset_seq()
    chaos.reset()
    skew.set_last_summary(None)


def _fixture(bs=64, din=16, dout=4):
    rng = np.random.RandomState(0)
    params = {"w": jnp.zeros((din, dout)), "b": jnp.zeros((dout,))}
    batch = (rng.randn(bs, din).astype(np.float32),
             rng.randn(bs, dout).astype(np.float32))
    return params, batch


def _loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)


def _build(builder=None, devices=None, mesh_axes=None):
    params, batch = _fixture()
    ad = AutoDist(strategy_builder=builder or AllReduce(), devices=devices,
                  mesh_axes=mesh_axes)
    item = ad.capture(_loss_fn, params, optax.sgd(0.1), example_batch=batch)
    return ad.create_distributed_session(item), batch


def _dict_kv(store):
    """The (set_bytes, get_bytes) pair DecisionChannel wants, over a
    plain dict — the stubbed coordination-service KV store."""
    return (lambda key, val: store.__setitem__(key, val),
            lambda key, timeout_ms: store[key])


def _stub_rows(*triples):
    rows = []
    for label, pred, tier in triples:
        rows.append({"label": label, "unroll": 1,
                     "knobs": {"unroll": 1, "bucket_mb": 0,
                               "microbatches": 0},
                     "predicted_ms": pred, "breakdown": {},
                     "tier": tier, "strategy": None, "strategy_name": ""})
    rows.sort(key=lambda r: (round(r["predicted_ms"], 6), r["label"]))
    return rows


def _decision(**over):
    base = dict(tier=1, label="unroll=8", strategy=None, strategy_name="",
                knobs={"unroll": 8, "bucket_mb": 0, "microbatches": 0},
                predicted_ms=0.5, incumbent_predicted_ms=1.0,
                measured_ms=1.2, margin_pct=50.0, remaining_steps=1000,
                reshape=False)
    base.update(over)
    return controller_mod.Decision(**base)


# ---------------------------------------------------------------------------
# decision shipping: canonical blobs, fingerprints, loud mismatches


def test_verdict_serialization_bitwise_deterministic():
    """Two processes deriving the same decision must serialize
    byte-identical blobs: float rounding, sorted knobs, sorted keys."""
    a = _decision(predicted_ms=0.1 + 0.2)       # 0.30000000000000004
    b = _decision(predicted_ms=0.3)
    blob_a = shipping.serialize_verdict(a, boundary=64)
    blob_b = shipping.serialize_verdict(b, boundary=64)
    assert blob_a == blob_b
    assert shipping.fingerprint(blob_a) == shipping.fingerprint(blob_b)
    # Knob dict insertion order must not leak into the bytes.
    c = _decision(knobs={"microbatches": 0, "bucket_mb": 0, "unroll": 8})
    assert (shipping.serialize_verdict(c, boundary=64)
            == shipping.serialize_verdict(_decision(), boundary=64))
    # The hold verdict is canonical too (every window ships one).
    hold_a = shipping.serialize_verdict(None, boundary=64)
    hold_b = shipping.serialize_verdict(None, boundary=64)
    assert hold_a == hold_b
    assert json.loads(hold_a.decode()) == {"v": 1, "boundary": 64,
                                           "switch": False}
    # Strategy object ids never cross the wire: value-typed fields only.
    payload = json.loads(blob_a.decode())
    assert "strategy" not in payload
    assert payload["strategy_name"] == ""


def test_two_chiefs_publish_identical_bytes(monkeypatch):
    """Two Controllers fed identical windows publish byte-identical
    blobs AND fingerprints under the same key sequence — the KV stores
    of two identically-driven chiefs are indistinguishable."""
    monkeypatch.setenv("AUTODIST_RETUNE", "exec")
    monkeypatch.setenv("AUTODIST_RETUNE_PATIENCE", "2")
    runner, _batch = _build()
    rows = _stub_rows(("fast", 0.5, 1))
    monkeypatch.setattr(controller_mod.Controller, "_priced_candidates",
                        lambda self, remaining: (1.0, list(rows)))
    monkeypatch.setattr(controller_mod.Controller, "_switch_cost_estimate",
                        lambda self, tier, reshape=False: 0.0)
    stores = []
    for _ in range(2):
        store = {}
        shipping.reset_seq()
        ctl = controller_mod.Controller(
            runner, channel=shipping.DecisionChannel(_dict_kv(store)))
        assert ctl.observe_window(1.0, remaining_steps=1000, step=8) is None
        dec = ctl.observe_window(1.0, remaining_steps=1000, step=16)
        assert dec is not None and dec.label == "fast"
        stores.append(store)
    assert stores[0] == stores[1]       # byte-identical blobs + echoes
    assert set(stores[0]) == {"autodist/retune/1", "autodist/retune/1/id",
                              "autodist/retune/2", "autodist/retune/2/id"}


def test_fetch_rejects_corrupted_blob_and_wrong_boundary():
    store = {}
    ch = shipping.DecisionChannel(_dict_kv(store))
    ch.publish(_decision(), boundary=32)

    # Corrupted blob: the recomputed fingerprint no longer matches the
    # published echo — loud refusal, not a silent divergent switch.
    tampered = dict(store)
    tampered["autodist/retune/1"] = (
        store["autodist/retune/1"].replace(b'"unroll":8', b'"unroll":4'))
    shipping.reset_seq()
    with pytest.raises(shipping.ShipMismatch, match="fingerprint"):
        shipping.DecisionChannel(_dict_kv(tampered)).fetch(boundary=32)

    # Intact blob but this process is at a different megastep boundary:
    # the fleet disagrees about the cadence — refuse.
    shipping.reset_seq()
    with pytest.raises(shipping.ShipMismatch, match="boundary"):
        shipping.DecisionChannel(_dict_kv(store)).fetch(boundary=40)

    # Sanity: the untampered fetch at the right boundary decodes.
    shipping.reset_seq()
    payload = shipping.DecisionChannel(_dict_kv(store)).fetch(boundary=32)
    assert payload["switch"] and payload["label"] == "unroll=8"


def test_chief_and_follower_switch_same_boundary(monkeypatch):
    """One shared (stubbed) KV store: the chief's published verdict and
    the follower's materialized decision re-serialize to the SAME bytes
    at the SAME boundary — both processes switch bitwise-consistently."""
    monkeypatch.setenv("AUTODIST_RETUNE", "exec")
    monkeypatch.setenv("AUTODIST_RETUNE_PATIENCE", "1")
    runner, _batch = _build()
    rows = _stub_rows(("fast", 0.5, 1))
    monkeypatch.setattr(controller_mod.Controller, "_priced_candidates",
                        lambda self, remaining: (1.0, list(rows)))
    monkeypatch.setattr(controller_mod.Controller, "_switch_cost_estimate",
                        lambda self, tier, reshape=False: 0.0)
    store = {}
    chief = controller_mod.Controller(
        runner, channel=shipping.DecisionChannel(_dict_kv(store)))
    follower = controller_mod.FollowerController(
        runner, channel=shipping.DecisionChannel(_dict_kv(store)))

    shipping.reset_seq()
    chief_dec = chief.observe_window(1.0, remaining_steps=1000, step=8)
    assert chief_dec is not None
    shipping.reset_seq()    # the follower is its own process: own sequence
    foll_dec = follower.observe_window(1.0, remaining_steps=1000, step=8)
    assert foll_dec is not None
    assert foll_dec.label == chief_dec.label == "fast"
    assert foll_dec.knobs == chief_dec.knobs
    assert (shipping.serialize_verdict(foll_dec, 8)
            == shipping.serialize_verdict(chief_dec, 8))

    # A follower whose loop drifted to a different boundary refuses.
    shipping.reset_seq()
    chief.observe_window(1.0, remaining_steps=992, step=16)
    shipping.reset_seq()
    with pytest.raises(shipping.ShipMismatch, match="boundary"):
        follower.observe_window(1.0, remaining_steps=992, step=24)

    # Out-of-cadence evaluations are declined on shipped jobs: the
    # verdict sequence must stay SPMD-symmetric.
    assert chief.request_evaluation("straggler verdict") is False


def test_multiprocess_without_channel_declines_once_counts_each(
        monkeypatch):
    """No KV byte channel on a 2-process job: controller_for returns
    None, warns ONCE per process, and bumps ``retune.declined`` on every
    declined resolution (the old behavior warned every window)."""
    monkeypatch.setenv("AUTODIST_RETUNE", "exec")
    runner, _batch = _build()
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    monkeypatch.setattr(shipping, "channel", lambda: None)
    warnings = []
    monkeypatch.setattr(controller_mod.logging, "warning",
                        lambda msg, *a: warnings.append(msg % a if a else msg))
    assert controller_mod.controller_for(runner) is None
    assert controller_mod.controller_for(runner) is None
    assert controller_mod.controller_for(runner) is None
    snap = observability.registry().snapshot()
    assert snap["counters"]["retune.declined"] == 3
    declined = [w for w in warnings if "no coordination-service" in w]
    assert len(declined) == 1, f"warned {len(declined)} times: {declined}"


# ---------------------------------------------------------------------------
# slow_host chaos fault


def test_slow_host_schedule_deterministic_and_spares_chief():
    spec = "40:seed7"
    # The chief (and any host but the target) is never delayed.
    assert all(chaos.slow_host_delay_ms(s, 0, spec=spec) == 0.0
               for s in range(20))
    assert chaos.slow_host_delay_ms(5, 2, spec=spec) == 0.0
    # The degraded host's delay replays bit-identically and jitters
    # within [0.5*MS, 1.5*MS).
    delays = [chaos.slow_host_delay_ms(s, chaos.SLOW_HOST_TARGET, spec=spec)
              for s in range(1, 64)]
    assert delays == [chaos.slow_host_delay_ms(s, chaos.SLOW_HOST_TARGET,
                                               spec=spec)
                      for s in range(1, 64)]
    assert all(20.0 <= d < 60.0 for d in delays)
    assert len(set(round(d, 6) for d in delays)) > 1  # actually jittered
    # A different seed is a different host.
    assert delays != [chaos.slow_host_delay_ms(s, chaos.SLOW_HOST_TARGET,
                                               spec="40:other")
                      for s in range(1, 64)]


def test_slow_host_injection_records_event_once(monkeypatch):
    monkeypatch.setenv("AUTODIST_CHAOS", "slow_host=2:s")
    chaos.reset()
    d1 = chaos.maybe_slow_host(3, process_index=chaos.SLOW_HOST_TARGET)
    d2 = chaos.maybe_slow_host(4, process_index=chaos.SLOW_HOST_TARGET)
    assert d1 > 0.0 and d2 > 0.0
    assert chaos.maybe_slow_host(3, process_index=0) == 0.0
    evs = [e for e in observability.recorder.events()
           if e["kind"] == "chaos:slow-host"]
    assert len(evs) == 1, "injection event must record once per process"


# ---------------------------------------------------------------------------
# healer: hysteresis + priced eviction


def _straggler_verdict(cause_ms, window=8):
    return {"hosts": {0: {}, 1: {}}, "windows": window, "significant": True,
            "max_skew_wait_ms": cause_ms, "max_abs_offset_ms": 0.1,
            "straggler": {"host": 1, "share_pct": 100.0,
                          "cause": "device_compute", "cause_ms": cause_ms,
                          "detail": f"host 1 drags {cause_ms:.1f} ms/step"}}


class _StubCoordinator:
    reform_pending = False
    world_size = 2

    def __init__(self):
        self.pinned, self.reforms = [], []

    def pin_strategy(self, sid):
        self.pinned.append(sid)

    def request_reform(self, world, reason=""):
        self.reforms.append((world, reason))


def _armed_healer(monkeypatch, patience, runner=None):
    monkeypatch.setenv("AUTODIST_RETUNE", "exec")
    monkeypatch.setenv("AUTODIST_SELFHEAL", "1")
    monkeypatch.setenv("AUTODIST_SELFHEAL_PATIENCE", str(patience))
    if runner is None:
        runner, _batch = _build()
    co = _StubCoordinator()
    h = selfheal.bind(SimpleNamespace(_runner=runner), co)
    assert h is not None
    return h, co


def test_healer_disabled_without_coordinator_or_knob(monkeypatch):
    monkeypatch.setenv("AUTODIST_RETUNE", "exec")
    runner, _batch = _build()
    assert selfheal.bind(SimpleNamespace(_runner=runner), None) is None
    monkeypatch.setenv("AUTODIST_SELFHEAL", "0")
    assert not selfheal.enabled()
    assert selfheal.bind(SimpleNamespace(_runner=runner),
                         _StubCoordinator()) is None


def test_transient_blip_never_evicts(monkeypatch):
    """Hysteresis: the verdict clearing mid-streak resets it — two
    degraded rounds, a clean round, two more degraded rounds never reach
    patience 3, so no eviction is even priced."""
    h, co = _armed_healer(monkeypatch, patience=3)
    h.note_progress(100, 10_000, 50.0)
    skew.set_last_summary(_straggler_verdict(40.0))
    degraded = SimpleNamespace(_active={("straggler", 1): {}})
    clean = SimpleNamespace(_active={})
    for det in (degraded, degraded, clean, degraded, degraded):
        h.note_anomalies(det, now=time.time())
    assert h._streak == 2 and h._streak_host == 1
    assert h.decisions == [] and co.reforms == [] and co.pinned == []
    assert not [e for e in observability.recorder.events()
                if e["kind"] == "selfheal"]
    # The streak moving to a DIFFERENT host restarts the count too.
    h.note_anomalies(SimpleNamespace(_active={("straggler", 0): {}}),
                     now=time.time())
    assert h._streak == 1 and h._streak_host == 0


def test_persistent_straggler_priced_eviction(monkeypatch):
    """A held verdict whose payoff clears the re-exec cost pins a shrink
    challenger and requests the re-form with the priced record."""
    h, co = _armed_healer(monkeypatch, patience=2)
    h.note_progress(100, 5000, 100.0)   # 4900 steps remaining, p50 100ms
    skew.set_last_summary(_straggler_verdict(80.0))
    det = SimpleNamespace(_active={("straggler", 1): {}})
    h.note_anomalies(det, now=1000.0)
    assert co.reforms == []             # streak 1 < patience
    h.note_anomalies(det, now=1002.5)
    assert len(co.reforms) == 1
    world, reason = co.reforms[0]
    assert world == 1 and reason.startswith("selfheal: degraded host 1")
    assert len(h.decisions) == 1
    rec = h.decisions[0]
    # saving = cur - (cur - drag) * w/(w-1) = 100 - 20*2 = 60 ms/step
    assert rec["decision"] == "evict" and rec["host"] == 1
    assert rec["world"] == 2 and rec["new_world"] == 1
    assert rec["before_p50_ms"] == 100.0
    assert rec["saving_ms_per_step"] == pytest.approx(60.0)
    assert rec["payoff_ms"] == pytest.approx(60.0 * 4900)
    assert rec["degrade_to_decision_ms"] == pytest.approx(2500.0)
    # The shrink challenger was serialized and pinned for the re-exec.
    assert rec["pinned_strategy_id"] and co.pinned == [
        rec["pinned_strategy_id"]]
    snap = observability.registry().snapshot()
    assert snap["counters"]["selfheal.decisions"] == 1
    assert snap["gauges"]["selfheal.degrade_to_decision_ms"] == \
        pytest.approx(2500.0)
    evs = [e for e in observability.recorder.events()
           if e["kind"] == "selfheal"]
    assert len(evs) == 1 and evs[0]["decision"] == "evict"
    # The streak armed again only from scratch after the decision.
    assert h._streak == 0 and h._streak_host is None


def test_eviction_refused_when_payoff_below_cost(monkeypatch):
    """Near the end of the run the saving cannot amortize the re-exec
    downtime: the healer refuses, with ONE priced refusal event."""
    h, co = _armed_healer(monkeypatch, patience=2)
    h.note_progress(990, 1000, 100.0)   # only 10 steps remaining
    skew.set_last_summary(_straggler_verdict(80.0))
    det = SimpleNamespace(_active={("straggler", 1): {}})
    for now in (1.0, 2.0, 3.0, 4.0):
        h.note_anomalies(det, now=now)
    assert h.decisions == [] and co.reforms == [] and co.pinned == []
    evs = [e for e in observability.recorder.events()
           if e["kind"] == "selfheal"]
    assert len(evs) == 1, "refusal event must not spam every round"
    assert evs[0]["decision"] == "refused"
    assert evs[0]["payoff_ms"] < evs[0]["reexec_cost_ms"]


# ---------------------------------------------------------------------------
# goodput stitch: the selfheal_ms class


def _segment(gen, start, end, goodput_ms, classes, **over):
    wall = (end - start) * 1e3
    seg = {"run_id": "r-heal", "generation": gen, "start": start,
           "end": end, "wall_ms": wall, "goodput_ms": goodput_ms,
           "classes": classes, "steps": 100, "peak_flops_total": 1e12,
           "model_flops": 1e12}
    seg.update(over)
    return seg


def test_stitch_reclassifies_selfheal_episode(tmp_path):
    """A generation that ended by self-heal eviction bills its drain
    save AND the following gap as ``selfheal_ms`` — a class move, so the
    classes still sum to the stitched wall exactly."""
    log = tmp_path / "stitch"
    log.mkdir()
    segs = [
        _segment(0, 100.0, 110.0, 8000.0,
                 {"emergency_save_ms": 500.0, "other_ms": 1500.0},
                 end_reason="selfheal"),
        _segment(1, 112.0, 120.0, 7000.0, {"other_ms": 1000.0}),
    ]
    for seg in segs:
        with open(log / f"goodput_r-heal_g{seg['generation']}.json",
                  "w") as f:
            json.dump(seg, f)
    st = goodput.stitch_run("r-heal", log_dir=str(log))
    assert st["generations"] == [0, 1]
    assert st["classes"]["selfheal_ms"] == pytest.approx(2500.0)
    assert st["classes"]["emergency_save_ms"] == 0.0
    assert st["classes"]["reexec_gap_ms"] == 0.0
    assert st["selfheal_episodes"] == [
        {"generation": 0, "drain_ms": 500.0, "gap_ms": 2000.0,
         "total_ms": 2500.0}]
    # Sum-to-wall stays exact across the reclassification.
    total = st["goodput_ms"] + sum(st["classes"].values())
    assert total == pytest.approx(st["wall_ms"], abs=0.01)
    # The healer's own pricing reads this back: one episode, 2500ms.
    assert goodput.priced_downtime("r-heal", log_dir=str(log))[
        "reexec_ms"] == pytest.approx(2500.0)


def test_stitch_plain_elastic_gap_stays_reexec(tmp_path):
    """Without the selfheal end_reason the same shape bills the gap as
    plain ``reexec_gap_ms`` — the episode list stays empty."""
    log = tmp_path / "stitch2"
    log.mkdir()
    segs = [
        _segment(0, 100.0, 110.0, 8000.0,
                 {"emergency_save_ms": 500.0, "other_ms": 1500.0},
                 run_id="r-plain"),
        _segment(1, 112.0, 120.0, 7000.0, {"other_ms": 1000.0},
                 run_id="r-plain"),
    ]
    for seg in segs:
        with open(log / f"goodput_r-plain_g{seg['generation']}.json",
                  "w") as f:
            json.dump(seg, f)
    st = goodput.stitch_run("r-plain", log_dir=str(log))
    assert st["classes"]["reexec_gap_ms"] == pytest.approx(2000.0)
    assert st["classes"]["emergency_save_ms"] == pytest.approx(500.0)
    assert st["classes"]["selfheal_ms"] == 0.0
    assert st["selfheal_episodes"] == []


# ---------------------------------------------------------------------------
# acceptance: the full 2-generation self-heal episode


def test_selfheal_end_to_end_two_generations(monkeypatch, tmp_path):
    """Chaos-degraded host -> straggler verdict -> held against
    hysteresis -> priced shrink decision -> emergency-save -> re-exec at
    N-1 with the challenger pinned -> resume, finishing with decreasing
    loss, one stitched ``selfheal_ms`` timeline, and the report's
    Re-tuning section listing the episode."""
    from autodist_tpu import report
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.checkpoint import CheckpointManager
    from autodist_tpu.coordinator import Coordinator
    from autodist_tpu.resilience import ElasticReform
    from autodist_tpu.strategy import PS

    num_steps, window, drag_ms = 600, 8, 40.0
    n_chips = len(jax.devices())
    half = n_chips // 2
    monkeypatch.setenv("AUTODIST_RETUNE", "exec")
    monkeypatch.setenv("AUTODIST_SELFHEAL", "1")
    monkeypatch.setenv("AUTODIST_SELFHEAL_PATIENCE", "2")
    monkeypatch.setenv("AUTODIST_GUARD_CHECK_EVERY", str(window))
    monkeypatch.setenv("AUTODIST_CHAOS", f"slow_host={int(drag_ms)}:e2e")
    monkeypatch.setenv("AUTODIST_RUN_ID", f"e2e-selfheal-{os.getpid()}")
    observability.refresh()
    degrade_at = 2 * window + 1     # first flushed window fully degraded

    bs = 16 * n_chips
    rng = np.random.RandomState(0)
    dims = (64, 256, 256, 8)
    params = {f"w{i}": jnp.asarray(
                  rng.randn(dims[i], dims[i + 1]).astype(np.float32) * 0.05)
              for i in range(len(dims) - 1)}
    batch = (rng.randn(bs, dims[0]).astype(np.float32),
             rng.randn(bs, dims[-1]).astype(np.float32))

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(len(dims) - 1):
            h = h @ p[f"w{i}"]
            if i < len(dims) - 2:
                h = jax.nn.relu(h)
        return jnp.mean((h - y) ** 2)

    def build(devices=None, mesh_axes=None):
        _reset_default()
        ad = AutoDist(strategy_builder=PS(), devices=devices,
                      mesh_axes=mesh_axes)
        item = ad.capture(loss_fn, params, optax.adam(3e-3),
                          example_batch=batch)
        return ad.create_distributed_session(item)

    runner = build()
    mgr = CheckpointManager(runner, str(tmp_path / "ckpt"),
                            save_interval_steps=10_000)
    state = mgr.restore_or_init()
    co = Coordinator(None, None)
    execs = []
    co._exec = lambda *a: execs.append(a)   # capture the re-exec env
    co._world_size = 2

    def feed():
        # Host 1's deterministic chaos drag, paid by the chief as
        # barrier wait inside the measured step latency; one straggler
        # verdict per sync round (the monitor transport tier-1 tests
        # use: a synthetic skew summary + observe_cluster).
        i = 0
        while True:
            i += 1
            if i >= degrade_at and not co.reform_pending:
                time.sleep(chaos.slow_host_delay_ms(i, 1) / 1e3)
                if i % window == 0:
                    skew.set_last_summary(_straggler_verdict(drag_ms,
                                                             window))
                    monitor.observe_cluster([], now=time.time())
            yield batch

    with pytest.raises(ElasticReform) as reform:
        mgr.run(state, feed(), num_steps=num_steps, coordinator=co,
                unroll=1)
    mgr.close()
    reform_step = reform.value.step
    assert reform_step >= degrade_at

    # The deciding generation's record: priced, host 1, shrink 2 -> 1.
    healer = selfheal.healer()
    assert healer is not None and len(healer.decisions) == 1
    rec = healer.decisions[0]
    assert rec["host"] == 1 and rec["new_world"] == 1
    assert rec["payoff_ms"] > rec["reexec_cost_ms"]
    assert rec["degrade_to_decision_ms"] is not None

    # The re-exec env pins the shrink challenger for the new generation.
    (_exe, _argv, env), = execs
    assert env.get("AUTODIST_STRATEGY_ID") == rec["pinned_strategy_id"]
    assert env.get("AUTODIST_RUN_GENERATION") == "1"

    # Generation 1 (simulated in-process): resume on half the devices.
    time.sleep(0.05)
    monkeypatch.setenv("AUTODIST_RUN_GENERATION", "1")
    observability.reset()
    runner2 = build(devices=jax.devices()[:half],
                    mesh_axes={"data": half})
    mgr2 = CheckpointManager(runner2, str(tmp_path / "ckpt"),
                             save_interval_steps=10_000)
    state2 = mgr2.restore_or_init()
    assert int(jax.device_get(state2.step)) == reform_step, \
        "emergency save / resume step mismatch"
    state2, metrics = mgr2.run(state2, iter(lambda: batch, None),
                               num_steps=num_steps, unroll=1)
    mgr2.close()
    assert int(jax.device_get(state2.step)) == num_steps
    final_loss = float(np.asarray(jax.device_get(metrics["loss"])).ravel()[-1])
    init_loss = float(loss_fn(params, batch))
    assert np.isfinite(final_loss)
    assert final_loss < init_loss, "resumed run must keep converging"

    # One stitched run-level timeline with the episode billed to
    # selfheal_ms and the classes still summing to the stitched wall.
    st = goodput.stitch_run()
    assert st is not None and st["generations"] == [0, 1]
    assert st["classes"]["selfheal_ms"] > 0
    assert len(st["selfheal_episodes"]) == 1
    ep = st["selfheal_episodes"][0]
    assert ep["generation"] == 0
    assert ep["total_ms"] == pytest.approx(ep["drain_ms"] + ep["gap_ms"])
    total = st["goodput_ms"] + sum(st["classes"].values())
    assert total == pytest.approx(st["wall_ms"], rel=0.02)

    # The report's Re-tuning section lists the episode: the deciding
    # generation died in the re-exec, so the record is recovered from
    # the persisted flight logs.
    path = report.render_report(runner2.program,
                                out_path=str(tmp_path / "report.html"))
    html = open(path).read()
    assert "Self-healing: reshape-on-degrade" in html
    assert "host 1" in html
    assert "selfheal_ms" in html
