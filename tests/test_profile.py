"""Per-layer device-time profiler (ISSUE 9 tentpole): scope provenance
from model code (named_scope) through jaxpr/HLO into the attribution
ledger.

Pins the acceptance contract: per-scope compute sums to the ledger's
``device_compute`` term and per-scope comms to ``exposed_comms`` (exact,
with any remainder in an explicit unattributed bucket) on BOTH the
unroll=1 and unroll=4 paths; ``AUTODIST_TELEMETRY=0`` makes zero
profiling calls (spy-pinned); the report renders the Per-layer profile
section; every zoo model emits named scopes (no model may profile as
100% unattributed).
"""
import itertools
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist, const, observability
from autodist_tpu.graph_item import GraphItem, scope_path
from autodist_tpu.models import ZOO, mlp
from autodist_tpu.observability import attribution, profile
from autodist_tpu.observability.profile import UNATTRIBUTED
from autodist_tpu.strategy import AllReduce
from autodist_tpu.tuner.calibration import Calibration


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch, tmp_path):
    monkeypatch.delenv("AUTODIST_TELEMETRY", raising=False)
    monkeypatch.delenv("AUTODIST_PROFILE", raising=False)
    monkeypatch.setenv("AUTODIST_TUNER_CALIBRATION",
                       str(tmp_path / "cal.json"))
    observability.refresh()
    observability.reset()
    yield
    observability.refresh()
    observability.reset()


# ---------------------------------------------------------------------------
# provenance: scope_path normalization + the per-eqn jaxpr map


def test_scope_path_unwraps_transform_frames():
    assert scope_path("layer0/attn") == "layer0/attn"
    assert scope_path("jvp(layer0)/attn") == "layer0/attn"
    assert scope_path("transpose(jvp(layer0))/attn") == "layer0/attn"
    assert scope_path(
        "jit(f)/jit(main)/transpose(jvp(stage0/block1))/conv1") == \
        "stage0/block1/conv1"
    assert scope_path("jit(f)/jit(main)") == ""
    assert scope_path("") == ""


@pytest.mark.parametrize("op_name, path, scope", [
    ("jit(step)/jvp(layer1)/moe/while/body/dispatch/gather",
     "layer1/moe/dispatch/gather", ("moe/dispatch", "forward")),
    ("jit(step)/transpose(jvp(layer3))/moe/while/body/experts/"
     "transpose(experts)/jvp(jit(gmm))/pallas_call",
     "layer3/moe/experts/experts/pallas_call", ("moe/experts", "backward")),
    ("jit(step)/jvp(layer1)/moe/while/cond/lt", "layer1/moe/lt",
     ("moe", "forward")),
    # The loop itself keeps its name: the layer's, in no sub-scope.
    ("jit(step)/jvp(layer1)/moe/while", "layer1/moe/while",
     ("moe", "forward")),
    ("jit(f)/outer/cond/branch_1_fun/while/body/inner/mul",
     "outer/inner/mul", ("outer/inner", profile.UNATTRIBUTED)),
    # A looped model's scan over its passes, differentiated: the layers'
    # rows, and ``pass`` for what the scan runs in no scope of its body.
    ("jit(step_fn)/jvp(pass)/while/body/closed_call/layer2/attn/qkv/"
     "dot_general", "pass/layer2/attn/qkv/dot_general", ("attn", "forward")),
    ("jit(step_fn)/transpose(jvp(pass))/while/body/closed_call",
     "pass/closed_call", ("pass", "backward")),
    ("jit(step_fn)/jvp(pass3)/lm_head/dot_general",
     "pass3/lm_head/dot_general", ("head", "forward"))])
def test_a_loops_and_a_conditionals_frames_are_not_scopes(op_name, path,
                                                         scope):
    """``lax.fori_loop`` / ``lax.cond`` / a differentiated ``lax.scan``
    lower a body's instructions under ``while/body`` / ``cond/branch_<i>_fun``
    / ``closed_call``: what is traced inside folds into the user's scopes as
    if there were no loop (the held expert layers' ``dispatch`` and
    ``experts`` live in one)."""
    assert scope_path(op_name) == path
    assert profile._scope_and_phase(op_name) == scope


@pytest.mark.parametrize("op_name, path, scope", [
    ("jit(step)/transpose(jvp(layer1))/jvp(layer1)/checkpoint/gdn/conv/mul",
     "layer1/gdn/conv/mul", ("gdn/conv", "backward")),
    ("jit(step)/transpose(jvp(layer1))/jvp(layer1)/checkpoint/"
     "rematted_computation/gdn/scan/while/body/dot_general",
     "layer1/gdn/scan/dot_general", ("gdn/scan", "backward")),
    ("jit(step)/transpose(jvp(layer1))/jvp(layer1)/remat2", "layer1/remat2",
     ("layer1", "backward")),
    # Inside a differentiated scan the second copy of the scopes is bare.
    ("jit(step_fn)/transpose(jvp(pass))/while/body/closed_call/layer3/mlp/"
     "layer3/mlp/checkpoint/rematted_computation/jit(silu)/logistic",
     "pass/layer3/mlp/logistic", ("mlp", "backward")),
    ("jit(step_fn)/transpose(jvp(pass))/while/body/closed_call/ln_f/ln_f/"
     "checkpoint/mul", "pass/ln_f/mul", ("ln_f", "backward")),
    # A checkpoint inside a scope stays what it was: one more segment.
    ("jit(step)/jvp(layer0)/gdn/scan/checkpoint/inverse/mul",
     "layer0/gdn/scan/checkpoint/inverse/mul", ("gdn/scan", "forward"))])
def test_a_recomputed_sublayers_frames_are_not_scopes(op_name, path, scope):
    """``jax.checkpoint`` around a sublayer (``TransformerConfig.recompute``)
    puts the backward pass's instructions behind a second copy of the call's
    scopes and ``checkpoint/`` or ``checkpoint/rematted_computation/``: they
    keep the rows they had."""
    assert scope_path(op_name) == path
    assert profile._scope_and_phase(op_name) == scope


def test_op_provenance_scopes_and_flops_sum_to_estimate():
    params, loss_fn, batch = mlp.tiny_fixture()
    item = GraphItem.capture(loss_fn, params, optax.sgd(0.1),
                             example_batch=batch)
    prov = item.op_provenance()
    assert prov, "mlp fixture must trace"
    scopes = {r["scope"] for r in prov if r["scope"]}
    assert {"dense0", "dense1"} <= scopes
    # The per-eqn breakdown is the SAME scan flops_estimate sums.
    assert sum(r["flops"] for r in prov) == pytest.approx(
        item.flops_estimate())
    # Matmuls landed inside their layer scopes, not scope-less.
    dots = [r for r in prov if r["prim"] == "dot_general"]
    assert dots and all(r["scope"] for r in dots)
    assert all(r["bytes"] >= 0 for r in prov)


def test_scope_costs_aggregates_per_scope():
    params, loss_fn, batch = mlp.tiny_fixture()
    item = GraphItem.capture(loss_fn, params, optax.sgd(0.1),
                             example_batch=batch)
    sc = item.scope_costs()
    assert sc["dense0"]["flops"] > 0 and sc["dense0"]["ops"] > 0
    assert sum(v["flops"] for v in sc.values()) == pytest.approx(
        item.flops_estimate())


def test_metadata_only_graph_item_has_empty_provenance():
    item = GraphItem(loss_fn=None, params=None, optimizer=None)
    assert item.op_provenance() == []
    assert item.scope_costs() == {}


def test_scope_of_longest_segment_prefix():
    known = {"layer0/attn", "layer0", "dense1"}
    assert profile.scope_of(
        "jit(f)/transpose(jvp(layer0))/attn/dot_general", known) == \
        "layer0/attn"
    assert profile.scope_of("layer0/mlp/up/kernel", known) == "layer0"
    assert profile.scope_of("dense1/kernel", known) == "dense1"
    assert profile.scope_of("optimizer/add", known) is None
    # A scope name must match as a whole segment, not a substring.
    assert profile.scope_of("dense10/kernel", known) is None


# ---------------------------------------------------------------------------
# HLO-side scope costs (synthetic scheduled text)


_HLO = """\
HloModule synthetic
  %f0 = f32[1024,256]{1,0} fusion(%a, %b), kind=kLoop, metadata={op_type="dot" op_name="jit(step)/jit(main)/jvp(dense0)/dot_general"}
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %g), replica_groups=[1,8]<=[8], metadata={op_name="jit(step)/jit(main)/transpose(jvp(dense1))/mul"}
  %m = f32[512]{0} fusion(%c), kind=kLoop, calls=%whatever
"""


def test_hlo_scope_costs_attributes_by_op_name():
    from autodist_tpu.tuner.cost_model import Topology
    topo = Topology(8, 1)
    out = profile.hlo_scope_costs(_HLO, {"dense0", "dense1"}, topo)
    assert out["dense0"]["compute_ms"] > 0
    assert out["dense0"]["comms_ms"] == 0
    assert out["dense1"]["comms_ms"] == pytest.approx(
        topo.all_reduce_cost(4096, 8) * 1e3)
    assert out["dense1"]["wire_bytes"] == pytest.approx(4096)
    # The metadata-less fusion is surfaced unattributed, never absorbed.
    assert out[UNATTRIBUTED]["compute_ms"] > 0
    # unroll divides per-step costs.
    half = profile.hlo_scope_costs(_HLO, {"dense0", "dense1"}, topo,
                                   unroll=2)
    assert half["dense1"]["comms_ms"] == pytest.approx(
        out["dense1"]["comms_ms"] / 2)


# ---------------------------------------------------------------------------
# runner end to end: the reconciliation acceptance contract


def _build():
    params, loss_fn, batch = mlp.tiny_fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(1e-2), example_batch=batch)
    return ad.create_distributed_session(item), batch


@pytest.mark.parametrize("unroll", [1, 4])
def test_profile_reconciles_to_ledger(unroll):
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, itertools.repeat(batch), 8, unroll=unroll)
    gauges = observability.registry().snapshot()["gauges"]
    summ = profile.last_profile()
    assert summ is not None and summ["reconciled"]
    assert summ["unroll"] == unroll and summ["steps"] == 8
    assert summ["scopes"], "mlp must attribute at least one scope"
    # THE acceptance invariant: per-scope sums == the ledger's terms,
    # remainder explicitly in the unattributed bucket.
    sum_c = sum(r["compute_ms"] for r in summ["scopes"].values()) + \
        summ["unattributed"]["compute_ms"]
    sum_m = sum(r["comms_ms"] for r in summ["scopes"].values()) + \
        summ["unattributed"]["comms_ms"]
    assert sum_c == pytest.approx(gauges["attr.device_compute_ms"],
                                  abs=1e-4)
    assert sum_m == pytest.approx(gauges["attr.exposed_comms_ms"],
                                  abs=1e-4)
    # profile.* gauges published.
    assert gauges["profile.scopes"] == len(summ["scopes"])
    assert 0 <= gauges["profile.coverage_pct"] <= 100
    assert "profile.top_compute_ms" in gauges


def test_profile_upgrades_to_scheduled_hlo_when_recorded():
    runner, batch = _build()
    state = runner.create_state()
    runner.make_callable(batch, aot=True)  # AOT stashes the scheduled HLO
    assert runner._scheduled_hlo_text is not None
    runner.run(state, itertools.repeat(batch), 4)
    summ = profile.last_profile()
    assert summ["sources"]["compute"] == "scheduled-hlo"
    gauges = observability.registry().snapshot()["gauges"]
    sum_c = sum(r["compute_ms"] for r in summ["scopes"].values()) + \
        summ["unattributed"]["compute_ms"]
    assert sum_c == pytest.approx(gauges["attr.device_compute_ms"],
                                  abs=1e-4)


def test_profile_knob_off_disables(monkeypatch):
    monkeypatch.setenv("AUTODIST_PROFILE", "0")
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, itertools.repeat(batch), 4)
    assert profile.last_profile() is None
    gauges = observability.registry().snapshot()["gauges"]
    assert not any(k.startswith("profile.") for k in gauges)
    # The ledger still ran — only the per-layer split is off.
    assert "attr.wall_ms" in gauges


def test_telemetry_off_makes_zero_profiling_calls(monkeypatch):
    monkeypatch.setenv("AUTODIST_TELEMETRY", "0")
    observability.refresh()
    calls = []

    def spy(label):
        def fn(*a, **k):
            calls.append(label)
        return fn

    monkeypatch.setattr(profile, "profile_runner", spy("profile-runner"))
    monkeypatch.setattr(profile, "model_scope_costs", spy("model-costs"))
    monkeypatch.setattr(profile, "hlo_scope_costs", spy("hlo-costs"))
    monkeypatch.setattr(profile, "finalize", spy("finalize"))
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, itertools.repeat(batch), 4)
    assert calls == [], f"profiling calls with telemetry off: {calls}"
    assert profile.last_profile() is None


# ---------------------------------------------------------------------------
# surfacing: report, monitor, sidecar


def test_report_renders_per_layer_profile():
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, itertools.repeat(batch), 4)
    observability.cluster._ingest([observability.snapshot()])
    path = runner.write_report(batch)
    text = open(path).read()
    assert "Per-layer profile" in text
    assert "dense0" in text
    assert "predicted" in text


_SYNTH = {
    "scopes": {"layer0/attn": {"compute_ms": 2.0, "comms_ms": 0.5,
                               "wire_bytes": 4096.0,
                               "predicted_compute_ms": 1.0,
                               "predicted_comms_ms": 1.0, "ops": 3}},
    "unattributed": {"compute_ms": 0.25, "comms_ms": 0.0,
                     "wire_bytes": 0.0},
    "totals": {"compute_ms": 2.25, "comms_ms": 0.5, "wire_bytes": 4096.0},
    "coverage_pct": 90.9, "top": ["layer0/attn"],
    "sources": {"compute": "scheduled-hlo", "comms": "scheduled-hlo"},
    "reconciled": True, "unroll": 1, "steps": 4,
}


def test_monitor_surfaces_profile_topk():
    from autodist_tpu.observability import monitor
    profile.set_last_profile(dict(_SYNTH))
    text = monitor.prometheus_text()
    assert 'autodist_profile_compute_ms{scope="layer0/attn"} 2.0' in text
    assert 'autodist_profile_wire_bytes{scope="layer0/attn"}' in text
    doc = monitor.status()
    assert doc["profile"]["top"][0]["scope"] == "layer0/attn"
    assert doc["profile"]["coverage_pct"] == pytest.approx(90.9)


def test_profile_sidecar_written_under_dump_graphs(monkeypatch, tmp_path):
    monkeypatch.setenv("AUTODIST_DUMP_GRAPHS", "1")
    monkeypatch.setattr(const, "DEFAULT_GRAPH_DUMP_DIR",
                        str(tmp_path / "graphs"))
    runner, batch = _build()
    state = runner.create_state()
    runner.run(state, itertools.repeat(batch), 4)
    path = tmp_path / "graphs" / "profile.json"
    assert path.exists(), "profile.json sidecar missing"
    summ = json.loads(path.read_text())
    assert summ["scopes"] and "unattributed" in summ


def test_dump_scheduled_writes_async_window_sidecar(monkeypatch, tmp_path):
    monkeypatch.setattr(const, "DEFAULT_GRAPH_DUMP_DIR",
                        str(tmp_path / "graphs"))
    runner, batch = _build()
    path = runner.dump_scheduled(batch)
    assert path.endswith("4-scheduled-hlo.txt")
    sidecar = path.replace(".txt", ".windows.json")
    assert os.path.exists(sidecar), \
        "dump_scheduled must write the parsed async-window summary"
    summ = json.loads(open(sidecar).read())
    assert isinstance(summ["windows"], list)
    assert np.isfinite(summ["exposed_ms_per_step"])
    assert summ["exposed_ms_per_step"] >= 0


def test_feed_calibration_per_scope_offenders(tmp_path):
    cal = Calibration(path=str(tmp_path / "c.json"))
    out = profile.feed_calibration(dict(_SYNTH), calibration=cal)
    assert out is cal
    contexts = {s.get("context") for s in cal.samples}
    assert "profile:layer0/attn" in contexts
    # measured compute 2.0 vs predicted 1.0 => compute scale up;
    # measured comms 0.5 vs predicted 1.0 => comms scale down.
    assert cal.term_scales["compute"] > 1.0
    assert cal.term_scales["comms"] < 1.0
    # Model-vs-itself teaches nothing: no scheduled-HLO source, no feed.
    cal2 = Calibration(path=str(tmp_path / "c2.json"))
    model_only = dict(_SYNTH, sources={"compute": "jaxpr-flops",
                                       "comms": "strategy-model"})
    assert profile.feed_calibration(model_only, calibration=cal2) is None
    assert cal2.term_scales == {"compute": 1.0, "comms": 1.0}


# ---------------------------------------------------------------------------
# model-zoo scope lint: no model may profile as 100% unattributed


@pytest.mark.parametrize("name", sorted(ZOO))
def test_zoo_model_emits_named_scopes(name):
    params, loss_fn, batch = ZOO[name].tiny_fixture()
    item = GraphItem.capture(loss_fn, params, optax.sgd(0.1),
                             example_batch=batch)
    sc = item.scope_costs()
    named = {k: v for k, v in sc.items() if k}
    assert named, f"{name}: forward emits no named scopes"
    total = sum(v["flops"] for v in sc.values())
    attributed = sum(v["flops"] for v in named.values())
    assert total > 0, f"{name}: fixture traces no matmul/conv flops"
    assert attributed / total >= 0.5, (
        f"{name}: only {100 * attributed / total:.0f}% of flops fall "
        f"inside named scopes — the per-layer profile would be mostly "
        f"unattributed")
