"""What is left of ISSUE 7's module (``kernel/overlap.py``) once nothing but
the compiler and ``grad_scatter`` orders a step's communication: bucket-plan
determinism, exposed-comms parsing, the cost model's bucket and compressor
terms, the tuner's exec variants, the scheduled-HLO dump.

The bucket issue plan stays a pure, chief/worker-identical function of the
captured program, and the exposed-comms figure is computed from
scheduled-HLO async start/done windows.
"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist, observability
from autodist_tpu.autodist import _reset_default
from autodist_tpu.graph_item import GraphItem, VariableItem
from autodist_tpu.kernel import overlap
from autodist_tpu.strategy import AllReduce
from autodist_tpu.tuner.search import EXEC_VARIANTS
from autodist_tpu.tuner.cost_model import (CostModel, Topology,
                                           _compressor_factor)

BATCH = 32


def _loss_fn(params, batch):
    x, y = batch
    h = jax.nn.relu(x @ params["w1"])
    h = jax.nn.relu(h @ params["w2"])
    return jnp.mean((h @ params["w3"] - y) ** 2)


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(BATCH, 8).astype(np.float32),
             rng.randn(BATCH, 4).astype(np.float32)) for _ in range(n)]


def _build(builder, monkeypatch):
    _reset_default()
    params = {"w1": jnp.zeros((8, 16)), "w2": jnp.zeros((16, 16)),
              "w3": jnp.zeros((16, 4))}
    ad = AutoDist(strategy_builder=builder)
    item = ad.capture(_loss_fn, params, optax.adam(1e-2),
                      example_batch=_batches(1)[0])
    runner = ad.create_distributed_session(item)
    monkeypatch.setattr(runner, "_obs", None)
    return runner


# -- bucket-plan determinism -------------------------------------------------


def test_bucket_order_deterministic_across_captures(monkeypatch):
    """Repeated capture of the same model yields an identical bucket
    issue order, grad-production order, and plan fingerprint — the
    chief/worker agreement contract (same as the tuner tie-break)."""
    runs = []
    for _ in range(3):
        r = _build(AllReduce(), monkeypatch)
        plan = r.bucket_plan()
        runs.append((plan, overlap.plan_fingerprint(plan),
                     r.grad_production_order()))
    assert runs[0] == runs[1] == runs[2]
    plan = runs[0][0]
    assert plan, "AllReduce vars must produce a fused bucket plan"
    names = [nm for b in plan for nm in b.names]
    assert sorted(names) == ["w1", "w2", "w3"]
    # Reverse-layer issue: the LAST layer's gradient is produced first.
    order = runs[0][2]
    assert order["w3"] < order["w2"] < order["w1"]
    assert names[0] == "w3"


def test_bucket_plan_splits_at_cap_and_orders_by_completion():
    members = [("a", (0, 0, "f32"), 3 << 20), ("b", (0, 0, "f32"), 3 << 20),
               ("c", (0, 0, "f32"), 3 << 20)]
    order = {"a": 5, "b": 1, "c": 3}
    plan = overlap.bucket_plan(members, order=order, cap_bytes=4 << 20)
    assert [b.names for b in plan] == [("b",), ("c",), ("a",)]
    uncapped = overlap.bucket_plan(members, order=order, cap_bytes=0)
    assert [b.names for b in uncapped] == [("b", "c", "a")]
    assert overlap.plan_fingerprint(plan) != overlap.plan_fingerprint(uncapped)


# -- exposed-comms parsing ---------------------------------------------------

_HLO_EXPOSED = """HloModule test
ENTRY %main {
  %p0 = f32[1024,256]{1,0} parameter(0)
  %ar-start = (f32[1024,256]{1,0}, f32[1024,256]{1,0}) all-reduce-start(%p0), replica_groups=[1,8]<=[8]
  %ar-done = f32[1024,256]{1,0} all-reduce-done(%ar-start)
}
"""

_HLO_HIDDEN = """HloModule test
ENTRY %main {
  %p0 = f32[1024,256]{1,0} parameter(0)
  %ar-start = (f32[1024,256]{1,0}, f32[1024,256]{1,0}) all-reduce-start(%p0), replica_groups=[1,8]<=[8]
  %fusion.1 = f32[4096,4096]{1,0} fusion(%p0), kind=kLoop
  %fusion.2 = f32[4096,4096]{1,0} fusion(%fusion.1), kind=kLoop
  %ar-done = f32[1024,256]{1,0} all-reduce-done(%ar-start)
}
"""

_HLO_SYNC = """HloModule test
ENTRY %main {
  %p0 = f32[1024,256]{1,0} parameter(0)
  %ar = f32[1024,256]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3,4,5,6,7}}
  %fusion.1 = f32[4096,4096]{1,0} fusion(%ar), kind=kLoop
}
"""


def test_async_windows_parse_bytes_groups_and_compute():
    recs = overlap.async_collective_windows(_HLO_HIDDEN)
    assert len(recs) == 1
    rec = recs[0]
    assert rec["op"] == "all-reduce"
    assert rec["bytes"] == 1024 * 256 * 4
    assert rec["group_size"] == 8
    assert rec["window_ops"] == 2
    assert rec["window_compute_bytes"] == 2 * 4096 * 4096 * 4
    bare = overlap.async_collective_windows(_HLO_EXPOSED)[0]
    assert bare["window_ops"] == 0


def test_exposed_ms_decreases_with_scheduled_compute():
    topo = Topology(8, 1)
    exposed = overlap.exposed_collective_ms(_HLO_EXPOSED, topo)
    hidden = overlap.exposed_collective_ms(_HLO_HIDDEN, topo)
    assert exposed > 0
    assert hidden < exposed  # the window's compute hides comm time
    # A back-to-back pair is fully exposed: the full priced collective.
    want = topo.all_reduce_cost(1024 * 256 * 4, 8) * 1e3
    assert exposed == pytest.approx(want)


def test_sync_collectives_count_whole_and_unroll_divides():
    topo = Topology(8, 1)
    ms = overlap.exposed_collective_ms(_HLO_SYNC, topo)
    assert ms == pytest.approx(
        topo.all_reduce_cost(1024 * 256 * 4, 8) * 1e3)
    assert overlap.exposed_collective_ms(_HLO_SYNC, topo, unroll=4) == \
        pytest.approx(ms / 4)


# -- scheduled-HLO dump ------------------------------------------------------


def test_dump_scheduled_writes_parseable_text(monkeypatch, tmp_path):
    runner = _build(AllReduce(), monkeypatch)
    batch = _batches(1)[0]
    path = runner.dump_scheduled(batch)
    assert path.endswith("4-scheduled-hlo.txt"), path
    with open(path) as f:
        text = f.read()
    # The parser accepts the real compiled text: a list (possibly empty
    # of async pairs on CPU) and a finite non-negative estimate.
    assert isinstance(overlap.async_collective_windows(text), list)
    ms = overlap.exposed_collective_ms(text, Topology(8, 1))
    assert np.isfinite(ms) and ms >= 0


# -- cost model bucket and wire terms ----------------------------------------


def _meta_item(nbytes_each=8 << 20, n_vars=4, flops=0.0):
    item = GraphItem(loss_fn=None, params=None, optimizer=None,
                     variables=[VariableItem(f"v{i}",
                                             (nbytes_each // 4,),
                                             jnp.float32)
                                for i in range(n_vars)])
    item._flops_estimate = flops
    return item


def _spec(tmp_path, num_hosts=4):
    from autodist_tpu.resource_spec import ResourceSpec
    path = tmp_path / "spec.yml"
    path.write_text("tpu:\n  accelerator: v5e-32\n"
                    f"  num_hosts: {num_hosts}\n  chips_per_host: 8\n")
    return ResourceSpec(str(path))


def test_bucket_cap_adds_latency_terms(tmp_path):
    spec = _spec(tmp_path)
    model = CostModel(Topology(32, 4))
    item = _meta_item(nbytes_each=32 << 20)
    strat = AllReduce(chunk_size=128).build(item, spec)
    fine = model.strategy_cost(strat, item, bucket_bytes=4 << 20)
    coarse = model.strategy_cost(strat, item, bucket_bytes=0)
    assert fine["n_buckets"] > coarse["n_buckets"]
    # Same bytes, more latency terms: serialized sync can only grow.
    assert fine["sync_ms"] >= coarse["sync_ms"] - 1e-9


def test_compressor_wire_bytes_priced(tmp_path):
    """Satellite: bf16/int8 wire formats shrink bytes-on-the-wire in the
    cost model instead of pricing as f32."""
    from autodist_tpu.proto import strategy_pb2
    C = strategy_pb2.AllReduceSynchronizer.Compressor
    assert _compressor_factor(C.NoneCompressor) == 1.0
    assert _compressor_factor(C.HorovodCompressor) == 0.5
    assert 0.25 < _compressor_factor(C.Int8Compressor) < 0.26
    big = VariableItem("m", (1024, 1024), jnp.float32)
    f = _compressor_factor(C.PowerSGDCompressor, big)
    assert f == pytest.approx(2 * (1024 + 1024) / (1024 * 1024))
    vec = VariableItem("v", (1024,), jnp.float32)
    assert _compressor_factor(C.PowerSGDCompressor, vec) == 1.0

    spec = _spec(tmp_path)
    model = CostModel(Topology(32, 4))
    item = _meta_item()

    def sync_ms(compressor):
        strat = AllReduce(chunk_size=128).build(item, spec)
        for nc in strat.proto.node_config:
            nc.all_reduce_synchronizer.compressor = compressor
        strat.invalidate_node_cache()
        return model.strategy_cost(strat, item)["sync_ms"]

    f32, bf16, int8 = (sync_ms(C.NoneCompressor),
                       sync_ms(C.HorovodCompressor),
                       sync_ms(C.Int8Compressor))
    assert int8 < bf16 < f32


# -- tuner search exec knobs -------------------------------------------------


def test_exec_variants_fixed_literal_order(tmp_path):
    labels = [v[0] for v in EXEC_VARIANTS]
    assert labels[0] == ""  # serialized baseline wins ties
    assert labels == sorted(labels, key=labels.index)  # literal order
    assert labels[1:] == [f"+microbatches={m}" for m in (4, 8, 16)]

    from autodist_tpu import tuner
    from autodist_tpu.tuner.calibration import Calibration
    spec = _spec(tmp_path)
    item = _meta_item(flops=1e13)
    result = tuner.search(item, spec, calibration=Calibration(
        path=str(tmp_path / "cal.json")))
    for row in result.ranked:
        assert "ar_bucket_mb" in row["knobs"]
        # Priced serially, one figure: the schedule the Runner executes.
        assert "exposed_sync_ms" not in row["breakdown"]
        assert row["breakdown"]["sync_ms"] >= 0
    # Serve objective stays exec-knob-free.
    serve = tuner.search(item, spec, objective="serve_latency",
                         calibration=Calibration(
                             path=str(tmp_path / "cal.json")))
    assert all("ar_bucket_mb" not in r["knobs"] for r in serve.ranked)


# -- telemetry surface -------------------------------------------------------


def test_runner_records_exposed_gauge(monkeypatch):
    if not observability.enabled():
        pytest.skip("telemetry disabled in this environment")
    runner = _build(AllReduce(), monkeypatch)
    monkeypatch.setattr(runner, "_obs", observability)
    observability.registry().reset()
    batch = _batches(1)[0]
    runner.make_callable(batch, aot=True)
    snap = observability.registry().snapshot()
    assert "comms.exposed_ms_per_step" in (snap.get("gauges") or {})
    # The report's Telemetry section reads that gauge into its
    # exposed-comms row, with no scheduler badge beside it.
    from autodist_tpu import report
    html = report._render_telemetry()
    assert "comms exposed" in html
    assert "ms/step (priced from the scheduled HLO" in html
    assert "overlap=" not in html
