"""Hierarchical topology-aware collectives (docs/collectives.md).

Three contracts pinned here:

* execution: the two-level reduce (full-precision RS/AG on the ICI leg,
  codec wire only across DCN) computes the same mean as the flat path,
  on BOTH transports (subgroup collectives and the ppermute fallback)
  and on the explicit nested ``(dcn, ici)`` mesh;
* accounting: the trace-time wire tally equals the cost model's
  ``hier_wire_split`` byte for byte (measured against predicted: what
  lets the tuner trust its per-leg pricing) and the codec factor tables and
  int8 transport crossover stay in sync across modules;
* tuning: ``hierarchical_ar_cost`` degenerates EXACTLY to the flat
  all-reduce price (single host, or f32 DCN wire), is monotonic in the
  knobs that matter, and the search picks a ``+hier=`` variant on a
  slow-DCN many-host topology while never selecting one single-host.
"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu import const, tuner
from autodist_tpu.cluster import Cluster
from autodist_tpu.graph_item import GraphItem, VariableItem
from autodist_tpu.kernel.synchronization import compressor as compressor_mod
from autodist_tpu.kernel.synchronization import hierarchical
from autodist_tpu.resource_spec import Connectivity, ResourceSpec
from autodist_tpu.tuner.calibration import Calibration
from autodist_tpu.tuner.cost_model import (HIER_CODEC_FACTORS, CostModel,
                                           Topology)
from autodist_tpu.tuner.search import hier_exec_variants

CODECS = ("f32", "bf16", "int8", "int8ef")
#: absolute tolerance per codec for a mean of N(0,1) gradients (bf16 on
#: CPU is a cast round-trip; int8 blockwise adds quantization noise).
TOL = {"f32": 1e-6, "bf16": 5e-3, "int8": 2e-2, "int8ef": 2e-2}


def _grads(n=37 * 5, world=8, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(world, n).astype(np.float32)


# -- leg resolution ----------------------------------------------------------


def test_resolve_legs_splits_and_degenerates(monkeypatch):
    assert hierarchical.resolve_legs(8, 4) == (4, 2)
    assert hierarchical.resolve_legs(8, 2) == (2, 4)
    # Invalid splits degenerate to the flat single-leg layout.
    assert hierarchical.resolve_legs(8, None) == (8, 1)
    assert hierarchical.resolve_legs(8, 8) == (8, 1)
    assert hierarchical.resolve_legs(8, 3) == (8, 1)
    # The env knob overrides the resource-spec hint (bench/test fake).
    monkeypatch.setenv("AUTODIST_HIER_ICI", "2")
    assert hierarchical.resolve_legs(8, 4) == (2, 4)


def test_leg_groups_are_host_major():
    assert hierarchical.ici_groups(8, 4) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert hierarchical.dcn_groups(8, 4) == [[0, 4], [1, 5], [2, 6], [3, 7]]


# -- execution numerics ------------------------------------------------------


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "ppermute"])
@pytest.mark.parametrize("codec", CODECS)
def test_hier_mean_matches_flat_mean(codec, grouped, monkeypatch):
    """Both transports of the two-level reduce compute the gradient mean
    within the codec's noise floor — with an odd payload size, so the
    shard padding path is exercised."""
    monkeypatch.setenv("AUTODIST_HIER_ICI", "4")
    grads = _grads()
    ref = grads.mean(axis=0)
    n = grads.shape[1]
    st0 = hierarchical.init_hier_state(n, 4, 2, codec)
    mesh = Mesh(np.array(jax.devices()), (const.MESH_AXIS_DATA,))

    def f(g):
        out, _st = hierarchical.hier_mean(
            g.reshape(n), const.MESH_AXIS_DATA, codec=codec,
            state=st0, grouped=grouped)
        return out

    fm = jax.jit(jax.shard_map(f, mesh=mesh,
                               in_specs=P(const.MESH_AXIS_DATA),
                               out_specs=P(None), check_vma=False))
    out = np.asarray(fm(grads.reshape(-1)))
    assert np.abs(out - ref).max() <= TOL[codec]


@pytest.mark.parametrize("codec", CODECS)
def test_nested_mesh_matches_flat_axis_expression(codec):
    """``hier_mean_nested`` over the explicit ``(dcn, ici)`` mesh from
    ``cluster.build_hierarchical_mesh`` computes the same mean as the
    flat-axis expression: the two are the same schedule, one written
    over subgroups, one over named nested axes."""
    cluster = Cluster(ResourceSpec(None))
    mesh = cluster.build_hierarchical_mesh(devices_per_host=4)
    assert mesh.axis_names == (const.MESH_AXIS_DCN, const.MESH_AXIS_ICI)
    assert dict(mesh.shape) == {const.MESH_AXIS_DCN: 2,
                                const.MESH_AXIS_ICI: 4}
    grads = _grads()
    ref = grads.mean(axis=0)
    n = grads.shape[1]
    st0 = hierarchical.init_hier_state(n, 4, 2, codec)

    def f(g):
        out, _st = hierarchical.hier_mean_nested(
            g.reshape(n), codec=codec, state=st0)
        return out

    fm = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=P((const.MESH_AXIS_DCN, const.MESH_AXIS_ICI)),
        out_specs=P(None), check_vma=False))
    out = np.asarray(fm(grads.reshape(-1)))
    assert np.abs(out - ref).max() <= TOL[codec]


def test_int8ef_reinjects_residual_across_calls(monkeypatch):
    """Error feedback over the DCN shard: with a constant gradient, two
    corrected reduces land closer to the true mean than two uncorrected
    ones on average — i.e. the returned state is a real residual, not a
    passthrough."""
    monkeypatch.setenv("AUTODIST_HIER_ICI", "4")
    grads = _grads(seed=3)
    n = grads.shape[1]
    ref = grads.mean(axis=0)
    mesh = Mesh(np.array(jax.devices()), (const.MESH_AXIS_DATA,))
    st0 = hierarchical.init_hier_state(n, 4, 2, "int8ef")

    def two_rounds(g):
        x = g.reshape(n)
        out1, st = hierarchical.hier_mean(x, const.MESH_AXIS_DATA,
                                          codec="int8ef", state=st0)
        out2, st = hierarchical.hier_mean(x, const.MESH_AXIS_DATA,
                                          codec="int8ef", state=st)
        return out1 + out2

    fm = jax.jit(jax.shard_map(two_rounds, mesh=mesh,
                               in_specs=P(const.MESH_AXIS_DATA),
                               out_specs=P(None), check_vma=False))
    summed = np.asarray(fm(grads.reshape(-1)))
    # Residual re-injection cancels quantization bias: the 2-step sum
    # tracks 2x the true mean tighter than one uncorrected step's noise
    # budget doubled.
    assert np.abs(summed - 2 * ref).max() <= 1.5 * TOL["int8"]


# -- wire accounting ---------------------------------------------------------


@pytest.mark.parametrize("codec", CODECS)
def test_wire_tally_matches_cost_model_split(codec, monkeypatch):
    """The trace-time tally and ``Topology.hier_wire_split`` must agree
    byte for byte: measured against predicted."""
    monkeypatch.setenv("AUTODIST_HIER_ICI", "4")
    grads = _grads()
    n = grads.shape[1]
    st0 = hierarchical.init_hier_state(n, 4, 2, codec)
    mesh = Mesh(np.array(jax.devices()), (const.MESH_AXIS_DATA,))

    def f(g):
        out, _st = hierarchical.hier_mean(
            g.reshape(n), const.MESH_AXIS_DATA, codec=codec, state=st0)
        return out

    hierarchical.reset_wire_tally()
    jax.jit(jax.shard_map(f, mesh=mesh,
                          in_specs=P(const.MESH_AXIS_DATA),
                          out_specs=P(None),
                          check_vma=False))(grads.reshape(-1))
    measured = hierarchical.wire_tally()
    predicted = Topology(8, num_hosts=2).hier_wire_split(n * 4.0, 8, codec)
    assert measured["ici"] == pytest.approx(predicted["ici"])
    assert measured["dcn"] == pytest.approx(predicted["dcn"])


def test_codec_tables_stay_in_sync():
    """The execution-side factor table and the cost model's copy are the
    same contract stated twice; so is the int8 transport crossover."""
    assert hierarchical.CODEC_FACTORS == HIER_CODEC_FACTORS
    from autodist_tpu.kernel.synchronization.compressor import _INT8_MAX_AXIS
    from autodist_tpu.tuner import cost_model as cost_model_mod
    assert _INT8_MAX_AXIS == cost_model_mod._INT8_MAX_AXIS


def test_dcn_ratio_targets():
    """The headline compression targets: at d=4 x h=2 the hierarchical
    DCN leg carries <= 0.51x the flat f32 ring's DCN share under bf16
    and <= 0.26x under int8(+EF), with the ICI leg at full precision."""
    topo = Topology(8, num_hosts=2)
    nbytes = 1 << 20
    flat = topo.flat_wire_split(2.0 * nbytes, 8)
    for codec, ceiling in (("bf16", 0.51), ("int8", 0.26),
                           ("int8ef", 0.26)):
        split = topo.hier_wire_split(nbytes, 8, codec)
        assert split["dcn"] / flat["dcn"] <= ceiling, codec
        assert split["ici"] == pytest.approx(flat["ici"])


def test_int8_transport_resolves_per_leg_group_size(monkeypatch):
    """Satellite regression: the int8 axis-size crossover must consult
    the LIVE group size of the leg the collective runs on, not the
    global axis size.  With asymmetric legs (wide axis, narrow DCN leg)
    the decisions differ — and forcing the ring transport through
    ``group_size`` on a narrow axis must still compute the right mean."""
    assert compressor_mod.int8_transport(2) == "allgather"
    assert compressor_mod.int8_transport(8) == "allgather"
    assert compressor_mod.int8_transport(9) == "ring"
    # A 16-wide flat axis would pick the ring; its h=2 DCN leg must not.
    assert compressor_mod.int8_transport(16) != \
        compressor_mod.int8_transport(2)

    grads = _grads(seed=1)
    ref = grads.mean(axis=0)
    n = grads.shape[1]
    mesh = Mesh(np.array(jax.devices()), (const.MESH_AXIS_DATA,))

    def f(g):
        # group_size=9 forces the ring transport on this 8-wide axis —
        # the decision must follow the passed leg size, and the ring
        # must still produce the mean.
        return compressor_mod.mean_int8_wire(
            g.reshape(n), const.MESH_AXIS_DATA, group_size=9)

    out = np.asarray(jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P(const.MESH_AXIS_DATA),
        out_specs=P(None), check_vma=False))(grads.reshape(-1)))
    assert np.abs(out - ref).max() <= 2e-2


# -- cost model --------------------------------------------------------------


def test_hier_ar_cost_degenerates_exactly_to_flat():
    nbytes = 8 << 20
    single = Topology(8, num_hosts=1)
    assert single.hierarchical_ar_cost(nbytes, 8, 0.5) == \
        pytest.approx(single.all_reduce_cost(nbytes, 8))
    multi = Topology(64, num_hosts=8)
    assert multi.hierarchical_ar_cost(nbytes, 64, 1.0) == \
        pytest.approx(multi.all_reduce_cost(nbytes, 64))


def test_hier_ar_cost_monotonic():
    topo = Topology(64, num_hosts=8)
    nbytes = 8 << 20
    # Decreasing in DCN compression; increasing in payload.
    assert topo.hierarchical_ar_cost(nbytes, 64, 0.25) < \
        topo.hierarchical_ar_cost(nbytes, 64, 0.5) < \
        topo.hierarchical_ar_cost(nbytes, 64, 1.0)
    assert topo.hierarchical_ar_cost(2 * nbytes, 64, 0.5) > \
        topo.hierarchical_ar_cost(nbytes, 64, 0.5)
    # A compressed DCN leg strictly beats the flat f32 ring cross-host.
    assert topo.hierarchical_ar_cost(nbytes, 64, 0.5) < \
        topo.all_reduce_cost(nbytes, 64)
    # More hosts at the same world size move bytes onto the slower leg:
    # the price never drops.
    costs = [Topology(64, num_hosts=h).hierarchical_ar_cost(nbytes, 64, 0.5)
             for h in (1, 2, 4, 8)]
    assert all(a <= b for a, b in zip(costs, costs[1:]))


# -- tuner integration -------------------------------------------------------


def _pod_spec(tmp_path, num_hosts=8, chips_per_host=8, interconnect=None):
    lines = ["tpu:", "  accelerator: v5e-64",
             f"  num_hosts: {num_hosts}",
             f"  chips_per_host: {chips_per_host}"]
    if interconnect:
        lines.append("interconnect:")
        for k, v in interconnect.items():
            lines.append(f"  {k}: {v}")
    path = tmp_path / "spec.yml"
    path.write_text("\n".join(lines) + "\n")
    return ResourceSpec(str(path))


def _metadata_item():
    return GraphItem(loss_fn=None, params=None, optimizer=None,
                     variables=[VariableItem("w", (4096, 4096), jnp.float32),
                                VariableItem("b", (4096,), jnp.float32)])


def test_golden_slow_dcn_many_hosts_picks_hierarchical(tmp_path):
    """Bandwidth-starved DCN on 8 hosts: the winning candidate carries a
    ``+hier=`` exec variant — the DCN codec baked into the strategy
    artifact (spec DCN + codec compressor) so the runner executes the
    priced two-level plan."""
    spec = _pod_spec(tmp_path, interconnect={"dcn_gbps": 1, "dcn_us": 200})
    item = _metadata_item()
    result = tuner.search(item, spec, calibration=Calibration(
        path=str(tmp_path / "cal.json")))
    knobs = result.chosen["knobs"]
    assert knobs.get("hier_dcn_codec") in ("bf16", "int8", "int8ef")
    assert result.chosen["breakdown"].get("hier_codec") == \
        knobs["hier_dcn_codec"]
    from autodist_tpu.proto import strategy_pb2
    S = strategy_pb2.AllReduceSynchronizer
    specs = {node.all_reduce_synchronizer.spec
             for node in result.chosen_strategy.node_config
             if node.WhichOneof("synchronizer") in (
                 "all_reduce_synchronizer", None)}
    assert S.Spec.DCN in specs


def test_single_host_never_picks_hierarchical(tmp_path):
    """Single host: there is no second level.  The variant generator
    returns nothing, and no ranked candidate carries a hier knob."""
    spec = _pod_spec(tmp_path, num_hosts=1, chips_per_host=8)
    assert hier_exec_variants(Topology(8, num_hosts=1)) == ()
    item = _metadata_item()
    result = tuner.search(item, spec, calibration=Calibration(
        path=str(tmp_path / "cal.json")))
    for row in result.ranked:
        assert "hier_dcn_codec" not in row["knobs"]
        assert not row["breakdown"].get("hier_codec")


def test_hier_variants_env_gates(monkeypatch):
    topo = Topology(64, num_hosts=8)
    assert len(hier_exec_variants(topo)) == 3
    monkeypatch.setenv("AUTODIST_HIER_DCN_CODEC", "int8")
    variants = hier_exec_variants(topo)
    assert len(variants) == 1 and variants[0][1]["hier"] == "int8"
    monkeypatch.setenv("AUTODIST_HIER_DCN_CODEC", "")
    monkeypatch.setenv("AUTODIST_HIER_COLLECTIVES", "off")
    assert hier_exec_variants(topo) == ()


def test_strategy_memory_prices_sharded_ef_state(tmp_path):
    """The hierarchical EF residual is a DCN shard (1/d of the
    gradient), not a full copy: ``strategy_memory`` must price it
    smaller than the flat EF state."""
    from autodist_tpu.strategy import AllReduce
    spec = _pod_spec(tmp_path, num_hosts=8, chips_per_host=8)
    item = _metadata_item()
    model = CostModel(Topology(64, num_hosts=8))
    flat = AllReduce(compressor="Int8CompressorEF").build(item, spec)
    hier = AllReduce(all_reduce_spec="DCN",
                     compressor="Int8CompressorEF").build(item, spec)
    mem_flat = model.strategy_memory(flat, item)
    mem_hier = model.strategy_memory(hier, item)
    assert mem_hier["sync_state_bytes"] < mem_flat["sync_state_bytes"]


def test_program_wire_split_skips_partitioned_vars(monkeypatch):
    """Gauge accounting counts dense all-reduces only: sharded-state
    vars move RS/AG wire priced elsewhere, and a var absent from the
    size map contributes nothing."""
    monkeypatch.setenv("AUTODIST_HIER_ICI", "4")

    class _Sync:
        def __init__(self, active=False, codec=None):
            self.compressor_kind = 0  # NoneCompressor
            self.hier_codec = codec
            self.devices_per_host = 4
            self.pconfig = type("P", (), {"active": active})()

    split = hierarchical.program_wire_split(
        {"dense": _Sync(), "sharded": _Sync(active=True),
         "hier": _Sync(codec="bf16")},
        {"dense": 1024.0, "sharded": 1 << 30, "hier": 1024.0}, 8)
    flat = Topology(8, num_hosts=2).flat_wire_split(2.0 * 1024.0, 8)
    hier = Topology(8, num_hosts=2).hier_wire_split(1024.0, 8, "bf16")
    assert split["ici"] == pytest.approx(flat["ici"] + hier["ici"])
    assert split["dcn"] == pytest.approx(flat["dcn"] + hier["dcn"])
