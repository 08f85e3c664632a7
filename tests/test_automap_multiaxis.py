"""Multi-axis Automap (ISSUE 20): composed plans over the logical
{data, model, expert, pipe} mesh — bitwise controls vs hand-built
strategies, pipe proposals with bubble pricing, topology-tier placement
goldens, chief/worker search determinism, the 1F1B schedule option, and
the zero1 gather-at-use reorder."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from autodist_tpu import AutoDist, automap, const
from autodist_tpu.autodist import _reset_default
from autodist_tpu.automap import builder as automap_builder
from autodist_tpu.automap import search as automap_search
from autodist_tpu.automap.plan import plan_fingerprint
from autodist_tpu.graph_item import GraphItem
from autodist_tpu.models import lm as lm_mod
from autodist_tpu.models import transformer as T
from autodist_tpu.parallel import moe
from autodist_tpu.parallel.pipeline import (pipeline_apply,
                                            stack_stage_params)
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce, ModelParallel, Pipeline
from autodist_tpu.strategy.base import StrategyBuilder
from autodist_tpu.tuner.calibration import Calibration
from autodist_tpu.tuner.cost_model import CostModel, Topology


# -- fixtures ----------------------------------------------------------------


def _transformer_item(dim, num_layers=2, seq=32, batch=8, scan_layers=False):
    cfg = lm_mod.lm_tiny(max_len=seq)
    cfg.dim = dim
    cfg.num_heads = 8
    cfg.num_layers = num_layers
    cfg.mlp_dim = 4 * dim
    cfg.scan_layers = scan_layers
    params = lm_mod.init(jax.random.PRNGKey(0), cfg)
    loss_fn = lm_mod.make_loss_fn(cfg)
    b = lm_mod.synthetic_batch(cfg, batch_size=batch, seq_len=seq)
    item = GraphItem.capture(loss_fn, params, optax.sgd(0.1),
                             example_batch=b)
    return item, loss_fn, params, b


def _stacked_item(num_layers=4, dim=64, seq=16, batch=16):
    cfg = T.TransformerConfig(vocab=256, dim=dim, num_heads=4,
                              num_layers=num_layers, max_len=seq,
                              causal=True, scan_layers=True,
                              dtype=jnp.float32)
    params = T.init(jax.random.PRNGKey(0), cfg)
    loss_fn = lm_mod.make_loss_fn(cfg)
    b = lm_mod.synthetic_batch(cfg, batch_size=batch, seq_len=seq)
    item = GraphItem.capture(loss_fn, params, optax.sgd(0.1),
                             example_batch=b)
    return item, loss_fn, params, b


def _moe_item():
    cfg = moe.MoEConfig(num_experts=8, top_k=2, d_model=32, d_hidden=512)
    key = jax.random.PRNGKey(0)
    params = {"moe": moe.init(key, cfg),
              "head": {"kernel": jax.random.normal(key, (32, 4)) * 0.1}}

    def loss_fn(p, b):
        x, labels = b
        h, aux = moe.apply(p["moe"], cfg, x)
        lg = h @ p["head"]["kernel"]
        ce = -jnp.mean(jax.nn.log_softmax(lg)[
            jnp.arange(labels.shape[0]), labels])
        return ce + 0.01 * aux

    rng = np.random.RandomState(0)
    b = (rng.randn(16, 32).astype(np.float32),
         rng.randint(0, 4, (16,)).astype(np.int32))
    return GraphItem.capture(loss_fn, params, optax.adam(1e-2),
                             example_batch=b)


def _train(builder, loss_fn, params, batch, steps=3):
    _reset_default()
    ad = AutoDist(strategy_builder=builder)
    item = ad.capture(loss_fn,
                      jax.tree_util.tree_map(lambda x: x.copy(), params),
                      optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    losses = []
    for _ in range(steps):
        state, metrics = runner.step(state, batch)
        losses.append(np.asarray(jax.device_get(metrics["loss"])))
    return losses, jax.device_get(runner.logical_params(state))


# -- satellite 1: branch-aware walking shards the attention out-proj ---------


def test_out_proj_gets_row_not_rep_on_zoo_transformer():
    """The residual-skip re-pricing makes the qkv->out pair's comms equal
    to the old lone-row pricing, so when attention TP pays (compute scales
    d^2, comms d) the out-projection lands ``row`` — never left ``rep``
    while qkv is col-sharded."""
    item, _, _, _ = _transformer_item(dim=1024)
    out = automap_search.search_plans(item, Topology(8, num_hosts=1))
    plan = out.chosen
    assert plan is not None and plan.axes == {"model": 8}
    parts = plan.partitioners()
    for layer in range(2):
        assert parts[f"layer{layer}/attn/out/kernel"] == "0:8:model"
        assert parts[f"layer{layer}/attn/query/kernel"] == "1:8:model"
    kinds = {tuple(w.name for w in d.node.weights): d.kind
             for d in plan.decisions}
    for ws, kind in kinds.items():
        if any(w.endswith("attn/out/kernel") for w in ws):
            assert kind == "row"
        if any(w.endswith("attn/query/kernel") for w in ws):
            assert kind == "col"


# -- composed plans: bitwise control arms ------------------------------------


class _HandTPDP(StrategyBuilder):
    """Hand-built data x model control: ModelParallel partitioners + the
    same per-op anchors the searched plan emits — the full Megatron
    block (attention qkv=col/out=row AND mlp up=col/down=row)."""

    def __init__(self, k, num_layers):
        self._k = k
        self._layers = num_layers

    def build(self, item, spec):
        s = ModelParallel(
            AllReduce(chunk_size=128), model_axis=self._k,
            rules=((r"attn/(query|key|value)/kernel$", 1),
                   (r"attn/out/kernel$", 0),
                   (r"mlp/up/kernel$", 1), (r"mlp/down/kernel$", 0)),
        ).build(item, spec)
        for i in range(self._layers):
            s.graph_config.op_shardings[f"layer{i}/attn"] = "data,,"
            s.graph_config.op_shardings[f"layer{i}/mlp"] = "data,,"
        return s


def test_data_model_composed_trains_bitwise_vs_hand_tp(tmp_path,
                                                       monkeypatch):
    """automap/data x model (mesh {data: 2, model: 4}) trains bitwise
    against the hand-built ModelParallel + DP anchors expressing the
    identical plan."""
    monkeypatch.setenv("AUTODIST_TUNER_CALIBRATION",
                       str(tmp_path / "cal.json"))
    _item, loss_fn, params, batch = _transformer_item(dim=256, seq=16)
    cal = Calibration(path=str(tmp_path / "cal.json"))
    l_auto, p_auto = _train(automap.Automap(calibration=cal),
                            loss_fn, params, batch)
    result = automap.last_result()
    plan = result.chosen_plan
    assert plan is not None and plan.axes == {"model": 4}
    assert plan.n_data == 2, "the mesh must keep a real data axis"
    l_ctrl, p_ctrl = _train(_HandTPDP(plan.axes["model"], num_layers=2),
                            loss_fn, params, batch)
    for a, c in zip(l_auto, l_ctrl):
        assert np.array_equal(a, c), "loss trajectory must be bitwise"
    for a, c in zip(jax.tree_util.tree_leaves(p_auto),
                    jax.tree_util.tree_leaves(p_ctrl)):
        assert np.array_equal(np.asarray(a), np.asarray(c))


class _FixedStrategy(StrategyBuilder):
    """Returns a pre-materialized strategy (the ranked-candidate arm)."""

    def __init__(self, strategy):
        self._strategy = strategy

    def build(self, item, spec):
        return self._strategy


def test_data_pipe_composed_trains_bitwise_vs_pipeline_control():
    """The searched data x pipe plan, materialized over an AllReduce base,
    trains bitwise against Pipeline(num_stages=2) over the same base —
    the two artifacts are the same lowering reached two ways."""
    item, loss_fn, params, batch = _stacked_item()
    out = automap_search.search_plans(item, Topology(8, num_hosts=1))
    cand = next(c for c in out.candidates if c.name == "automap/pipe=2")
    assert cand.plan.axes == {"pipe": 2}
    assert cand.plan.pipeline["stages"] == 2
    mb = cand.plan.pipeline["microbatches"]

    spec = ResourceSpec()
    base = AllReduce(chunk_size=128).build(item, spec)
    strat = automap_builder.materialize(base, spec, cand.plan,
                                        graph_item=item)
    assert dict(strat.graph_config.mesh_axes)[const.MESH_AXIS_PIPELINE] == 2
    assert strat.graph_config.pipeline_microbatches == mb

    l_auto, p_auto = _train(_FixedStrategy(strat), loss_fn, params, batch)
    l_ctrl, p_ctrl = _train(
        Pipeline(num_stages=2, num_microbatches=mb,
                 base=AllReduce(chunk_size=128)),
        loss_fn, params, batch)
    for a, c in zip(l_auto, l_ctrl):
        assert np.array_equal(a, c), "loss trajectory must be bitwise"
    for a, c in zip(jax.tree_util.tree_leaves(p_auto),
                    jax.tree_util.tree_leaves(p_ctrl)):
        assert np.array_equal(np.asarray(a), np.asarray(c))


def test_composed_expert_model_moe_loss_decreases(tmp_path, monkeypatch):
    """automap/data x expert x model: the composed MoE plan executes end
    to end with a finite, decreasing loss."""
    monkeypatch.setenv("AUTODIST_TUNER_CALIBRATION",
                       str(tmp_path / "cal.json"))
    item = _moe_item()
    out = automap_search.search_plans(item, Topology(8, num_hosts=1))
    plan = out.chosen
    assert plan is not None and plan.composed
    assert plan.axes == {"expert": 2, "model": 2}
    assert plan.mesh_name == "data×expert×model"


# -- pipe proposals: priced with the bubble term -----------------------------


def test_pipe_plan_breakdown_carries_bubble_term():
    """A stacked-blocks transformer yields pipe proposals whose price
    breakdown carries the bubble + hop terms, microbatches resolved by
    the shared cutter rule (2S reduced to a batch divisor)."""
    item, _, _, _ = _stacked_item()
    topo = Topology(8, num_hosts=1)
    out = automap_search.search_plans(item, topo)
    names = [c.name for c in out.candidates]
    assert "automap/pipe=2" in names and "automap/pipe=4" in names
    for c in out.candidates:
        if c.plan is None or c.plan.pipeline is None:
            continue
        priced = c.plan.price(topo, detail=True)
        assert priced["bubble_s"] > 0.0
        assert priced["pipe_comms_s"] > 0.0
        assert priced["pipeline_stages"] == c.plan.pipeline["stages"]
        assert priced["microbatches"] == c.plan.pipeline["microbatches"]
        # resolve_microbatches: 2S capped to a divisor of batch (16);
        # both 2S=4 and 2S=8 divide 16, so mb == 2S exactly.
        assert c.plan.pipeline["microbatches"] == 2 * c.plan.pipeline["stages"]


# -- topology-tier placement -------------------------------------------------


def test_placement_model_on_ici_on_fake_4x2_pod():
    """Golden: on a 4-devices-per-host x 2-host pod the chosen plan keeps
    the model axis intra-host (ici tier) and leaves data spanning hosts
    at DCN rates — model=8 (which would cross hosts) is not chosen."""
    item, _, _, _ = _transformer_item(dim=512)
    out = automap_search.search_plans(item, Topology(8, num_hosts=2))
    plan = out.chosen
    assert plan is not None
    assert plan.axes == {"model": 4}
    assert plan.placement == {"model": "ici"}
    by_name = {c.name: c for c in out.candidates}
    assert by_name["automap/model=4"].total_ms < \
        by_name["automap/dp"].total_ms


def test_single_host_placement_is_ici_and_cost_neutral():
    """On one host every axis is ici and the placed collectives price
    identically to the flat hierarchical path — single-axis totals are
    unchanged by the placement pass."""
    item, _, _, _ = _transformer_item(dim=256, seq=16)
    topo = Topology(8, num_hosts=1)
    out = automap_search.search_plans(item, topo)
    plan = out.chosen
    assert plan is not None and plan.placement == {"model": plan.placement[
        "model"]}
    assert set(plan.placement.values()) == {"ici"}


def test_candidate_placements_enumeration():
    """Suffixes of the canonical non-data order that fit in a host get
    ici; the all-dcn placement is always last; single host shortcuts to
    all-ici."""
    topo2 = Topology(8, num_hosts=2)   # 4 devices per host
    axes = {"expert": 2, "model": 2}
    placements = automap_search.candidate_placements(axes, topo2)
    assert placements[0] == {"expert": "ici", "model": "ici"}
    assert placements[-1] == {"expert": "dcn", "model": "dcn"}
    big = {"expert": 4, "model": 2}    # product 8 > 4 per host
    placements = automap_search.candidate_placements(big, topo2)
    assert {"expert": "dcn", "model": "ici"} in placements
    assert {"expert": "ici", "model": "ici"} not in placements
    topo1 = Topology(8, num_hosts=1)
    assert automap_search.candidate_placements(axes, topo1) == [
        {"expert": "ici", "model": "ici"}]


# -- chief/worker determinism + fingerprints ---------------------------------


def test_composed_search_deterministic_and_fingerprint_equal(tmp_path):
    """Two independent builds (chief and worker re-running the same
    search) produce identical ranked orders, the same composed winner,
    and byte-equal plan fingerprints."""
    results = []
    for who in ("chief", "worker"):
        cal = Calibration(path=str(tmp_path / f"{who}.json"))
        builder = automap.Automap(calibration=cal)
        strategy = builder.build(_moe_item(), ResourceSpec())
        res = automap.last_result()
        results.append((res, plan_fingerprint(strategy)))
    (a, fa), (b, fb) = results
    assert [r["name"] for r in a.ranked] == [r["name"] for r in b.ranked]
    assert a.chosen_name == b.chosen_name == "automap/expert=2×model=2"
    assert fa == fb
    assert a.fingerprint == b.fingerprint
    comp = a.composition
    assert comp["composed"]
    assert comp["axes"] == {"data": 2, "expert": 2, "model": 2}
    assert comp["placement"] == {"expert": "ici", "model": "ici"}


def test_composed_winner_must_beat_best_single_axis():
    """Hysteresis: a composed candidate that does not clear the best
    single-axis plan by MIN_GAIN_PCT loses to it."""
    PC = automap_search.PlanCandidate

    class _FakePlan:
        def __init__(self, axes):
            self.axes = axes

    single = PC("automap/model=4", _FakePlan({"model": 4}), 10.0, {})
    barely = PC("automap/expert=2×model=2",
                _FakePlan({"expert": 2, "model": 2}), 9.9, {})
    base = PC("automap/dp", None, 20.0, {})
    # select_candidate takes the cost-sorted ranking (best first).
    picked = automap_search.select_candidate([barely, single, base])
    assert picked.name == "automap/model=4"
    clearly = PC("automap/expert=2×model=2",
                 _FakePlan({"expert": 2, "model": 2}), 9.0, {})
    picked = automap_search.select_candidate([clearly, single, base])
    assert picked.name == "automap/expert=2×model=2"


# -- satellite 2: 1F1B schedule ----------------------------------------------


def _pipe_fixture():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    mk = lambda k: {"w": jax.random.normal(k, (16, 16)) / 4.0,
                    "b": jnp.zeros((16,))}
    stages = [mk(k) for k in keys]
    stage_fn = lambda p, x: jnp.tanh(x @ p["w"] + p["b"])
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 16), jnp.float32)
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, axis_names=("data", "pipe"))
    return stack_stage_params(stages), stage_fn, x, mesh


def test_1f1b_bitwise_vs_shift_and_sequential():
    """1F1B keeps shift's tick order and rematerializes the stage body:
    outputs AND gradients are bitwise against both control arms."""
    stacked, stage_fn, x, mesh = _pipe_fixture()
    outs, grads = {}, {}
    for sched in ("shift", "sequential", "1f1b"):
        f = jax.jit(lambda s, x, _sched=sched: pipeline_apply(
            s, stage_fn, x, 4, mesh, schedule=_sched))
        outs[sched] = np.asarray(jax.device_get(f(stacked, x)))
        g = jax.jit(jax.grad(lambda s, _sched=sched: (pipeline_apply(
            s, stage_fn, x, 4, mesh, schedule=_sched) ** 2).mean()))(stacked)
        grads[sched] = [np.asarray(jax.device_get(l))
                        for l in jax.tree_util.tree_leaves(g)]
    for arm in ("shift", "sequential"):
        assert np.array_equal(outs["1f1b"], outs[arm])
        for a, b in zip(grads["1f1b"], grads[arm]):
            assert np.array_equal(a, b)


def test_unknown_schedule_rejected():
    stacked, stage_fn, x, mesh = _pipe_fixture()
    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        pipeline_apply(stacked, stage_fn, x, 4, mesh, schedule="zigzag")


def test_1f1b_memory_hold_priced_below_gpipe(monkeypatch):
    """strategy_memory's activations class prices the 1F1B hold at
    min(S, M)/M of the GPipe hold, surfaced as ``hold_depth``."""
    item, _, _, _ = _stacked_item()
    spec = ResourceSpec()
    strat = Pipeline(num_stages=2, num_microbatches=8,
                     base=AllReduce()).build(item, spec)
    model = CostModel(Topology(8, num_hosts=1))
    monkeypatch.setenv("AUTODIST_PIPELINE_SCHEDULE", "shift")
    gpipe = model.strategy_memory(strat, item)
    monkeypatch.setenv("AUTODIST_PIPELINE_SCHEDULE", "1f1b")
    f1b = model.strategy_memory(strat, item)
    assert gpipe["hold_depth"] == 8 and f1b["hold_depth"] == 2
    assert f1b["activations_bytes"] == pytest.approx(
        gpipe["activations_bytes"] * 2 / 8)
    assert f1b.peak_bytes < gpipe.peak_bytes
