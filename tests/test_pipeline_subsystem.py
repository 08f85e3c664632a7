"""ISSUE 14: the pipeline subsystem — stage cutter, bitwise schedule
contract, cost-model bubble term, tuner ranking, observability closure,
and the StepGuard/checkpoint contracts under the pipelined path.

The acceptance pin: a zoo transformer trained under
``Pipeline(stages=2, microbatches=4)`` on the forced 8-device CPU mesh is
BITWISE-equal (params + loss trajectory) to the unpipelined control arm —
the ``sequential`` schedule, which runs the same stage placement with one
microbatch in flight, isolating exactly the schedule overlap.
"""
import itertools

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist, const, observability
from autodist_tpu.autodist import _reset_default
from autodist_tpu.models import lm as lm_mod
from autodist_tpu.ops import scan_blocks
from autodist_tpu.pipeline import cutter, observe
from autodist_tpu.resilience import StepGuard
from autodist_tpu.strategy import AllReduce, Pipeline


# ---------------------------------------------------------------------------
# fixtures


def _zoo_lm(num_layers=4, batch_size=16, seq=16):
    cfg = lm_mod.lm_tiny(max_len=seq)
    cfg.num_layers = num_layers
    cfg.scan_layers = True
    params = lm_mod.init(jax.random.PRNGKey(0), cfg)
    loss_fn = lm_mod.make_loss_fn(cfg)
    batches = [lm_mod.synthetic_batch(cfg, batch_size=batch_size,
                                      seq_len=seq, seed=s)
               for s in range(6)]
    return params, loss_fn, batches


def _stacked_float_model(dim=16, n_layers=4, batch=16, n_batches=10, seed=0):
    """inproj -> scan_blocks stack -> head, float inputs (chaos-poisonable)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), n_layers + 2)
    params = {
        "inproj": {"kernel": jax.random.normal(keys[0], (8, dim)) * 0.3},
        "blocks": {
            "w": jnp.stack([jax.random.normal(k, (dim, dim)) / np.sqrt(dim)
                            for k in keys[1:1 + n_layers]]),
            "b": jnp.zeros((n_layers, dim))},
        "head": {"kernel": jax.random.normal(keys[-1], (dim, 4)) * 0.3},
    }

    def loss_fn(p, b):
        x, labels = b
        h = x @ p["inproj"]["kernel"]
        h = scan_blocks(p["blocks"],
                        lambda bp, a: jnp.tanh(a @ bp["w"] + bp["b"]), h)
        logits = h @ p["head"]["kernel"]
        return -jnp.mean(jax.nn.log_softmax(logits)[
            jnp.arange(labels.shape[0]), labels])

    rng = np.random.RandomState(1)
    batches = [(rng.randn(batch, 8).astype(np.float32),
                rng.randint(0, 4, (batch,)).astype(np.int32))
               for _ in range(n_batches)]
    return params, loss_fn, batches


def _train(builder, params, loss_fn, batches, schedule=None,
           monkeypatch=None, steps=None):
    if schedule is not None:
        monkeypatch.setenv("AUTODIST_PIPELINE_SCHEDULE", schedule)
    _reset_default()
    ad = AutoDist(strategy_builder=builder)
    item = ad.capture(loss_fn, params, optax.adam(1e-2),
                      example_batch=batches[0])
    runner = ad.create_distributed_session(item)
    if schedule is not None and isinstance(builder, Pipeline):
        # The context reads AUTODIST_PIPELINE_SCHEDULE lazily: pin it
        # here so this arm provably runs the requested schedule (a
        # lazy-env leak would make the bitwise comparison vacuous).
        assert runner.program.parallel_context().pipeline_schedule == \
            schedule
    state = runner.create_state()
    losses = []
    for b in batches[:steps or len(batches)]:
        state, m = runner.step(state, b)
        losses.append(float(jax.device_get(m["loss"])))
    flat = jax.tree_util.tree_flatten_with_path(
        runner.logical_params(state))[0]
    return losses, {jax.tree_util.keystr(p): np.asarray(jax.device_get(l))
                    for p, l in flat}


# ---------------------------------------------------------------------------
# acceptance: bitwise schedule contract on the zoo transformer


def test_zoo_transformer_pipeline_bitwise_vs_unpipelined(monkeypatch):
    """Pipeline(stages=2, microbatches=4) on the 8-device mesh: the
    shifting schedule's params AND per-step loss trajectory are BITWISE
    equal to the unpipelined (sequential-schedule) control arm — the
    numerics contract that pipelining changes when work runs, never what
    is computed."""
    params, loss_fn, batches = _zoo_lm()
    mk = lambda: Pipeline(num_stages=2, num_microbatches=4)
    l_pipe, p_pipe = _train(mk(), params, loss_fn, batches,
                            schedule="shift", monkeypatch=monkeypatch,
                            steps=4)
    l_seq, p_seq = _train(mk(), params, loss_fn, batches,
                          schedule="sequential", monkeypatch=monkeypatch,
                          steps=4)
    assert l_pipe == l_seq, f"loss trajectory diverged: {l_pipe} vs {l_seq}"
    for k, want in p_seq.items():
        np.testing.assert_array_equal(p_pipe[k], want,
                                      err_msg=f"param {k} not bitwise")
    # And the pipelined arm tracks the plain-DP arm numerically (the
    # data-axis reduction grouping differs, so this one is tolerance).
    l_dp, _ = _train(AllReduce(), params, loss_fn, batches,
                     schedule="shift", monkeypatch=monkeypatch, steps=4)
    np.testing.assert_allclose(l_pipe, l_dp, rtol=2e-4)


# ---------------------------------------------------------------------------
# stage cutter


def _indexed_layer_model():
    """Three indexed layer scopes + a scope-less equation between them +
    an unscoped prelude (the satellite's regression shape)."""
    params = {"layer0": {"w": jnp.ones((8, 8))},
              "mid": jnp.ones((8, 8)),
              "layer1": {"w": jnp.ones((8, 32))},
              "layer2": {"w": jnp.ones((32, 8))},
              "pre": jnp.ones((8, 8))}

    def loss_fn(p, b):
        x = b @ p["pre"]  # unscoped prelude -> charged to the first stage
        with jax.named_scope("layer0"):
            x = jnp.tanh(x @ p["layer0"]["w"])
        x = x @ p["mid"]  # scope-less -> nearest enclosing stage (layer0's)
        with jax.named_scope("layer1"):
            x = jnp.tanh(x @ p["layer1"]["w"])
        with jax.named_scope("layer2"):
            x = jnp.tanh(x @ p["layer2"]["w"])
        return jnp.mean(x ** 2)

    batch = jnp.ones((4, 8))
    _reset_default()
    ad = AutoDist(strategy_builder=AllReduce())
    return ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)


def test_cutter_rolls_unattributed_into_nearest_stage():
    """Satellite: scope-less equations are charged to their nearest
    enclosing stage, never dropped — per-stage FLOPs sum EXACTLY to
    flops_estimate() on a model with scope-less eqns."""
    item = _indexed_layer_model()
    cut = cutter.cut_stages(item, 2)
    total = sum(s["flops"] for s in cut.stages)
    assert total == item.flops_estimate(), \
        f"stage balance {total} != flops_estimate {item.flops_estimate()}"
    assert cut.num_stages == 2
    # The heavy pair (layer1 8x32 + layer2 32x8) outweighs layer0: the
    # balanced cut isolates layer0 (plus the rolled-up scope-less costs)
    # from the wide layers.
    assert cut.stages[0]["scopes"][-1] == "layer0" or \
        "layer0" in cut.stages[0]["scopes"]
    # The prelude matmul and the mid matmul both landed somewhere.
    per_layer_only = 0.0
    for rec in item.op_provenance():
        per_layer_only += rec["flops"] if rec["scope"] else 0.0
    assert total > per_layer_only, "scope-less flops were dropped"


def test_cutter_deterministic_and_balanced():
    """Chief/worker determinism: the same program cut twice (and cut
    from a fresh capture) yields identical boundaries — the
    (rounded-cost, boundaries) tie-break contract."""
    item = _indexed_layer_model()
    a = cutter.cut_stages(item, 2).to_json()
    b = cutter.cut_stages(item, 2).to_json()
    c = cutter.cut_stages(_indexed_layer_model(), 2).to_json()
    assert a == b == c
    cut3 = cutter.cut_stages(item, 3)
    assert [tuple(s["scopes"]) for s in cut3.stages] == \
        [tuple(s["scopes"]) for s in cutter.cut_stages(item, 3).stages]


def test_cutter_stacked_blocks_layout():
    """The scan_blocks layout: the single ``blocks`` scope expands into
    L homologous layers; L % S == 0 cuts are perfectly balanced."""
    params, loss_fn, batches = _zoo_lm()
    _reset_default()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1),
                      example_batch=batches[0])
    cut = cutter.cut_stages(item, 2)
    assert cut.num_layers == 4 and cut.num_stages == 2
    assert cut.imbalance == 0.0  # homogeneous layers, even split
    assert any("blocks[" in s for st in cut.stages for s in st["scopes"])


def test_resolve_stages_precedence(monkeypatch):
    params, loss_fn, batches = _zoo_lm()
    _reset_default()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1),
                      example_batch=batches[0])
    spec = ad.cluster.resource_spec
    monkeypatch.setenv("AUTODIST_PIPELINE_STAGES", "2")
    assert cutter.resolve_stages(item, spec) == (2, "env")
    monkeypatch.delenv("AUTODIST_PIPELINE_STAGES")
    k, source = cutter.resolve_stages(item, spec)
    assert source == "auto" and k > 1 and 4 % k == 0
    assert cutter.resolve_stages(item, spec, explicit=4) == (4, "explicit")


def test_pipeline_builder_defaults_and_event(monkeypatch):
    """Pipeline() with no args resolves S from the env knob, picks
    M = AUTODIST_MICROBATCHES (clamped to a batch divisor when
    defaulted), and records the ``pipeline`` flight event."""
    monkeypatch.setenv("AUTODIST_PIPELINE_STAGES", "2")
    monkeypatch.setenv("AUTODIST_MICROBATCHES", "4")
    params, loss_fn, batches = _zoo_lm()
    _reset_default()
    observability.recorder.clear()
    ad = AutoDist(strategy_builder=Pipeline())
    item = ad.capture(loss_fn, params, optax.sgd(0.1),
                      example_batch=batches[0])
    s = ad.build_strategy(item)
    assert dict(s.graph_config.mesh_axes) == {"data": 4, "pipe": 2}
    assert s.graph_config.pipeline_microbatches == 4
    kinds = [e["kind"] for e in observability.recorder.events()]
    assert "pipeline" in kinds
    cut = cutter.last_cut()
    assert cut is not None and cut.num_stages == 2 and cut.source == "env"


# ---------------------------------------------------------------------------
# cost model + tuner ranking


def test_cost_model_bubble_term_and_microbatch_knob():
    """More microbatches => smaller bubble => cheaper; imbalance and
    bubble_ms land in the breakdown."""
    from autodist_tpu.tuner.cost_model import CostModel, Topology
    params, loss_fn, batches = _zoo_lm()
    _reset_default()
    ad = AutoDist(strategy_builder=Pipeline(num_stages=2,
                                            num_microbatches=4))
    item = ad.capture(loss_fn, params, optax.sgd(0.1),
                      example_batch=batches[0])
    strategy = ad.build_strategy(item)
    model = CostModel(Topology(num_devices=8))
    bd4 = model.strategy_cost(strategy, item)
    bd8 = model.strategy_cost(strategy, item, microbatches=8)
    assert bd4["microbatches"] == 4 and bd8["microbatches"] == 8
    assert bd8["bubble_ms"] < bd4["bubble_ms"]
    assert bd8["compute_ms"] < bd4["compute_ms"]
    assert bd4["pipeline_stages"] == 2
    assert bd4["bubble_ms"] > 0
    # A knob that does not divide the captured batch (16) is not priced:
    # it falls back to the artifact's count (the runtime would raise).
    bd5 = model.strategy_cost(strategy, item, microbatches=5)
    assert bd5["microbatches"] == 4
    # Unpipelined strategies are unaffected by the knob (no-op variant).
    _reset_default()
    ad2 = AutoDist(strategy_builder=AllReduce())
    item2 = ad2.capture(loss_fn, params, optax.sgd(0.1),
                        example_batch=batches[0])
    s2 = ad2.build_strategy(item2)
    assert model.strategy_cost(s2, item2, microbatches=8).total_ms == \
        model.strategy_cost(s2, item2).total_ms


def test_pipeline_family_ranked_and_microbatch_exec_knob(monkeypatch):
    """Satellite: the Pipeline family is enumerated under auto for a
    stacked-blocks model even with no mesh hint (cutter-proposed S), the
    winning microbatch exec knob lands in the knobs AND the strategy
    artifact, and repeated searches agree ((rounded-cost, name)
    determinism)."""
    from autodist_tpu.tuner.search import enumerate_candidates
    from autodist_tpu.tuner.search import search as run_search
    params, loss_fn, batches = _zoo_lm()
    _reset_default()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1),
                      example_batch=batches[0])
    spec = ad.cluster.resource_spec
    cands, _space = enumerate_candidates(item, spec)
    pipe = [c for c in cands if c.family == "Pipeline"]
    assert pipe, "no Pipeline candidate for a stacked-blocks model"
    res = run_search(item, spec)
    rows = [r for r in res.ranked if r["family"] == "Pipeline"]
    assert rows, "Pipeline family missing from the ranking"
    row = rows[0]
    assert row["knobs"].get("microbatches"), "microbatch knob not priced"
    assert row["strategy"].graph_config.pipeline_microbatches == \
        row["knobs"]["microbatches"], "winning knob not written back"
    assert row["breakdown"]["bubble_ms"] >= 0
    res2 = run_search(item, spec)
    assert [r["name"] for r in res.ranked] == \
        [r["name"] for r in res2.ranked]
    assert round(res.ranked[0]["predicted_ms"], 4) == \
        round(res2.ranked[0]["predicted_ms"], 4)


def test_registry_and_objective_completeness_pin_pipeline():
    """Satellite: the Pipeline family is pinned in both directions — it
    is a CANDIDATE_FAMILIES entry backed by an exported builder, and
    every objective prices it without error."""
    from autodist_tpu import strategy as strategy_mod
    from autodist_tpu.tuner.cost_model import CostModel, Topology
    from autodist_tpu.tuner.search import CANDIDATE_FAMILIES, OBJECTIVES
    fams = {cls.__name__ for cls in CANDIDATE_FAMILIES}
    assert "Pipeline" in fams
    assert "Pipeline" in strategy_mod.__all__
    params, loss_fn, batches = _zoo_lm()
    _reset_default()
    ad = AutoDist(strategy_builder=Pipeline(num_stages=2,
                                            num_microbatches=4))
    item = ad.capture(loss_fn, params, optax.sgd(0.1),
                      example_batch=batches[0])
    strategy = ad.build_strategy(item)
    model = CostModel(Topology(num_devices=8))
    for name in OBJECTIVES:
        bd = OBJECTIVES[name](model, strategy, item)
        assert bd.total_ms > 0, f"objective {name} cannot price Pipeline"


# ---------------------------------------------------------------------------
# observability closure


def test_pipeline_gauges_report_and_monitor(monkeypatch):
    """An observed pipelined loop publishes the pipeline.* gauges, the
    monitor /status pipeline row, and the report's Pipeline section."""
    from autodist_tpu.observability import monitor
    params, loss_fn, batches = _zoo_lm()
    _reset_default()
    observability.refresh()
    observability.registry().reset()
    ad = AutoDist(strategy_builder=Pipeline(num_stages=2,
                                            num_microbatches=4))
    item = ad.capture(loss_fn, params, optax.adam(1e-2),
                      example_batch=batches[0])
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    state, _ = runner.run(state, itertools.repeat(batches[0]), 4)
    g = observability.registry().snapshot()["gauges"]
    assert g["pipeline.stages"] == 2
    assert g["pipeline.microbatches"] == 4
    expected = observe.predicted_bubble(2, 4)
    assert abs(g["pipeline.bubble_fraction"] - round(expected, 4)) < 1e-9
    assert g["pipeline.bubble_ms_per_step"] > 0
    status = monitor.status()
    assert status["pipeline"]["stages"] == 2
    assert status["pipeline"]["microbatches"] == 4
    assert status["pipeline"]["bubble_ms_per_step"] == \
        g["pipeline.bubble_ms_per_step"]
    path = runner.write_report(batches[0])
    text = open(path).read()
    assert "Pipeline" in text and "bubble" in text
    assert "stage-cut imbalance" in text


def test_pipelined_telemetry_off_zero_calls(monkeypatch):
    """Satellite: AUTODIST_TELEMETRY=0 extends to the per-stage
    instrumentation — a PIPELINED observed run makes zero
    pipeline-observability calls (spy-pinned)."""
    monkeypatch.setenv("AUTODIST_TELEMETRY", "0")
    observability.refresh()
    assert not observability.enabled()
    params, loss_fn, batches = _zoo_lm()
    _reset_default()
    ad = AutoDist(strategy_builder=Pipeline(num_stages=2,
                                            num_microbatches=4))
    item = ad.capture(loss_fn, params, optax.adam(1e-2),
                      example_batch=batches[0])
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    state, _ = runner.step(state, batches[0])  # compile before measuring
    calls = []
    monkeypatch.setattr(observe, "finalize",
                        lambda *a, **k: calls.append("finalize"))
    monkeypatch.setattr(observe, "status_section",
                        lambda *a, **k: calls.append("status"))
    monkeypatch.setattr(observability.metrics.Gauge, "set",
                        lambda *a, **k: calls.append("gauge"))
    state, m = runner.run(state, itertools.repeat(batches[0]), 2)
    assert calls == [], f"pipeline telemetry calls with telemetry off: {calls}"
    assert m is not None


# ---------------------------------------------------------------------------
# resilience contracts under the pipelined path (chaos)


def test_pipeline_guard_rollback_at_megastep_granularity(monkeypatch):
    """Chaos NaN inside a pipelined megastep: the device-side flag trips
    the StepGuard at the megastep boundary, rollback restores the
    megastep-entry snapshot, and the trajectory matches a clean run that
    never saw the poisoned block — bitwise."""
    k, n = 2, 8
    params, loss_fn, batches = _stacked_float_model()
    monkeypatch.setenv("AUTODIST_CHAOS", "nan_at=3")  # block 2 (steps 3-4)
    _reset_default()
    ad = AutoDist(strategy_builder=Pipeline(num_stages=2,
                                            num_microbatches=4))
    item = ad.capture(loss_fn, params, optax.adam(1e-2),
                      example_batch=batches[0])
    runner = ad.create_distributed_session(item)
    guard = StepGuard(check_every=k, max_strikes=3)
    state = runner.create_state()
    state, _ = runner.run(state, iter(batches), n, step_guard=guard,
                          unroll=k)
    assert guard.rollbacks == 1
    assert int(jax.device_get(state.step)) == n

    monkeypatch.delenv("AUTODIST_CHAOS")
    clean = batches[:2] + batches[4:]  # the poisoned block is skipped
    _reset_default()
    ad2 = AutoDist(strategy_builder=Pipeline(num_stages=2,
                                             num_microbatches=4))
    item2 = ad2.capture(loss_fn, params, optax.adam(1e-2),
                        example_batch=batches[0])
    ref = ad2.create_distributed_session(item2)
    s_ref = ref.create_state()
    for b in clean[:n]:
        s_ref, _ = ref.step(s_ref, b)
    want = jax.tree_util.tree_leaves(ref.logical_params(s_ref))
    got = jax.tree_util.tree_leaves(runner.logical_params(state))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))


def test_pipeline_checkpoint_resume_at_megastep_granularity(tmp_path):
    """Checkpoint/resume under the pipelined path at unroll=K: saves
    land on megastep boundaries and the resumed trajectory matches the
    uninterrupted pipelined run bitwise."""
    from autodist_tpu.checkpoint import CheckpointManager
    params, loss_fn, batches = _stacked_float_model(n_batches=8)

    def build():
        _reset_default()
        ad = AutoDist(strategy_builder=Pipeline(num_stages=2,
                                                num_microbatches=4))
        item = ad.capture(loss_fn, params, optax.adam(1e-2),
                          example_batch=batches[0])
        return ad.create_distributed_session(item)

    runner = build()
    mgr = CheckpointManager(runner, tmp_path / "a", save_interval_steps=2,
                            max_to_keep=8)
    state = mgr.restore_or_init()
    state, _ = mgr.run(state, iter(batches[:4]), num_steps=4, unroll=2)
    assert mgr.latest_step() == 4
    mgr.close()

    # Resume in a FRESH session from the saved megastep boundary.
    runner2 = build()
    mgr2 = CheckpointManager(runner2, tmp_path / "a", save_interval_steps=2,
                             max_to_keep=8)
    state2 = mgr2.restore_or_init()
    assert int(jax.device_get(state2.step)) == 4
    # num_steps is a TOTAL target: continue from step 4 to step 8.
    state2, _ = mgr2.run(state2, iter(batches[4:]), num_steps=8, unroll=2)
    mgr2.close()

    # Control: uninterrupted pipelined run over the same batches.
    ref = build()
    s_ref = ref.create_state()
    s_ref, _ = ref.run(s_ref, iter(batches), 8, unroll=2)
    want = jax.tree_util.tree_leaves(ref.logical_params(s_ref))
    got = jax.tree_util.tree_leaves(runner2.logical_params(state2))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))


def test_anchors_skipped_event_on_explicit_path(monkeypatch):
    """Satellite (ROADMAP 2d first rung): GraphConfig.op_shardings
    anchors on the explicit path record an ``anchors-skipped`` flight
    event + report warning instead of being silently ignored."""
    from autodist_tpu.strategy import PSLoadBalancing
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}

    def loss_fn(p, b):
        x, y = b
        with jax.named_scope("dense"):
            h = x @ p["w"] + p["b"]
        return jnp.mean((h - y) ** 2)

    rng = np.random.RandomState(0)
    batch = (rng.randn(16, 8).astype(np.float32),
             rng.randn(16, 4).astype(np.float32))
    _reset_default()
    observability.refresh()
    observability.recorder.clear()
    # PS with staleness forces the explicit shard_map path; plant an
    # activation anchor the gspmd path would inject.
    from autodist_tpu.strategy import PS

    class AnchoredPS(PS):
        def build(self, graph_item, resource_spec):
            s = super().build(graph_item, resource_spec)
            s.graph_config.op_shardings["dense"] = "data,"
            for n in s.node_config:
                n.ps_synchronizer.staleness = 1  # -> explicit path
            return s

    ad = AutoDist(strategy_builder=AnchoredPS())
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    assert runner.program.use_explicit_path
    state = runner.create_state()
    runner.step(state, batch)
    kinds = [e["kind"] for e in observability.recorder.events()]
    assert "anchors-skipped" in kinds
    path = runner.write_report(batch)
    assert "anchors-skipped" in open(path).read()


# ---------------------------------------------------------------------------
# ISSUE 15 satellite (ROADMAP 3d): skip_idle=None gates on backend


def test_skip_idle_default_resolves_per_backend():
    """The fill/drain compute skip defaults ON only where it pays: OFF on
    XLA:CPU (the lax.cond transpose under AD is slower than the garbage
    compute it avoids, timed once as a skip-vs-noskip pair) and OFF
    under the sequence-parallel composition (lax.cond cannot wrap the
    stage's manual seq-axis collectives); ON on TPU/GPU."""
    from autodist_tpu.pipeline import resolve_skip_idle
    assert resolve_skip_idle(backend="cpu") is False
    assert resolve_skip_idle(backend="tpu") is True
    assert resolve_skip_idle(backend="gpu") is True
    # seq-parallel composition wins over any backend.
    assert resolve_skip_idle(backend="tpu", seq_manual=True) is False
    assert resolve_skip_idle(backend="cpu", seq_manual=True) is False
    # This harness runs on CPU: the live default must resolve off.
    assert resolve_skip_idle() is False


def test_skip_idle_default_is_value_preserving():
    """Flipping the resolved default must never change committed values:
    the skip gates GARBAGE fill/drain compute only (commits are masked
    by `valid` either way).  Pin skip on == skip off == auto bitwise."""
    from autodist_tpu.pipeline.schedule import (pipeline_apply,
                                                stack_stage_params)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), (const.MESH_AXIS_PIPELINE,))
    rng = np.random.RandomState(0)
    stages = [{"w": jnp.asarray(rng.randn(6, 6).astype(np.float32))}
              for _ in range(2)]
    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.randn(8, 6).astype(np.float32))

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"])

    outs = {}
    for label, skip in (("auto", None), ("on", True), ("off", False)):
        outs[label] = np.asarray(jax.jit(
            lambda s, xx, sk=skip: pipeline_apply(
                s, stage_fn, xx, 4, mesh, skip_idle=sk))(stacked, x))
    assert np.array_equal(outs["auto"], outs["off"])  # CPU default = off
    assert np.array_equal(outs["on"], outs["off"])
