"""Fused multi-step dispatch (megasteps): parity, guard semantics,
telemetry accounting, resident-batch fast path, block feeding (ISSUE 5).

The contract under test: ``Runner.run(state, it, N, unroll=K)`` compiles
K steps into ONE ``lax.scan`` dispatch and reproduces the trajectory of
N sequential ``step()`` calls BITWISE on the CPU tier — on both the
zero-telemetry fast path and the observed path — while StepGuard keeps
its divergence contract at megastep granularity (rollback to the
megastep-entry snapshot, offending block skipped) and the telemetry
accounting stays honest (``step.count == N``, one latency observation
per dispatch valued per-dispatch/K).
"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist, observability
from autodist_tpu.autodist import _reset_default
from autodist_tpu.resilience import StepGuard
from autodist_tpu.strategy import PS, AllReduce

BATCH = 32


def _loss_fn(params, batch):
    x, y = batch
    h = jax.nn.relu(x @ params["w1"])
    return jnp.mean((h @ params["w2"] - y) ** 2)


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(BATCH, 8).astype(np.float32),
             rng.randn(BATCH, 4).astype(np.float32)) for _ in range(n)]


def _build(builder=None):
    _reset_default()
    params = {"w1": jnp.zeros((8, 16)), "w2": jnp.zeros((16, 4))}
    ad = AutoDist(strategy_builder=builder or AllReduce())
    item = ad.capture(_loss_fn, params, optax.adam(1e-2),
                      example_batch=_batches(1)[0])
    return ad.create_distributed_session(item)


def _params_np(runner, state):
    return {k: np.asarray(jax.device_get(v))
            for k, v in runner.logical_params(state).items()}


# -- bitwise trajectory parity ------------------------------------------------


@pytest.mark.parametrize("unroll", [2, 4])
@pytest.mark.parametrize(
    "builder", [AllReduce, PS, lambda: PS(gspmd_update=True)],
    ids=["gspmd", "explicit", "gspmd-zero1"])
def test_unroll_parity_fast_path(builder, unroll, monkeypatch):
    """run(unroll=K) on the zero-telemetry fast path matches N sequential
    step() calls bitwise, on both execution paths and with the optimizer
    state sharded under the pure-GSPMD lowering."""
    n = 8
    batches = _batches(n)
    ref = _build(builder())
    monkeypatch.setattr(ref, "_obs", None)
    s_ref = ref.create_state()
    for b in batches:
        s_ref, m_ref = ref.step(s_ref, b)

    fused = _build(builder())
    monkeypatch.setattr(fused, "_obs", None)
    s = fused.create_state()
    s, m = fused.run(s, iter(batches), n, unroll=unroll)

    for k, want in _params_np(ref, s_ref).items():
        np.testing.assert_array_equal(_params_np(fused, s)[k], want,
                                      err_msg=f"param {k} diverged")
    assert int(jax.device_get(s.step)) == n
    # Per-step metrics stacked (K,); the flag aggregated to one scalar.
    assert np.shape(jax.device_get(m["loss"])) == (unroll,)
    assert np.shape(jax.device_get(m["notfinite"])) == ()
    assert float(np.asarray(jax.device_get(m["loss"]))[-1]) == \
        float(jax.device_get(m_ref["loss"]))


@pytest.mark.parametrize("unroll", [2, 4])
def test_unroll_parity_observed_path_and_telemetry_accounting(unroll):
    """Observed path: bitwise parity AND honest accounting — step.count
    counts steps, the latency histogram gets one observation per
    dispatch, and the unroll badge gauge is set."""
    n = 8
    batches = _batches(n)
    ref = _build()
    assert ref._obs is not None, "telemetry must be on for this test"
    s_ref = ref.create_state()
    for b in batches:
        s_ref, _ = ref.step(s_ref, b)

    fused = _build()
    s = fused.create_state()
    observability.registry().reset()
    s, _ = fused.run(s, iter(batches), n, unroll=unroll)

    for k, want in _params_np(ref, s_ref).items():
        np.testing.assert_array_equal(_params_np(fused, s)[k], want)

    snap = observability.registry().snapshot()
    assert snap["counters"]["step.count"] == n
    assert snap["counters"]["step.examples"] == n * BATCH
    assert snap["counters"]["host_transfer.batches"] == n // unroll
    assert snap["histograms"]["step.latency_ms"]["count"] == n // unroll
    assert snap["gauges"]["step.unroll"] == unroll


def test_unroll_requires_step_multiple():
    runner = _build()
    state = runner.create_state()
    with pytest.raises(ValueError, match="not a multiple of"):
        runner.run(state, iter(_batches(8)), 7, unroll=2)


# -- StepGuard at megastep granularity ---------------------------------------


def test_guard_rollback_inside_megastep_restores_entry_snapshot():
    """A NaN on the SECOND step of a megastep must still trip the guard
    (device-side aggregation), roll back to the megastep-entry state,
    and skip the whole offending K-block — the trajectory then matches
    a sequential run that never saw the poisoned batches."""
    k, n = 2, 8
    batches = _batches(n + 2, seed=1)
    poison = (np.full((BATCH, 8), np.nan, np.float32),
              batches[3][1])
    fed = batches[:3] + [poison] + batches[4:]      # steps 1..: b3 is NaN
    clean = batches[:2] + batches[4:]               # block (b2, poison) skipped

    guard = StepGuard(check_every=k, max_strikes=3)
    fused = _build()
    s = fused.create_state()
    s, _ = fused.run(s, iter(fed), n, step_guard=guard, unroll=k)
    assert guard.rollbacks == 1
    assert int(jax.device_get(s.step)) == n

    ref = _build()
    s_ref = ref.create_state()
    for b in clean[:n]:
        s_ref, _ = ref.step(s_ref, b)
    for key, want in _params_np(ref, s_ref).items():
        np.testing.assert_array_equal(_params_np(fused, s)[key], want,
                                      err_msg=f"param {key} diverged")


def test_guard_cadence_rounds_up_to_unroll_multiple():
    """check_every=3 with unroll=2 must check at step 4 (the first
    megastep boundary >= 3), not silently never: a NaN at step 3 is
    caught and rolled back."""
    k, n = 2, 8
    # First check lands at step 4 (cadence 3 -> 4), so rollback restores
    # step 0 and replays the full run: 4 consumed + 8 fresh batches.
    batches = _batches(n + 4, seed=2)
    poison = (np.full((BATCH, 8), np.nan, np.float32), batches[2][1])
    fed = batches[:2] + [poison] + batches[3:]
    guard = StepGuard(check_every=3, max_strikes=3)
    runner = _build()
    s = runner.create_state()
    s, m = runner.run(s, iter(fed), n, step_guard=guard, unroll=k)
    assert guard.rollbacks == 1
    assert not bool(jax.device_get(m["notfinite"]))
    assert int(jax.device_get(s.step)) == n


def test_diverged_accepts_stacked_flag():
    assert StepGuard.diverged(
        {"notfinite": jnp.array([False, True, False])})
    assert not StepGuard.diverged(
        {"notfinite": jnp.array([False, False])})


# -- resident-batch fast path (Remapper.shard_batch / shard_block) -----------


def test_shard_batch_fast_path_returns_placed_batch_untouched():
    runner = _build()
    batch = _batches(1)[0]
    placed = runner.remapper.shard_batch(batch)
    again = runner.remapper.shard_batch(placed)
    # No new buffers: the SAME array objects come back.
    for a, b in zip(jax.tree_util.tree_leaves(placed),
                    jax.tree_util.tree_leaves(again)):
        assert a is b
    # Host batches still go through placement.
    fresh = runner.remapper.shard_batch(batch)
    for a, b in zip(jax.tree_util.tree_leaves(batch),
                    jax.tree_util.tree_leaves(fresh)):
        assert a is not b and isinstance(b, jax.Array)


def test_shard_block_places_and_fast_paths():
    runner = _build()
    k = 4
    blocks = tuple(np.stack([leaf] * k)
                   for leaf in _batches(1)[0])
    placed = runner.remapper.shard_block(blocks)
    for leaf in jax.tree_util.tree_leaves(placed):
        assert isinstance(leaf, jax.Array)
        assert leaf.shape[0] == k
        # Leading (scan) dim replicated, batch dim sharded over data.
        assert leaf.sharding.spec[0] is None
    again = runner.remapper.shard_block(placed)
    for a, b in zip(jax.tree_util.tree_leaves(placed),
                    jax.tree_util.tree_leaves(again)):
        assert a is b


# -- block feeding ------------------------------------------------------------


def test_block_stacker_stacks_recycles_and_stops():
    from autodist_tpu.data import BlockStacker, BufferPool

    class _Loader:
        def __init__(self, n):
            self.pool = BufferPool((4, 3), np.float32, size=4)
            self._n = n
            self._i = 0

        def recycle(self, buf):
            self.pool.release(buf)

        def __iter__(self):
            return self

        def __next__(self):
            if self._i >= self._n:
                raise StopIteration
            out = self.pool.acquire()
            out[:] = self._i
            self._i += 1
            return out

    src = _Loader(6)
    stacker = BlockStacker(src, 2, recycle_to=src)
    b0 = next(stacker)
    assert b0.shape == (2, 4, 3)
    np.testing.assert_array_equal(b0[0], 0.0)
    np.testing.assert_array_equal(b0[1], 1.0)
    # Source batch buffers went straight back to the loader's pool.
    assert src.pool.outstanding == 0
    b1 = next(stacker)
    np.testing.assert_array_equal(b1[0], 2.0)
    # Recycling a block buffer returns it to the stacker's pool and the
    # next block reuses it (no fresh allocation).
    stacker.recycle(b0)
    b2 = next(stacker)
    assert b2 is b0
    np.testing.assert_array_equal(b2[0], 4.0)


def test_block_stacker_partial_tail_raises_stopiteration():
    from autodist_tpu.data import BlockStacker
    stacker = BlockStacker(iter([np.zeros((2, 2), np.float32)] * 3), 2)
    next(stacker)
    with pytest.raises(StopIteration):
        next(stacker)


def test_run_auto_wires_native_loader(tmp_path):
    """A framework NativeDataLoader passed straight to run() is composed
    with the DevicePrefetcher (and BlockStacker under unroll) without
    the caller lifting a finger."""
    from autodist_tpu.data import NativeDataLoader, write_record_file
    rng = np.random.RandomState(0)
    records = rng.randn(8 * BATCH, 8).astype(np.float32)
    path = str(tmp_path / "x.rec")
    write_record_file(path, records)

    def loss(p, x):
        return jnp.mean((x @ p["w"]) ** 2)

    _reset_default()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss, {"w": jnp.zeros((8, 4))}, optax.sgd(1e-2),
                      example_batch=records[:BATCH])
    runner = ad.create_distributed_session(item)

    loader = NativeDataLoader(path, (8,), np.float32, BATCH, seed=0)
    state = runner.create_state()
    state, metrics = runner.run(state, loader, 6, unroll=2)
    loader.close()
    assert int(jax.device_get(state.step)) == 6
    assert np.isfinite(np.asarray(jax.device_get(metrics["loss"]))).all()


def test_checkpoint_manager_run_unroll_saves_at_megastep_boundaries(tmp_path):
    """CheckpointManager.run(unroll=K): saves land on megastep
    boundaries, and a resume from a non-K-aligned step single-steps to
    the next boundary before fusing again."""
    from autodist_tpu.checkpoint import CheckpointManager
    batch = _batches(1)[0]
    runner = _build()
    mgr = CheckpointManager(runner, tmp_path / "mgr", save_interval_steps=2,
                            max_to_keep=8)
    state = mgr.restore_or_init()
    data = iter(lambda: batch, None)
    state, _ = mgr.run(state, data, num_steps=8, unroll=2)
    assert int(jax.device_get(state.step)) == 8
    assert mgr.latest_step() == 8
    mgr.close()

    # Parity against the sequential checkpointed loop.
    ref = _build()
    mgr2 = CheckpointManager(ref, tmp_path / "ref", save_interval_steps=2,
                             max_to_keep=8)
    s_ref = mgr2.restore_or_init()
    s_ref, _ = mgr2.run(s_ref, data, num_steps=8)
    for key, want in _params_np(ref, s_ref).items():
        np.testing.assert_array_equal(_params_np(runner, state)[key], want)
    mgr2.close()


# -- dump_compiled regression -------------------------------------------------


def test_dump_compiled_reports_failure_instead_of_none(monkeypatch):
    runner = _build()
    good = _batches(1)[0]
    state = runner.create_state()
    runner.step(state, good)
    bad = (np.zeros((BATCH, 9), np.float32),
           np.zeros((BATCH, 4), np.float32))  # 9 != w1's 8: cannot lower
    monkeypatch.delenv("AUTODIST_DUMP_GRAPHS", raising=False)
    out = runner.dump_compiled(bad)
    assert out is not None and "HLO dump failed" in out
    monkeypatch.setenv("AUTODIST_DUMP_GRAPHS", "1")
    with pytest.raises(Exception):
        runner.dump_compiled(bad)
    # A good batch still dumps to a path.
    monkeypatch.delenv("AUTODIST_DUMP_GRAPHS", raising=False)
    path = runner.dump_compiled(good)
    assert path.endswith(".txt")


# -- cost model ranks unroll factors ------------------------------------------


def test_cost_model_amortizes_dispatch_overhead_with_unroll():
    from autodist_tpu.graph_item import GraphItem, VariableItem
    from autodist_tpu.strategy import AllReduce as AR
    from autodist_tpu.tuner.cost_model import (DISPATCH_MS, CostModel,
                                               Topology)
    import autodist_tpu.resource_spec as rs
    item = GraphItem(loss_fn=None, params=None, optimizer=None,
                     variables=[VariableItem("v", (64, 4), jnp.float32)])
    spec = rs.ResourceSpec()
    strat = AR(chunk_size=128).build(item, spec)
    model = CostModel(Topology(num_devices=8, num_hosts=1))
    c1 = model.strategy_cost(strat, item)
    c8 = model.strategy_cost(strat, item, unroll=8)
    assert c1["dispatch_ms"] == pytest.approx(DISPATCH_MS)
    assert c8["dispatch_ms"] == pytest.approx(DISPATCH_MS / 8)
    assert c8.total_ms < c1.total_ms
    assert c1.total_ms - c8.total_ms == pytest.approx(
        DISPATCH_MS * (1 - 1 / 8))
