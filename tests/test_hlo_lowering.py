"""HLO-assertion tier: the claimed lowerings must be visible in compiled HLO.

Round-1 verdict: the ZeRO-1 "ReduceScatter" claim was never verified — and
on the CPU backend GSPMD in fact emits all-reduce + dynamic-slice, never
reduce-scatter (the AR+DS -> RS rewrite is a backend pass).  The explicit
shard_map path makes the collective *structural* (``psum_scatter`` /
all_gather-VJP), so these tests assert on compiled HLO text and fail if the
mechanism regresses.  Parity claim under test:
``autodist_tpu/kernel/synchronization/ps_synchronizer.py`` (accumulator +
take_grad -> ReduceScatter; reference ``ps_synchronizer.py:553-630``).

These are the CPU pipeline's view, on the live 8-device mesh, with a step
run first.  The same claims against the real v5e compiler, for a detached
``v5e:2x4`` topology and with the TPU backend's rewrites applied, sit in
``tests/test_topology_aot.py::test_v5e_compiler_hlo``.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.strategy import (PS, AllReduce, ModelParallel, Parallax,
                                   PartitionedPS)


def _loss_fn(params, batch):
    x, y = batch
    h = jax.nn.relu(x @ params["w1"])
    pred = h @ params["w2"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _fixture():
    rng = np.random.RandomState(0)
    params = {"w1": jnp.zeros((64, 128)), "w2": jnp.zeros((128, 8)),
              "b": jnp.zeros((8,))}
    batch = (rng.randn(32, 64).astype(np.float32),
             rng.randn(32, 8).astype(np.float32))
    return params, batch


def _compiled_hlo(strategy, mesh_axes=None, optimizer=None):
    params, batch = _fixture()
    ad = AutoDist(strategy_builder=strategy, mesh_axes=mesh_axes)
    item = ad.capture(_loss_fn, params, optimizer or optax.adam(1e-3),
                      example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    sharded = runner.remapper.shard_batch(batch)
    state, _ = runner.step(state, sharded, shard_inputs=False)
    state_shapes = jax.eval_shape(lambda: runner.create_state())
    text = runner._compiled.lower(state_shapes, sharded).compile().as_text()
    return text, runner


def _count(text, op):
    # HLO op invocations: `%name = type op-name(args)` (+ async -start forms).
    return len(re.findall(rf"\b{op}(?:-start)?(?:\.\d+)?\(", text))


def test_ps_zero1_lowers_to_reduce_scatter():
    """PS => ReduceScatter of grads + AllGather of params, NOT a full
    AllReduce per variable (the framework's central perf mechanism)."""
    text, runner = _compiled_hlo(PS())
    assert runner.program.use_explicit_path
    rs, ag, ar = (_count(text, "reduce-scatter"), _count(text, "all-gather"),
                  _count(text, "all-reduce"))
    # w1, w2, b all ZeRO-1-sharded: one scatter + one gather each (compiler
    # may fuse, so >= 1); the only all-reduces allowed are scalar metrics.
    assert rs >= 1, f"no reduce-scatter in PS HLO (ar={ar}, ag={ag})"
    assert ag >= 1, f"no all-gather in PS HLO"
    scalar_ar = ar  # loss pmean (+ adam count is local) => small constant
    assert scalar_ar <= 2, \
        f"PS path emits {ar} all-reduces — gradient AllReduce leaked back in"


def test_partitioned_ps_fsdp_lowers_to_reduce_scatter():
    """PartitionedPS (params sharded over data = FSDP/ZeRO-3): the backward
    emits ReduceScatter via the all_gather VJP; forward gathers shards."""
    text, runner = _compiled_hlo(PartitionedPS())
    assert runner.program.use_explicit_path
    assert _count(text, "reduce-scatter") >= 1
    assert _count(text, "all-gather") >= 1
    assert _count(text, "all-reduce") <= 2  # metrics only


def test_gspmd_ps_escape_hatch_keeps_update_sharded():
    """gspmd_update=True: pure-GSPMD lowering. On CPU the backend has no
    AR->RS rewrite, so assert the *semantic* ZeRO pattern instead: the
    reduction is followed by a dynamic-slice (shard-local update) and an
    all-gather; on TPU the compiler's collective pass may emit
    reduce-scatter directly."""
    text, runner = _compiled_hlo(PS(gspmd_update=True))
    assert not runner.program.use_explicit_path
    if jax.default_backend() in ("tpu",):
        assert _count(text, "reduce-scatter") >= 1 or (
            _count(text, "all-reduce") >= 1 and _count(text, "dynamic-slice") >= 1)
    else:
        assert _count(text, "all-reduce") >= 1
        assert _count(text, "dynamic-slice") >= 1
    assert _count(text, "all-gather") >= 1


def test_explicit_allreduce_buckets_fuse_collectives():
    """Strategy `group` ids bucket same-group gradients into ONE collective
    (ScopedAllocator parity): 3 vars in 1 chunk group + bf16 compressor =>
    1 gradient all-reduce + 1 loss all-reduce, not 3+1."""
    text, runner = _compiled_hlo(
        AllReduce(chunk_size=8, compressor="HorovodCompressor"))
    assert runner.program.use_explicit_path
    ar = _count(text, "all-reduce")
    assert ar <= 2, f"expected fused bucket (1 grad AR + 1 loss AR), got {ar}"
    # bf16 wire format: at least one all-reduce operates on bf16.
    assert re.search(r"all-reduce[^=]*=\s*bf16", text) or "bf16" in text


def test_model_parallel_tp_inserts_activation_collectives():
    """TP (ModelParallel): row/col-parallel matmuls must communicate
    activations (all-reduce or reduce-scatter over the model axis), and
    kernel storage must actually be sharded over 'model'."""
    params, batch = _fixture()
    ad = AutoDist(strategy_builder=ModelParallel(rules=(("w1", 1), ("w2", 0))),
                  mesh_axes={"data": 4, "model": 2})
    item = ad.capture(_loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    # storage sharded over model
    w1_shards = {s.data.shape for s in state.params["w1"].addressable_shards}
    assert w1_shards == {(64, 64)}, f"w1 not TP-sharded: {w1_shards}"
    sharded = runner.remapper.shard_batch(batch)
    state, _ = runner.step(state, sharded, shard_inputs=False)
    state_shapes = jax.eval_shape(lambda: runner.create_state())
    text = runner._compiled.lower(state_shapes, sharded).compile().as_text()
    assert (_count(text, "all-reduce") + _count(text, "reduce-scatter")) >= 1, \
        "TP emitted no activation collectives"


def test_parallax_mixed_paths_share_one_program():
    """Parallax: sparse vars ride PS (reduce-scatter), dense ride AR —
    composed in a single explicit program on a multi-axis mesh."""
    rng = np.random.RandomState(0)
    params = {"emb": jnp.zeros((512, 32)), "head": jnp.zeros((32, 4))}

    def loss(p, b):
        idx, y = b
        h = p["emb"][idx]  # gather -> sparse_access detection
        return jnp.mean((h @ p["head"] - y) ** 2)

    batch = (rng.randint(0, 512, (32,)).astype(np.int32),
             rng.randn(32, 4).astype(np.float32))
    ad = AutoDist(strategy_builder=Parallax(), mesh_axes={"data": 4, "model": 2})
    item = ad.capture(loss, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    sharded = runner.remapper.shard_batch(batch)
    state, metrics = runner.step(state, sharded, shard_inputs=False)
    assert np.isfinite(float(metrics["loss"]))
    state_shapes = jax.eval_shape(lambda: runner.create_state())
    text = runner._compiled.lower(state_shapes, sharded).compile().as_text()
    assert _count(text, "all-reduce") >= 1  # dense head
