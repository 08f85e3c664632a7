"""Pipeline parallelism: schedule numerics + end-to-end pipelined training."""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh

from autodist_tpu import AutoDist
from autodist_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
from autodist_tpu.parallel.sharding_rules import apply_sharding_rules
from autodist_tpu.strategy import AllReduce


def _stages(n_stages=4, dim=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), n_stages)
    mk = lambda k: {"w": jax.random.normal(k, (dim, dim)) * (1.0 / np.sqrt(dim)),
                    "b": jnp.zeros((dim,))}
    return [mk(k) for k in keys]


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _mesh(axes):
    devs = np.array(jax.devices()).reshape(*axes.values())
    return Mesh(devs, axis_names=tuple(axes))


@pytest.mark.parametrize("num_micro", [4, 6, 8])  # 6: M % P != 0 legacy path
def test_pipeline_matches_sequential(num_micro):
    stages = _stages()
    stacked = stack_stage_params(stages)
    x = jax.random.normal(jax.random.PRNGKey(1), (4 * num_micro, 16),
                          jnp.float32)
    # stage count must equal the pipe-axis size: 4 stages on a 4-device
    # pipe axis; the remaining devices go to data.
    mesh = _mesh({"data": 2, "pipe": 4})
    got = jax.jit(lambda s, x: pipeline_apply(s, _stage_fn, x, num_micro, mesh))(
        stacked, x)
    expect = x
    for p in stages:
        expect = _stage_fn(p, expect)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_match_sequential():
    stages = _stages()
    stacked = stack_stage_params(stages)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16), jnp.float32)
    mesh = _mesh({"data": 2, "pipe": 4})

    def loss_pipe(s):
        return (pipeline_apply(s, _stage_fn, x, 4, mesh) ** 2).mean()

    def loss_seq(s):
        h = x
        for i in range(4):
            h = _stage_fn(jax.tree_util.tree_map(lambda l: l[i], s), h)
        return (h ** 2).mean()

    gp = jax.jit(jax.grad(loss_pipe))(stacked)
    gs = jax.jit(jax.grad(loss_seq))(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_schedule_length_and_bubble_model():
    """Pin the documented schedule: scan trip count is M + 2P - 3 for the
    sharded-commit path (M % P == 0), M + P - 1 legacy; wall-clock bubble
    is the GPipe (P-1)/(M+P-1)."""
    from autodist_tpu.parallel.pipeline import (bubble_fraction,
                                                num_schedule_steps)
    assert num_schedule_steps(4, 8, True) == 13
    assert num_schedule_steps(4, 6, False) == 9
    assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-12

    stages = _stages()
    stacked = stack_stage_params(stages)
    mesh = _mesh({"data": 2, "pipe": 4})
    for m, steps in ((8, 13), (6, 9)):
        x = jax.random.normal(jax.random.PRNGKey(1), (4 * m, 16), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda s, x: pipeline_apply(s, _stage_fn, x, m, mesh))(stacked, x)
        assert f"length={steps}" in str(jaxpr), \
            f"M={m}: schedule scan is not {steps} steps"


def test_skip_idle_saves_fill_drain_compute():
    """The cond-skip removes fill/drain garbage stage executions: per rank
    M computed slots instead of all M + 2P - 3 ticks of the scan.  Counted,
    not timed: the stage body reports each execution to the host, so the
    saving is the number of stage ticks that ran (M/(M+2P-3) ~= 0.62 of
    them at P=4, M=8), whatever else the host is doing."""
    p_size, m = 4, 8
    stacked = stack_stage_params(_stages(p_size))
    x = jax.random.normal(jax.random.PRNGKey(1), (4 * m, 16), jnp.float32)
    mesh = _mesh({"data": 2, "pipe": p_size})
    ranks = mesh.devices.size
    executed = []  # one entry a stage execution; append is atomic

    def counted_stage(p, a):
        jax.debug.callback(lambda: executed.append(1))
        return _stage_fn(p, a)

    def run(skip):
        executed.clear()
        out = jax.jit(lambda s, x: pipeline_apply(
            s, counted_stage, x, m, mesh, skip_idle=skip))(stacked, x)
        out.block_until_ready()
        jax.effects_barrier()
        return len(executed), out

    from autodist_tpu.parallel.pipeline import num_schedule_steps
    ticks = num_schedule_steps(p_size, m, True)
    assert ticks == m + 2 * p_size - 3
    n_skip, out_skip = run(True)
    n_full, out_full = run(False)
    np.testing.assert_allclose(np.asarray(out_skip), np.asarray(out_full),
                               rtol=1e-5, atol=1e-5)
    # Every (data, pipe) rank runs its stage once a microbatch with the
    # skip, once a scan tick without it.
    assert n_skip == ranks * m, (n_skip, n_full)
    assert n_full == ranks * ticks, (n_skip, n_full)


def test_pipelined_model_trains_e2e():
    """Full framework path: embedding -> pipelined blocks -> head, on a
    data x pipe mesh, numeric parity with the sequential model."""
    dim, n_stages = 16, 4
    stages = _stages(n_stages, dim)
    k = jax.random.PRNGKey(2)
    params = {"inproj": {"kernel": jax.random.normal(k, (8, dim)) * 0.3},
              "stages": stack_stage_params(stages),
              "head": {"kernel": jax.random.normal(k, (dim, 4)) * 0.3}}

    ad = AutoDist(strategy_builder=AllReduce(),
                  mesh_axes={"data": 2, "pipe": 4})
    mesh = ad.cluster.build_mesh({"data": 2, "pipe": 4})

    def loss_fn(p, batch):
        x, labels = batch
        h = x @ p["inproj"]["kernel"]
        h = pipeline_apply(p["stages"], _stage_fn, h, 4, mesh)
        logits = h @ p["head"]["kernel"]
        return -jnp.mean(jax.nn.log_softmax(logits)[
            jnp.arange(labels.shape[0]), labels])

    rng = np.random.RandomState(0)
    batch = (rng.randn(16, 8).astype(np.float32),
             rng.randint(0, 4, (16,)).astype(np.int32))
    opt = optax.sgd(0.1)
    item = ad.capture(loss_fn, params, opt, example_batch=batch)
    strategy = ad.build_strategy(item)
    apply_sharding_rules(strategy, item, 4, rules=((r"^stages/", 0),),
                         mesh_axis="pipe")

    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    dist_losses = []
    for _ in range(3):
        state, metrics = runner.step(state, batch)
        dist_losses.append(float(jax.device_get(metrics["loss"])))

    p, o = params, opt.init(params)
    ref_losses = []
    for _ in range(3):
        l, g = jax.value_and_grad(loss_fn)(p, batch)
        u, o = opt.update(g, o, p)
        p = optax.apply_updates(p, u)
        ref_losses.append(float(l))
    np.testing.assert_allclose(dist_losses, ref_losses, rtol=1e-4, atol=1e-5)
