"""The ``fsdp`` gradients' reduce-scatter as independent permutes, held
against the next layer's backward pass by the data (PR 35;
``kernel/synchronization/grad_scatter.py``, ``parallel/context.py:
layer_boundary``).  The CPU mesh proves values, fallbacks and the jaxpr; the
schedule the v5e compiler makes of it is
``tests/test_topology_aot.py::test_v5e_compiler_keeps_the_scatter_in_the_backward_pass``.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu import AutoDist
from autodist_tpu.kernel.synchronization import grad_scatter
from autodist_tpu.models import lm
from autodist_tpu.models import transformer as T
from autodist_tpu.parallel import context as parallel_ctx
from autodist_tpu.strategy import PartitionedPS, UnevenPartitionedPS


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_exchange_scatter_is_psum_scatter(n, dim, dtype):
    """Every chip's shard of the sum, to what the order of ``n`` additions
    in the gradient's own dtype explains."""
    rng = np.random.RandomState(n * 10 + dim)
    # One full gradient a chip, stacked along a leading axis that the
    # shard_map splits.
    g = jnp.asarray(rng.randn(n, 16, 24), dtype)

    def both(x):
        x = x[0]
        return (grad_scatter.exchange_scatter(x, "data", n, dim)[None],
                jax.lax.psum_scatter(x, "data", scatter_dimension=dim,
                                     tiled=True)[None])
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    got, want = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=P("data"), out_specs=(P("data"), P("data")),
        check_vma=False))(g)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    shard = list(g.shape[1:])
    shard[dim] //= n
    assert list(got.shape) == [n] + shard
    eps = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0,
        atol=n * eps * float(np.abs(np.asarray(g, np.float32)).sum(0).max()))
    exact = np.asarray(g, np.float64).sum(0)
    rows = shard[dim]
    for r in range(n):
        np.testing.assert_allclose(
            np.asarray(got[r], np.float64),
            np.take(exact, range(r * rows, (r + 1) * rows), axis=dim),
            atol=n * eps * 8)


@pytest.mark.parametrize("shape, dim, n, min_bytes, why", [
    ((1600, 1600), 0, 4, grad_scatter.ASYNC_MIN_BYTES, ""),
    ((6400, 1600), 1, 4, grad_scatter.ASYNC_MIN_BYTES, ""),
    ((64, 64), 0, 4, grad_scatter.ASYNC_MIN_BYTES, "under"),
    ((64, 64), 0, 4, 0, ""),
    ((1600,), 0, 4, 0, "rank"),
    ((50257, 1600), 0, 4, 0, "not divisible"),
    ((1602, 1600), 0, 4, 0, "not divisible"),
    ((1600, 1600), 0, 1, 0, "one chip"),
])
def test_which_leaves_take_the_exchange(shape, dim, n, min_bytes, why):
    """Bytes, rank, axis size and divisibility decide, from the shape."""
    said = grad_scatter.why_not(shape, jnp.float32, dim, n, min_bytes)
    assert (said == "") == (why == "") and why in said


def _tiny(layers=3):
    cfg = T.TransformerConfig(vocab=64, dim=32, num_heads=4,
                              num_layers=layers, max_len=16, causal=True,
                              dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(1)
    batches = [(rng.randint(0, 64, (8, 17)).astype(np.int32),)
               for _ in range(3)]
    return cfg, params, batches


def _runner(tmp_path, cfg, params, batch, builder=PartitionedPS):
    # Plain SGD: a key bias's gradient is rounding noise around zero (the
    # softmax does not see it), which Adam would scale up to a full step.
    spec = tmp_path / "spec.yml"
    spec.write_text("nodes:\n  - address: 127.0.0.1\n    chief: true\n"
                    "    cpus: [0, 1, 2, 3]\n")
    ad = AutoDist(str(spec), builder(), devices=jax.devices()[:4])
    item = ad.capture(lm.make_loss_fn(cfg), params, optax.sgd(0.1),
                      example_batch=batch)
    return ad.create_distributed_session(item)


def _step_with(runner, batch, min_bytes):
    specs = runner.program.batch_specs(batch)
    return jax.jit(
        runner._explicit_step_fn(specs, async_min_bytes=min_bytes),
        in_shardings=(runner.state_shardings, None),
        out_shardings=(runner.state_shardings, None))


def _permutes(text):
    return len(re.findall(r"collective[-_]permute", text))


@pytest.mark.parametrize("case", ["under-the-threshold", "forced-on",
                                  "uneven-shards"])
def test_the_lowered_step_takes_the_form_the_shapes_allow(case, tmp_path):
    """The toy's matrices are under the threshold: the step the Runner
    builds holds no permute (today's program).  With the threshold at zero
    (a test's argument, no setting) each matrix handed to the boundary op
    is scattered by ``n - 1`` permutes.  Stored padded (uneven shards), a
    leaf keeps the plain transpose whatever the threshold."""
    cfg, params, batches = _tiny()
    builder = UnevenPartitionedPS if case == "uneven-shards" else PartitionedPS
    if case == "uneven-shards":     # no dimension of any leaf divides by 4
        cfg = T.TransformerConfig(vocab=63, dim=30, num_heads=3, num_layers=3,
                                  max_len=17, mlp_dim=70, causal=True,
                                  dtype=jnp.float32)
        params = lm.init(jax.random.PRNGKey(0), cfg)
    runner = _runner(tmp_path, cfg, params, batches[0], builder)
    sharded = runner.remapper.shard_batch(batches[0])
    min_bytes = None if case == "under-the-threshold" else 0
    text = _step_with(runner, sharded, min_bytes).lower(
        runner.state_struct, sharded).as_text()
    if case == "forced-on":
        matrices = 3 * 6    # q, k, v, out, up, down of each layer
        assert _permutes(text) == matrices * 3
    else:
        assert _permutes(text) == 0
    assert "all_gather" in text or "all-gather" in text


def test_three_steps_equal_the_plain_transposes(tmp_path):
    """A ``PartitionedPS`` trajectory on four devices with every matrix in
    the asynchronous form against the same steps in the parent's form:
    losses and every parameter to 1e-6."""
    cfg, params, batches = _tiny()
    runner = _runner(tmp_path, cfg, params, batches[0])
    ends = {}
    for name, min_bytes in (("exchange", 0), ("plain", 1 << 40)):
        state = runner.create_state()
        losses = []
        step = None
        for batch in batches:
            sharded = runner.remapper.shard_batch(batch)
            step = step or _step_with(runner, sharded, min_bytes)
            state, metrics = step(state, sharded)
            losses.append(float(metrics["loss"]))
        ends[name] = (losses, jax.tree_util.tree_map(
            np.asarray, runner.logical_params(state)))
    np.testing.assert_allclose(ends["exchange"][0], ends["plain"][0],
                               rtol=1e-6)
    assert ends["plain"][0][-1] < ends["plain"][0][0]
    flat = jax.tree_util.tree_leaves_with_path(ends["exchange"][1])
    for (path, got), want in zip(flat,
                                 jax.tree_util.tree_leaves(ends["plain"][1])):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                   err_msg=str(path))


@pytest.mark.parametrize("what", ["arguments", "jaxpr"])
def test_the_boundary_op_without_a_context_is_the_identity(what,
                                                           monkeypatch):
    """No context (one chip, GSPMD, plain JAX): the arguments come back as
    they are, and a model that calls the op traces to the jaxpr of one that
    does not."""
    cfg, params, batches = _tiny()
    if what == "arguments":
        x = jnp.ones((2, 3))
        ahead = {"layer1": params["layer1"]}
        got, got_x = parallel_ctx.layer_boundary(ahead, x)
        assert got is ahead and got_x is x
        return
    loss = lm.make_loss_fn(cfg)
    with_op = str(jax.make_jaxpr(jax.grad(loss))(params, batches[0]))
    called = []

    def never(params_ahead, x):
        called.append(1)
        return params_ahead, x
    assert "custom_vjp" not in with_op and "optimization_barrier" not in with_op
    monkeypatch.setattr(parallel_ctx, "layer_boundary", never)
    without = str(jax.make_jaxpr(jax.grad(loss))(params, batches[0]))
    assert called and with_op == without
