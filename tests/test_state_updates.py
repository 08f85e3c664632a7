"""``aux["state_updates"]``: a step that also writes variables by a rule that
is no gradient.  The GSPMD step and its megastep apply it after the
optimizer's update; the explicit ``shard_map`` step refuses it, naming the
variable; capture refuses a name that is no variable, another shape or
dtype, and a variable the loss has a gradient for."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, strategy
from autodist_tpu.autodist import _reset_default
from autodist_tpu.graph_item import GraphItem


def _params():
    return {"w": jnp.full((4, 3), 0.5), "count": jnp.zeros((3,)),
            "nested": {"ema": jnp.ones((3,))}}


def _loss(params, batch):
    (x,) = batch
    y = x @ params["w"]
    # ``count`` only chooses (an index has no gradient); ``ema`` enters
    # through stop_gradient.
    chosen = jnp.argmax(y + params["count"], axis=-1)
    seen = jnp.bincount(chosen, length=3).astype(jnp.float32)
    ema = jax.lax.stop_gradient(params["nested"]["ema"])
    loss = jnp.mean((y - ema) ** 2)
    return loss, {"seen": seen.sum(), "state_updates": {
        "count": params["count"] + jnp.sign(seen.mean() - seen),
        "nested/ema": 0.9 * ema + 0.1 * jax.lax.stop_gradient(y).mean(0)}}


def _batch(rows=8, seed=0):
    return (np.random.RandomState(seed).randn(rows, 4).astype(np.float32),)


def _by_hand(params, batches, lr):
    """Plain Adam on ``w`` and the two rules written out."""
    opt = optax.adam(lr)
    state = opt.init(params)
    for batch in batches:
        (_, aux), grads = jax.value_and_grad(_loss, has_aux=True)(params,
                                                                   batch)
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        params = {"w": params["w"], "count": aux["state_updates"]["count"],
                  "nested": {"ema": aux["state_updates"]["nested/ema"]}}
    return params, state


@pytest.fixture(autouse=True)
def _fresh_autodist():
    _reset_default()
    yield
    _reset_default()


@pytest.mark.parametrize("mega", [False, True])
def test_the_gspmd_step_writes_the_updates_after_the_optimizers(mega):
    batches = [_batch(seed=i) for i in range(4)]
    ad = AutoDist(strategy_builder=strategy.AllReduce())
    item = ad.capture(_loss, _params(), optax.adam(1e-2),
                      example_batch=batches[0])
    assert item.state_updates == ("count", "nested/ema")
    runner = ad.create_distributed_session(item)
    assert not runner.program.use_explicit_path
    state = runner.create_state()
    if mega:
        block = jax.tree_util.tree_map(lambda *x: np.stack(x), *batches)
        state, metrics = runner.megastep(state, block)
    else:
        for batch in batches:
            state, metrics = runner.step(state, batch)
    assert "state_updates" not in metrics["aux"] and "seen" in metrics["aux"]
    want, want_opt = _by_hand(_params(), batches, 1e-2)
    got = jax.device_get(state.params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    assert float(np.abs(got["count"]).max()) >= 1.0
    # Adam's moments of a written variable stay zero: its gradient is.
    mu = jax.device_get(state.opt_state[0].mu)
    assert float(np.abs(mu["count"]).max()) == 0.0
    assert float(np.abs(mu["nested"]["ema"]).max()) == 0.0
    assert float(np.abs(mu["w"]).max()) > 0.0


def test_the_explicit_step_refuses_and_names_the_variables():
    ad = AutoDist(strategy_builder=strategy.PartitionedPS())
    item = ad.capture(_loss, _params(), optax.adam(1e-2),
                      example_batch=_batch())
    runner = ad.create_distributed_session(item)
    if not runner.program.use_explicit_path:
        pytest.skip("this mesh takes the GSPMD step under PartitionedPS")
    with pytest.raises(NotImplementedError, match="count, nested/ema"):
        runner.step(runner.create_state(), _batch())


def _with_updates(updates, loss=None):
    def loss_fn(params, batch):
        value, aux = _loss(params, batch)
        return (value if loss is None else loss(params, value)), dict(
            aux, state_updates=updates(params))
    return loss_fn


@pytest.mark.parametrize("updates, loss, message", [
    (lambda p: {"missing": p["count"]}, None, "no variable"),
    (lambda p: {"count": p["count"][:2]}, None, r"float32\(2,\)"),
    (lambda p: {"count": p["count"].astype(jnp.int32)}, None, "int32"),
    (lambda p: {"w": p["w"]}, None, "gradient with respect to it"),
    (lambda p: {"count": p["count"]},
     lambda p, value: value + jnp.sum(jax.checkpoint(jnp.tanh)(p["count"])),
     "gradient with respect to it")])
def test_capture_refuses_what_the_step_could_not_write(updates, loss,
                                                       message):
    with pytest.raises(ValueError, match=message):
        GraphItem.capture(_with_updates(updates, loss), _params(),
                          optax.adam(1e-2), example_batch=_batch())


def test_a_variable_behind_stop_gradient_inside_a_checkpoint_is_accepted():
    def loss_fn(params, batch):
        value, aux = _loss(params, batch)
        inner = jax.checkpoint(
            lambda c, v: v + jnp.sum(jax.lax.stop_gradient(c)))
        return inner(params["count"], value), aux
    item = GraphItem.capture(loss_fn, _params(), optax.adam(1e-2),
                             example_batch=_batch())
    assert item.state_updates == ("count", "nested/ema")


def test_inside_a_scan_capture_does_not_look_and_refuses():
    """The stated limit (docs/usage/state-updates.md): the walk enters
    ``jit``, ``checkpoint`` and custom-derivative calls and nothing with a
    loop or a branch of its own, so a written variable that a ``scan``'s
    body reads counts as reached even behind ``stop_gradient``.  Stop the
    gradient before the loop."""
    def loss_fn(inside):
        def loss(params, batch):
            value, aux = _loss(params, batch)
            count = params["count"] if inside \
                else jax.lax.stop_gradient(params["count"])
            total, _ = jax.lax.scan(
                lambda acc, c: (acc + jnp.sum(
                    jax.lax.stop_gradient(c) if inside else c), None),
                jnp.zeros(()), count[None])
            return value + total, aux
        return loss
    with pytest.raises(ValueError, match="gradient with respect to it"):
        GraphItem.capture(loss_fn(True), _params(), optax.adam(1e-2),
                          example_batch=_batch())
    item = GraphItem.capture(loss_fn(False), _params(), optax.adam(1e-2),
                             example_batch=_batch())
    assert "count" in item.state_updates


def test_a_loss_without_the_entry_is_as_before():
    item = GraphItem.capture(
        lambda p, b: (jnp.mean((b[0] @ p["w"]) ** 2), {"n": jnp.ones(())}),
        _params(), optax.adam(1e-2), example_batch=_batch())
    assert item.state_updates == () and item.aux_output
