"""The delta rule with a decay a CHANNEL of the key (``ops/gated_delta.py``
with ``g`` of (batch, s, heads, d_k): Kimi Delta Attention's rule) against
the rule one position at a time, on the CPU in float32: outputs, final
states and the gradients of q, k, v, g and beta with cotangents on the
output and on the final state; the ``lax.scan`` and the Pallas kernels
interpreted; lengths of one chunk, of many and of a length the chunk does
not divide; one, two and four sub-blocks a chunk; fewer key heads than value
heads; a scalar decay broadcast over the channels is the scalar rule; every
gate at the lower bound through a whole chunk stays finite and right; what
the kernels are handed and how many heads a program takes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops import gated_delta
from autodist_tpu.ops.gated_delta import gated_delta_rule

HEADS, D_K, D_V = 4, 8, 12


def recurrent_rule(q, k, v, g, beta):
    """``S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_(t-1) + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t``, a position at a time in float32: ``(o,
    final state)``; q and k repeated to the value heads."""
    group = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(t, group, axis=2) for t in (q, k))
    b, s, h, d_k = q.shape
    q, k, v, g, beta = (jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                        for t in (q, k, v, g, beta))

    def step(state, x):
        q, k, v, g, beta = x                      # (b, h, ...); g (b, h, d_k)
        state = jnp.exp(g)[..., None] * state
        u = beta[..., None] * (v - jnp.einsum("bhkd,bhk->bhd", state, k))
        state = state + k[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkd,bhk->bhd", state, q)

    state, o = jax.lax.scan(step, jnp.zeros((b, h, d_k, v.shape[-1])),
                            (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1), state


def _inputs(seed, rows, s, decay=1.0, heads=HEADS, key_heads=None, d_k=D_K,
            d_v=D_V, dtype=jnp.float32):
    """Unit k, q scaled by d_k^-1/2, log decays a channel in
    ``GATE_LOWER_BOUND x sigmoid`` (the safe gate's form) times ``decay``,
    beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    key_heads = key_heads or heads
    q = jax.random.normal(ks[0], (rows, s, key_heads, d_k))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d_k ** -0.5
    k = jax.random.normal(ks[1], (rows, s, key_heads, d_k))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (rows, s, heads, d_v))
    g = decay * gated_delta.GATE_LOWER_BOUND * jax.nn.sigmoid(
        2.0 * jax.random.normal(ks[3], (rows, s, heads, d_k)))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (rows, s, heads)))
    return tuple(t.astype(dtype) for t in (q, k, v)) + (g, beta)


def _both(args, rule):
    """Outputs, states and the five gradients (cotangents on the output and
    on the final state) of ``rule`` and of the recurrence."""
    ws = jax.random.split(jax.random.PRNGKey(9))
    w_o = jax.random.normal(ws[0], args[2].shape, jnp.float32)
    w_s = jax.random.normal(ws[1], (args[2].shape[0], args[2].shape[2],
                                    args[0].shape[3], args[2].shape[3]))

    def scored(f):
        def run(*a):
            o, state = f(*a)
            return (jnp.sum(o.astype(jnp.float32) * w_o)
                    + jnp.sum(state * w_s), (o, state))
        return run

    with jax.default_matmul_precision("highest"):
        return [jax.jit(jax.value_and_grad(scored(f), argnums=(0, 1, 2, 3, 4),
                                           has_aux=True))(*args)
                for f in (rule, recurrent_rule)]


def _assert_close(got, want, tol, name=""):
    assert bool(jnp.isfinite(jnp.asarray(got, jnp.float32)).all()), name
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=tol, rtol=0, err_msg=name)


def _assert_the_recurrence(args, rule, tol=2e-5, grad_tol=2e-4):
    ((_, (o, state)), grads), ((_, (o_want, state_want)), grads_want) = \
        _both(args, rule)
    _assert_close(o, o_want, tol, "o")
    _assert_close(state, state_want, tol, "state")
    for name, got, want in zip("q k v g beta".split(), grads, grads_want):
        assert got.shape == want.shape, name
        _assert_close(got, want, grad_tol, name)


@pytest.mark.parametrize("s, chunk, decay", [
    (16, 16, 1.0), (50, 16, 1.0), (128, 64, 1.0), (128, 64, 0.05)])
def test_the_chunked_rule_is_the_recurrence(s, chunk, decay):
    """The scan: one and four sub-blocks a chunk, one chunk and many, a
    length the chunk does not divide (padded inside: the padding decays
    nothing and writes nothing), gates down to the lower bound and near
    zero."""
    _assert_the_recurrence(
        _inputs(s + chunk, 2, s, decay),
        lambda *a: gated_delta_rule(*a, chunk=chunk))


@pytest.mark.parametrize("s, chunk, key_heads", [(48, 16, 4), (48, 16, 2),
                                                 (128, 64, 4)])
def test_the_interpreted_kernels_are_the_recurrence(s, chunk, key_heads):
    """``kda_walk_fwd`` and ``kda_walk_bwd`` in the Pallas interpreter:
    ``gamma`` a (chunk, d_k) block a head, its cotangent a channel, the
    state's carry-over a scaling of its rows; with two value heads a key head
    q and k are read a key head and dq, dk summed over the group."""
    args = _inputs(3 + key_heads, 2, s, key_heads=key_heads)
    text = str(jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, chunk=chunk, interpret=True))(*args))
    assert "kda_walk_fwd" in text and "gdn_walk_fwd" not in text
    _assert_the_recurrence(
        args, lambda *a: gated_delta_rule(*a, chunk=chunk, interpret=True))


def test_the_interpreted_kernels_are_the_scan_in_bf16():
    """bf16 operands: both walks round the same operands, so they agree to
    the rounding of one product's order."""
    args = _inputs(5, 1, 64, dtype=jnp.bfloat16)
    (o, state), (o_scan, state_scan) = (
        gated_delta_rule(*args, chunk=16, interpret=interpret)
        for interpret in (True, None))
    _assert_close(o, o_scan, 2e-2, "o")
    _assert_close(state, state_scan, 2e-2, "state")


@pytest.mark.parametrize("interpret", [None, True], ids=["scan", "kernels"])
def test_a_scalar_decay_over_the_channels_is_the_scalar_rule(interpret):
    """``g`` a head, broadcast over the key's channels, through the vector
    form gives what the scalar form gives of ``g``: values and gradients
    (the channel form's ``dg`` summed over the channels)."""
    q, k, v, g, beta = _inputs(11, 2, 64)
    g = g[..., 0]

    def scalar(q, k, v, g, beta):
        return gated_delta_rule(q, k, v, g, beta, chunk=16,
                                interpret=interpret)

    def by_channel(q, k, v, g, beta):
        return gated_delta_rule(
            q, k, v, jnp.broadcast_to(g[..., None], g.shape + (D_K,)), beta,
            chunk=16, interpret=interpret)

    def grads(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(f(*a)[0])), argnums=(0, 1, 2, 3, 4)))(
                q, k, v, g, beta)

    with jax.default_matmul_precision("highest"):
        (want, d_want), (got, d_got) = grads(scalar), grads(by_channel)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in zip("q k v g beta".split(), d_got, d_want):
        _assert_close(a, b, 2e-5, name)


@pytest.mark.parametrize("interpret", [None, True], ids=["scan", "kernels"])
def test_every_gate_at_the_lower_bound_stays_finite_and_right(interpret):
    """``g = GATE_LOWER_BOUND`` in every position and channel of two chunks
    of 64: inside a sub-block the columns' operand reaches e^75, which
    float32 holds, and the result and gradients are the recurrence's; one
    step under the bound per sub-block position more would not be (the
    bound is what the form rests on, ``kda.gate_lower_bound``)."""
    q, k, v, g, beta = _inputs(13, 1, 128, heads=2)
    g = jnp.full_like(g, gated_delta.GATE_LOWER_BOUND)
    assert float(gated_delta.sub_block_gate_min(g)) == pytest.approx(
        gated_delta.GATE_LOWER_BOUND * (gated_delta.SUB_BLOCK - 1))
    _assert_the_recurrence(
        (q, k, v, g, beta),
        lambda *a: gated_delta_rule(*a, interpret=interpret))
    # bf16 operands hold the same exponents.
    o, state = gated_delta_rule(*(t.astype(jnp.bfloat16) for t in (q, k, v)),
                                g, beta, interpret=interpret)
    assert bool(jnp.isfinite(o.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(state).all())


def test_rows_do_not_mix():
    args = _inputs(17, 2, 48)
    o, state = gated_delta_rule(*args, chunk=16)
    o1, state1 = gated_delta_rule(*(t[1:] for t in args), chunk=16)
    np.testing.assert_allclose(o[1:], o1, atol=1e-6)
    np.testing.assert_allclose(state[1:], state1, atol=1e-6)


@pytest.mark.parametrize("wrong", ["g", "beta"])
def test_gates_of_another_shape_are_refused(wrong):
    q, k, v, g, beta = _inputs(19, 1, 16)
    if wrong == "g":
        g = g[..., :D_K - 1]
    else:
        beta = jnp.broadcast_to(beta[..., None], g.shape)
    with pytest.raises(ValueError, match="a head and channel of the key"):
        gated_delta_rule(q, k, v, g, beta, chunk=16)


def _shapes(jaxpr):
    for eqn in jaxpr.eqns:
        for var in list(eqn.invars) + list(eqn.outvars):
            if hasattr(var, "aval") and hasattr(var.aval, "shape"):
                yield var.aval.shape
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _shapes(sub)


def test_no_chunk_by_chunk_by_channel_array_is_traced():
    """Forward and backward at Ling's heads (32 x 128 / 128, two chunks of
    64) with the kernels: no array XLA sees ends in (64, 64, 128) or holds
    64 x 64 x 128 a head; the columns' operand is four copies of k, and the
    kernels take ``gamma`` as (n, b, h, chunk, d_k)."""
    sds = jax.ShapeDtypeStruct
    x = sds((1, 128, 32, 128), jnp.bfloat16)
    g = sds((1, 128, 32, 128), jnp.float32)
    beta = sds((1, 128, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, interpret=False)[0]
                           .astype(jnp.float32)), argnums=(0, 1, 2, 3, 4)))(
        x, x, x, g, beta)
    shapes = set(_shapes(jaxpr.jaxpr))
    chunk, d_k = gated_delta.CHUNK, 128
    head = 2 * 1 * 32       # chunks x rows x heads
    assert not [s for s in shapes
                if int(np.prod(s)) >= head * chunk * chunk * d_k]
    assert (2, 1, 32, 4, 64, 128) in shapes         # the columns' operand
    text = str(jaxpr)
    assert "kda_walk_fwd" in text and "kda_walk_bwd" in text


@pytest.mark.parametrize("heads, key_heads, want", [(32, 32, 8), (32, 16, 8),
                                                    (4, 4, 4)])
def test_heads_a_program_count_the_wider_gamma_block(heads, key_heads, want):
    """At 64 x 128 / 128 in bfloat16 ``gamma`` and its cotangent are 32 KB a
    head each, double-buffered: 8 of 32 heads a program where the scalar
    form takes 16; both kernels' padded estimates stay within the budget at
    the trip they take."""
    args = (heads, key_heads, 64, 128, 128, jnp.bfloat16)
    assert gated_delta._head_block(*args, True) == want
    assert gated_delta._head_block(*args) >= want
    group = heads // key_heads
    for transposed in (False, True):
        trip = gated_delta._trip_heads(want, group, 64, 128, 128,
                                       jnp.bfloat16, transposed, True)
        assert want % trip == 0 and trip % group == 0
        assert gated_delta._walk_vmem(
            want, trip, group, 64, 128, 128, jnp.bfloat16, transposed,
            True) <= gated_delta._VMEM_BUDGET


@pytest.mark.parametrize("interpret, kernel", [(True, 1), (None, 0)])
def test_the_gauges_and_the_event_name_the_form(interpret, kernel):
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    # No reset of the registry or the recorder: other files' tests in this
    # process read what their own traces announced once.
    gated_delta._announced.clear()
    before = observability.registry().snapshot()["gauges"]
    gated_delta_rule(*_inputs(23, 1, 32), chunk=16, interpret=interpret)
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["kda.heads"] == HEADS
    assert gauges["kda.key_dim"] == D_K
    assert gauges["kda.sub_block"] == 16
    assert gauges["kda.gate_lower_bound"] == -5.0
    assert gauges["kda.scan_kernel"] == kernel
    assert {k: v for k, v in gauges.items() if k.startswith("gdn.")} \
        == {k: v for k, v in before.items() if k.startswith("gdn.")}
    event = [e for e in recorder.events() if e["kind"] == "kda"][-1]
    assert "sub-blocks of 16" in event["detail"]
    assert ("Pallas kernels kda_walk_fwd" in event["detail"]) == bool(kernel)
