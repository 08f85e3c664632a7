"""The chunked gated delta rule (``ops/gated_delta.py``) against the rule one
position at a time, on the CPU in float32: outputs, final states and the
gradients of q, k, v, g and beta; lengths of one chunk, of many and of a
length the chunk does not divide; decays near 0 and near 1; write strengths
up to 2; rows that must not mix; bf16 operands within a stated tolerance.
A chunk's inverse by block products against a float64 inverse and against
the triangular solve it replaces, its closed cotangent against autodiff
through the solve, and which of the two a chunk's size takes.  The walk over
the chunks as Pallas kernels, interpreted, against the ``lax.scan`` and
against the rule one position at a time, one, two and four value heads a key
head: outputs, final state and the five gradients with a cotangent on the
final state; what the kernels are handed (no ``chunk`` x d term a value
head, q and k a key head) and how many heads a program and a trip take."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops import gated_delta
from autodist_tpu.ops.gated_delta import gated_delta_rule

HEADS, D_K, D_V = 3, 8, 12


def recurrent_gated_delta_rule(q, k, v, g, beta):
    """The rule one position at a time, in float32, as the module's
    docstring writes it: ``(o, final state)``; with fewer key heads than
    value heads, q and k repeated to them."""
    group = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(t, group, axis=2) for t in (q, k))
    b, s, h, d_k = q.shape
    q, k, v, g, beta = (jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                        for t in (q, k, v, g, beta))

    def step(state, x):
        q, k, v, g, beta = x                              # (b, h, ...)
        state = jnp.exp(g)[..., None, None] * state
        u = beta[..., None] * (v - jnp.einsum("bhkd,bhk->bhd", state, k))
        state = state + k[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkd,bhk->bhd", state, q)

    state, o = jax.lax.scan(step, jnp.zeros((b, h, d_k, v.shape[-1])),
                        (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1), state


def _inputs(seed, rows, s, decay=1.0, beta_max=2.0, dtype=jnp.float32,
            heads=HEADS, key_heads=None, d_k=D_K, d_v=D_V):
    """q and k as the mixer hands them over (unit k, q scaled by
    d_k^-1/2; ``key_heads`` of them, None: ``heads``), log decays of about
    ``-decay``, beta in (0, beta_max)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    key_heads = key_heads or heads
    q = jax.random.normal(ks[0], (rows, s, key_heads, d_k))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d_k ** -0.5
    k = jax.random.normal(ks[1], (rows, s, key_heads, d_k))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (rows, s, heads, d_v))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (rows, s, heads)))
    beta = beta_max * jax.nn.sigmoid(
        2.0 * jax.random.normal(ks[4], (rows, s, heads)))
    return tuple(t.astype(dtype) for t in (q, k, v)) + (g, beta)


def _both(args, chunk):
    """Outputs, states and the five gradients of a random projection of the
    output, chunked and one position at a time."""
    weights = jax.random.normal(jax.random.PRNGKey(9),
                                args[2].shape, jnp.float32)

    def chunked(*a):
        o, state = gated_delta_rule(*a, chunk=chunk)
        return jnp.sum(o.astype(jnp.float32) * weights), (o, state)

    def stepwise(*a):
        o, state = recurrent_gated_delta_rule(*a)
        return jnp.sum(o * weights), (o, state)

    with jax.default_matmul_precision("highest"):
        return [jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                           has_aux=True))(*args)
                for f in (chunked, stepwise)]


def _assert_close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=tol, rtol=0)


@pytest.mark.parametrize("s, chunk", [(16, 16), (64, 16), (50, 16), (64, 64),
                                      (96, 32), (7, 8)])
@pytest.mark.parametrize("decay, beta_max", [(1.0, 2.0), (0.01, 2.0),
                                             (30.0, 1.0)])
def test_the_chunked_rule_is_the_recurrence(s, chunk, decay, beta_max):
    """Decay 0.01: alpha near 1, the state grows with beta up to 2; decay
    30: alpha near 0, exp(gamma_i - gamma_j) underflows to an exact 0 and
    nothing divides by it."""
    args = _inputs(s, 2, s, decay, beta_max)
    ((_, (o, state)), grads), ((_, (o_want, state_want)), grads_want) = \
        _both(args, chunk)
    assert o.shape == (2, s, HEADS, D_V) and state.shape == (2, HEADS, D_K,
                                                             D_V)
    _assert_close(o, o_want, 5e-6)
    _assert_close(state, state_want, 5e-6)
    for name, got, want in zip("q k v g beta".split(), grads, grads_want):
        assert bool(jnp.isfinite(got).all()), name
        _assert_close(got, want, 2e-5)


def test_rows_do_not_mix():
    """Two rows together are each row alone: the state starts at zero at
    each row's first position."""
    args = _inputs(3, 2, 40)
    both, _ = gated_delta_rule(*args, chunk=16)
    for row in range(2):
        alone, _ = gated_delta_rule(*(t[row:row + 1] for t in args),
                                    chunk=16)
        np.testing.assert_allclose(both[row:row + 1], alone, atol=1e-6)
    # And a row's output does not depend on what follows a position.
    cut, _ = gated_delta_rule(*(t[:, :24] for t in args), chunk=16)
    np.testing.assert_allclose(both[:, :24], cut, atol=1e-6)


def test_the_padding_decays_nothing_and_writes_nothing():
    """50 positions in chunks of 16: the final state is the state after
    position 50, not after 64."""
    args = _inputs(4, 1, 50)
    _, state = gated_delta_rule(*args, chunk=16)
    _, want = recurrent_gated_delta_rule(*args)
    _assert_close(state, want, 5e-6)


def test_bf16_operands_stay_within_their_rounding():
    """bf16 q, k and v (the train path): every product but T's two takes
    bf16 operands and accumulates in float32, the state is carried in
    float32.  Against the float32 recurrence on the same (rounded) inputs
    the output is within 2e-2 of its largest magnitude: three bf16
    roundings (2^-9 each) through 16 chunks' worth of state."""
    args = _inputs(5, 2, 256, decay=0.05, dtype=jnp.bfloat16)
    o, _ = gated_delta_rule(*args, chunk=16)
    assert o.dtype == jnp.bfloat16
    want, _ = recurrent_gated_delta_rule(*args)
    _assert_close(o, want, 2e-2)
    grads = jax.grad(lambda *a: jnp.sum(
        gated_delta_rule(*a, chunk=16)[0].astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))(*args)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
               for g in grads)


def _alike_systems(c, shared, decay, n=24, d=96, seed=1):
    """``a`` (n, c, c) float64 as ``_chunk_terms`` makes it, from unit keys
    that share a direction of weight ``shared`` (the ill-conditioned case:
    a chunk's keys alike), beta in [1, 2], log decays of about ``-decay``."""
    r = np.random.default_rng(seed)
    common = r.standard_normal((n, 1, d))
    common /= np.linalg.norm(common, axis=-1, keepdims=True)
    k = r.standard_normal((n, c, d))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    k = shared * common + (1 - shared) * k
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = r.uniform(1.0, 2.0, (n, c))
    gamma = np.cumsum(-decay * np.log1p(np.exp(r.standard_normal((n, c)))),
                      -1)
    a = (beta[..., None] * np.exp(gamma[..., :, None] - gamma[..., None, :])
         * (k @ np.swapaxes(k, -1, -2)))
    return np.tril(a, -1)


def _solve(a):
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.scipy.linalg.solve_triangular(
        a + eye, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True)


@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
@pytest.mark.parametrize("shared", [0.7, 0.95])
@pytest.mark.parametrize("decay", [0.0, 3.0])
def test_the_block_inverse_is_the_inverse(chunk, shared, decay):
    """Float32 block products against a float64 inverse, keys alike, where
    the powers of ``a`` grow before they cancel: within 1e-5 of the
    inverse's largest entry, and the row substitution it replaces beside
    it (3e-7 to 6e-7 at 64 rows where the block form reads 1e-6 to 2e-6)."""
    a = _alike_systems(chunk, shared, decay)
    want = np.linalg.inv(np.eye(chunk) + a)
    scale = np.max(np.abs(want))
    a = jnp.asarray(a, jnp.float32)
    errors = {name: float(np.max(np.abs(np.asarray(f(a), np.float64) - want))
                    / scale)
              for name, f in (("block products",
                               jax.jit(gated_delta.unit_lower_inverse)),
                              ("triangular solve", jax.jit(_solve)))}
    assert errors["block products"] < 1e-5, errors
    assert errors["triangular solve"] < 1e-5, errors


@pytest.mark.parametrize("chunk", [8, 64])
def test_the_block_inverses_gradient_is_the_solves(chunk):
    """The closed form ``dA = -tril(T^T dT T^T, -1)`` against autodiff
    through ``solve_triangular``; nothing on or above the diagonal, which
    neither form reads."""
    a = jnp.asarray(_alike_systems(chunk, 0.7, 0.0), jnp.float32)
    weights = jax.random.normal(jax.random.PRNGKey(2), a.shape)
    got, want = (jax.jit(jax.grad(lambda a: jnp.sum(f(a) * weights)))(a)
                 for f in (gated_delta.unit_lower_inverse, _solve))
    _assert_close(got, want, 1e-5)
    assert not np.triu(np.asarray(got)).any()


def test_a_chunk_that_is_no_power_of_two_takes_the_solve():
    """96 positions in chunks of 48: the fallback, still the recurrence."""
    args = _inputs(7, 2, 96)
    ((_, (o, state)), grads), ((_, (o_want, state_want)), grads_want) = \
        _both(args, 48)
    _assert_close(o, o_want, 5e-6)
    _assert_close(state, state_want, 5e-6)
    for got, want in zip(grads, grads_want):
        _assert_close(got, want, 2e-5)


@pytest.mark.parametrize("chunk, solves", [(64, False), (48, True)])
def test_no_triangular_solve_is_traced_for_a_power_of_two(chunk, solves):
    """Forward, recomputation and backward of the rule at the train path's
    chunk are products only; a chunk of 48 still solves."""
    args = _inputs(8, 1, 192)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk)[0]),
        argnums=(0, 1, 2, 3, 4)))(*args)
    assert ("triangular_solve" in str(jaxpr)) == solves
    assert ("block products" in gated_delta.inverse_form(chunk)) != solves


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "rows, s, chunk, heads, key_heads, d_k, d_v, head_block",
    [(1, 64, 16, 4, 4, 8, 8, 4),        # equal heads
     (1, 64, 16, 4, 2, 8, 8, 4),        # two value heads a key head
     (1, 48, 16, 6, 6, 8, 8, 3),        # heads no multiple of 8, 3 a program
     (1, 64, 16, 4, 4, 8, 24, 2),       # d_k != d_v
     (1, 50, 16, 4, 2, 16, 8, 4),       # a length the chunk does not divide
     (2, 40, 8, 6, 3, 8, 12, 6),        # two rows
     (1, 64, 16, 8, 2, 8, 8, 4),        # four a key head, one key head a
     (1, 48, 16, 8, 4, 8, 12, 4)],      # program; two key heads a program
    ids=["equal-heads", "grouped", "six-heads-by-three", "dk-not-dv",
         "padded-length", "two-rows", "group-of-four", "two-groups-a-program"])
def test_the_interpreted_kernels_are_the_scan(
        rows, s, chunk, heads, key_heads, d_k, d_v, head_block, dtype,
        monkeypatch):
    """The two kernels under the Pallas interpreter against the ``lax.scan``
    they replace on a TPU, on the same inputs: ``o``, the final state and
    the gradients of q, k, v, g and beta of a loss that reads both, so that
    the final state's cotangent is not zero.  Forward the two run the same
    three functions a head (``_head_step``'s); backward the kernel's is written out
    and the scan's is autodiff's, so in float32 they agree to rounding (the
    sums associate differently), and in bfloat16 the scan's transpose rounds
    each of a step's additions to the state's cotangent to bfloat16 where
    the kernel sums them in float32: within bfloat16's rounding."""
    if head_block != heads:     # what the budget gives at the cells' widths
        monkeypatch.setattr(gated_delta, "_head_block",
                            lambda *_: head_block)
    args = _inputs(s + heads, rows, s, dtype=dtype, heads=heads,
                   key_heads=key_heads, d_k=d_k, d_v=d_v)
    assert gated_delta.walk_form(True, heads, key_heads, chunk, d_k, d_v,
                                 dtype)[:2] == (True, head_block)
    ((_, (o, state)), grads), ((_, (o_want, state_want)), grads_want) = \
        _walked(args, chunk, True), _walked(args, chunk, None)
    assert o.shape == (rows, s, heads, d_v) and o.dtype == dtype
    assert state.shape == (rows, heads, d_k, d_v)
    _assert_close(o, o_want, 1e-6)
    _assert_close(state, state_want, 1e-6)
    for name, got, want in zip("q k v g beta".split(), grads, grads_want):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        _assert_close(got, want, 5e-6 if dtype == jnp.float32 else 2e-2)


def _walked(args, chunk, interpret):
    """``((loss, (o, state)), the five gradients)`` of a loss that reads
    ``o`` and the final state, the walk by ``interpret``; the recurrence one
    position at a time where that is ``"recurrence"``."""
    v = args[2]
    w_o, w_s = (jax.random.normal(jax.random.PRNGKey(i), shape)
                for i, shape in ((1, v.shape), (2, (
                    v.shape[0], v.shape[2], args[0].shape[3], v.shape[3]))))

    def loss(*a):
        o, state = (recurrent_gated_delta_rule(*a)
                    if interpret == "recurrence" else
                    gated_delta_rule(*a, chunk=chunk, interpret=interpret))
        return (jnp.sum(o.astype(jnp.float32) * w_o)
                + jnp.sum(state * w_s)), (o, state)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("decay", [1.0, 30.0], ids=["decay-1", "decay-30"])
@pytest.mark.parametrize("key_heads", [4, 2, 1],
                         ids=["ratio-1", "ratio-2", "ratio-4"])
def test_the_interpreted_kernels_are_the_recurrence(key_heads, decay, dtype):
    """The kernels, interpreted, against the rule one position at a time in
    float32 on the same (rounded) inputs, one, two and four value heads a key
    head, 50 positions in chunks of 16 (the last chunk padded), decays of
    about 1 and near 0 (``exp(gamma)`` underflows to an exact 0 inside a
    chunk, and ``U = T diag(beta) V`` there): ``o``, the final state and the
    five gradients with a cotangent on the final state.  float32 to
    rounding; bfloat16 operands within their rounding through four chunks."""
    args = _inputs(11 + key_heads, 2, 50, decay=decay, beta_max=1.0,
                   dtype=dtype, heads=4, key_heads=key_heads)
    ((_, (o, state)), grads), ((_, (o_want, state_want)), grads_want) = \
        _walked(args, 16, True), _walked(args, 16, "recurrence")
    tol, grad_tol = (5e-6, 2e-5) if dtype == jnp.float32 else (2e-2, 4e-2)
    _assert_close(o, o_want, tol)
    _assert_close(state, state_want, tol)
    for name, got, want in zip("q k v g beta".split(), grads, grads_want):
        assert bool(jnp.isfinite(got.astype(jnp.float32)).all()), name
        assert got.shape == want.shape, name
        _assert_close(got, want, grad_tol)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, but
    a ``pallas_call``'s own body (the kernels loop over a program's key
    heads with ``fori_loop``, a ``scan`` in there)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


def test_a_trace_takes_both_kernels_and_no_scan_where_they_engage():
    """``grad`` of the rule with the kernels: two ``pallas_call``s (a third
    under ``jax.checkpoint``, which runs the walk twice) and no ``scan``
    around them; off TPU with nothing asked, the scan and no kernel; a chunk
    of no whole 8-row tiles declines."""
    args = _inputs(8, 1, 64)

    def trace(**kw):
        return jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(gated_delta_rule(*a, **kw)[0]),
            argnums=(0, 1, 2, 3, 4)))(*args)

    def traced(**kw):
        return str(trace(**kw))
    kernels, scan = traced(chunk=16, interpret=True), traced(chunk=16)
    assert "gdn_walk_fwd" in kernels and "gdn_walk_bwd" in kernels
    assert "scan" not in {e.primitive.name for e in _equations(
        trace(chunk=16, interpret=True).jaxpr)}
    # How many arrays each call writes: o, the chunks' states, the final
    # state forward; transposed the cotangents of T, M * Q K^T, q, k, v and
    # the two gates.  Under jax.checkpoint
    # (``TransformerConfig(recompute="linear_mixer")``) the forward pass
    # proper saves no states: they are written when the backward pass runs
    # the walk again (``optimize_remat``).
    def writes(jaxpr):
        return [line.count("ShapedArray") for line in jaxpr.splitlines()
                if "out_avals=" in line]
    assert writes(kernels) == [3, 7]
    assert writes(str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(jax.checkpoint(
            lambda *a: gated_delta_rule(*a, chunk=16, interpret=True))(*a)[0]),
        argnums=(0, 1, 2, 3, 4)))(*args))) == [2, 3, 7]
    assert "scan[" in scan and "pallas_call" not in scan
    assert "pallas_call" not in traced(chunk=12, interpret=True)
    assert gated_delta.walk_form(None, HEADS, HEADS, 16, D_K, D_V,
                                 jnp.float32)[:2] == (None, 0)


@pytest.mark.parametrize("key_heads", [4, 2], ids=["ratio-1", "ratio-2"])
def test_the_kernels_are_handed_no_chunk_by_width_term(key_heads):
    """Where the kernels engage, nothing of ``chunk`` x d a value head is
    made for them: of the arrays with a value head's (n, b, h, chunk, .)
    the walk's operands hold ``T``, ``M * Q K^T`` (chunk x chunk) and v, and
    the transposed kernel writes their cotangents; q and k go in, and their
    cotangents come out, a KEY head; the gates as rows; and no equation of
    the trace repeats or broadcasts anything to (n, b, h, chunk, d_k)."""
    n, chunk, heads, d_k, d_v = 4, 16, 4, 8, 24
    args = _inputs(8, 1, n * chunk, heads=heads, key_heads=key_heads,
                   d_k=d_k, d_v=d_v, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk, interpret=True)[0]
                           .astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))(*args)
    eqns = list(_equations(jaxpr.jaxpr))
    calls = {e.params["name"]: e for e in eqns
             if e.primitive.name == "pallas_call"}
    assert set(calls) == {"gdn_walk_fwd", "gdn_walk_bwd"}
    square, key, value, gates, state = (
        (n, 1, heads, chunk, chunk), (n, 1, key_heads, chunk, d_k),
        (n, 1, heads, chunk, d_v), (n, 1, 1, heads, chunk),
        (1, heads, d_k, d_v))
    terms = [square, square, key, key, value, gates, gates]
    assert [v.aval.shape for v in calls["gdn_walk_fwd"].invars] == terms
    assert [v.aval.shape for v in calls["gdn_walk_bwd"].invars] \
        == terms + [(n,) + state, value, state]
    assert [v.aval.shape for v in calls["gdn_walk_bwd"].outvars] == terms
    assert [v.aval.dtype for v in calls["gdn_walk_bwd"].outvars] \
        == [jnp.float32] + [jnp.bfloat16] * 4 + [jnp.float32] * 2
    # With equal heads the chunked q and k have a value head's shape
    # themselves: then it is only a repeat or a broadcast that must not be.
    wide = (n, 1, heads, chunk, d_k)
    made = {e.primitive.name for e in eqns for v in e.outvars
            if getattr(v.aval, "shape", None) == wide}
    assert not made & ({"gather", "broadcast_in_dim"} if key_heads == heads
                       else made), made


@pytest.mark.parametrize("heads, key_heads, d_k, d_v, want, trips",
                         [(32, 16, 128, 128, 16, (8, 4)),
                          (30, 30, 96, 192, 10, (5, 5)),
                          (6, 6, 8, 12, 6, (6, 6)), (8, 2, 8, 12, 8, (8, 8)),
                          (1, 1, 2048, 2048, 0, None),
                          (4, 1, 1024, 1024, 0, None)])
def test_heads_a_program_follow_the_shapes(heads, key_heads, d_k, d_v, want,
                                           trips):
    """The two cells' shapes, two toys', and a head (and a group of four)
    whose blocks pass the budget alone (the scan then); a program's heads
    are whole groups of a key head's, and so are the heads a trip of its
    loop takes, forward and transposed: eight at most, fewer where their
    staging would pass the budget."""
    group = heads // key_heads
    assert gated_delta._head_block(heads, key_heads, 64, d_k, d_v,
                                   jnp.bfloat16) == want
    assert want == 0 or (heads % want == 0 and want % group == 0)
    if want:
        assert trips == tuple(
            gated_delta._trip_heads(want, group, 64, d_k, d_v, jnp.bfloat16,
                                    transposed)
            for transposed in (False, True))
        assert all(want % trip == 0 and trip % group == 0 for trip in trips)


@pytest.mark.parametrize("interpret, kernel", [(True, 1), (None, 0)])
def test_the_gauges_and_the_event_name_the_form_that_ran(interpret, kernel):
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    observability.reset()
    gated_delta._announced.clear()
    gated_delta_rule(*_inputs(6, 2, 40), chunk=16, interpret=interpret)
    (event,) = [e for e in recorder.events() if e["kind"] == "gdn"]
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["gdn.scan_kernel"] == kernel
    assert gauges["gdn.scan_head_block"] == (HEADS if kernel else 0)
    # Three chunks of 16 a row: T in float32 and M * Q K^T, q and k, v and
    # the two gates, float32 inputs; the same terms either way.
    assert gauges["gdn.walk_term_bytes_per_row"] == 3 * 16 * HEADS * (
        16 * 8 + (2 * D_K + D_V) * 4 + 2 * 4)
    assert gated_delta.WALK_TERMS in event["detail"]
    assert "U = T diag(beta) (V - diag(exp gamma) K S_0)" \
        in gated_delta.WALK_TERMS
    assert ("walk over the chunks: Pallas kernels, 3 heads a program, the "
            "state in VMEM (interpret=True requested)" if kernel else
            "walk over the chunks: lax.scan (backend is cpu; the kernels "
            "compile for tpu)") in event["detail"]


def test_the_trace_announces_the_rule_once_a_shape():
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    observability.reset()
    gated_delta._announced.clear()
    args = _inputs(6, 2, 40)
    gated_delta_rule(*args, chunk=16)
    gated_delta_rule(*args, chunk=16)
    events = [e for e in recorder.events() if e["kind"] == "gdn"]
    assert len(events) == 1
    assert "3 chunks of 16 a row" in events[0]["detail"]
    assert gated_delta.BACKWARD in events[0]["detail"]
    assert "jax.checkpoint" in gated_delta.BACKWARD
    assert "the inverse's own cotangent" in gated_delta.BACKWARD
    assert ("inverse: block products level by level from 1 x 1, float32, "
            "vector work with the systems along the lanes; its cotangent "
            "closed" in events[0]["detail"])
    assert "triangular solve" in gated_delta.inverse_form(48)
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["gdn.heads"] == HEADS and gauges["gdn.chunk"] == 16
    assert gauges["gdn.chunks_per_row"] == 3
    assert gauges["gdn.state_bytes_per_row"] == HEADS * D_K * D_V * 4


# ---------------------------------------------------------------------------
# a scalar decay traces the program it traced before the rule took a decay a
# channel


def _traced_rule(heads, key_heads, d_k, d_v, interpret):
    """The text of ``grad`` of the rule at a cell's heads over two chunks
    (bf16 operands, float32 gates, cotangents on ``o`` and on the final
    state; nothing runs), the addresses of the functions it names struck."""
    import hashlib
    import re
    sds = jax.ShapeDtypeStruct
    qk = sds((1, 128, key_heads, d_k), jnp.bfloat16)
    v = sds((1, 128, heads, d_v), jnp.bfloat16)
    gate = sds((1, 128, heads), jnp.float32)

    def f(*a):
        o, state = gated_delta_rule(*a, interpret=interpret)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(state)

    text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(
        qk, qk, v, gate, gate))
    return hashlib.sha1(re.sub(r"0x[0-9a-f]+", "0x", text).encode()) \
        .hexdigest()[:16]


# Pinned on PR 46's tree (2afff19), before ``ops/gated_delta.py`` took a decay
# a channel: the whole traced program, terms, kernels' bodies (or the scan's
# step) and their transposes, of the two cells that run the scalar rule.
_SCALAR_DECAY = {
    ("olmo-hybrid-7b.train-s4096", False): (
        (30, 30, 96, 192), "07bb6de77f1d20af"),
    ("olmo-hybrid-7b.train-s4096", None): (
        (30, 30, 96, 192), "e5ee061208f42c62"),
    ("qwen3-next-80b-a3b.train-s8192", False): (
        (32, 16, 128, 128), "2987eb1646415507"),
    ("qwen3-next-80b-a3b.train-s8192", None): (
        (32, 16, 128, 128), "02ed9c2eb1deb2c5"),
}


@pytest.mark.parametrize("cell, interpret", list(_SCALAR_DECAY))
def test_a_scalar_decay_traces_the_program_it_traced(cell, interpret):
    """With ``g`` a scalar a head and position the rule's traced program is
    the parent's, text for text: the kernels (``interpret=False``: the form
    the chip runs) and the scan, at the heads of the two cells whose decay is
    a scalar, so the vector form cannot move either."""
    shape, want = _SCALAR_DECAY[cell, interpret]
    assert _traced_rule(*shape, interpret) == want
