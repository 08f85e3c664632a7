"""Flash attention kernel vs the dense reference (interpret mode on CPU)."""
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex_core

from autodist_tpu.graph_item import _sub_jaxprs
from autodist_tpu.models import layers as L
from autodist_tpu.ops.flash_attention import flash_attention, _dense_reference

# ``autodist_tpu.ops`` exports the function under the module's name.
fa = importlib.import_module("autodist_tpu.ops.flash_attention")


def _qkv(b=2, h=2, s=64, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d), jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal, 16, 16, 0, True)  # interpret
    expect = _dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_mha_reference():
    q, k, v = _qkv(s=32)
    got = flash_attention(q, k, v, True, 8, 8, 0, True)
    expect = L.dot_product_attention(q, k, v, L.causal_mask(q.shape[2]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(s=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, 8, 8, 0, True) ** 2).sum()

    def loss_dense(q, k, v):
        return (_dense_reference(q, k, v, causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_q_offset_matches_shifted_global_positions():
    """q_offset masks as if q were a shard of a longer sequence."""
    q, k, v = _qkv(s=32)
    qs = q[:, :, 16:, :]
    got = flash_attention(qs, k, v, True, 8, 8, 16, True)
    full = _dense_reference(q, k, v, True)[:, :, 16:, :]
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_kernel_goes_under_a_full_manual_region_on_a_mesh():
    """On a mesh of several devices the kernel call is wrapped in a
    shard_map over every free axis (batch over data, heads over model) —
    jax will not partition a Mosaic kernel itself — and an axis that can
    split neither raises instead of replicating the work."""
    from jax.sharding import Mesh
    from autodist_tpu.parallel import context as parallel_ctx

    q, k, v = _qkv(b=4, h=2, s=32)

    def kernel(ql, kl, vl):
        # Local views: batch 4 / data 4, heads 2 / model 2.
        assert ql.shape == (1, 1, 32, 16)
        return flash_attention(ql, kl, vl, True, 8, 8, 0, True)

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    with parallel_ctx.use(parallel_ctx.ParallelContext(mesh)):
        got = jax.jit(lambda q, k, v: fa._under_full_manual(
            kernel, q, k, v))(q, k, v)
        grads = jax.jit(jax.grad(lambda q, k, v: (fa._under_full_manual(
            kernel, q, k, v) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_reference(q, k, v, True)),
                               rtol=2e-5, atol=2e-5)
    want = jax.grad(lambda q, k, v: (_dense_reference(q, k, v, True) ** 2)
                    .sum(), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)

    seq_mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "seq"))
    with parallel_ctx.use(parallel_ctx.ParallelContext(seq_mesh)):
        with pytest.raises(NotImplementedError, match="'seq'"):
            fa._under_full_manual(kernel, q, k, v)


# ---------------------------------------------------------------------------
# dtype discipline: f32 lives in the accumulators and the softmax statistics;
# what enters the MXU and what leaves a kernel has the inputs' dtype.


def _eqns(jaxpr, kernels=True):
    """Every equation of a jaxpr, nested ones (jit and custom_vjp bodies,
    a kernel's ``pl.when`` branches) included; without ``kernels`` what lies
    around the ``pallas_call``s only, not their bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not kernels:
            continue
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub, kernels)
        for param in eqn.params.values():    # shard_map's is a bare Jaxpr
            if hasattr(param, "eqns"):
                yield from _eqns(param, kernels)


def _force_rows(monkeypatch, rows):
    """Steer the one rule that picks the rows a program: the kernels take
    no argument for it."""
    rule = fa._rows_per_program

    def forced(*args):
        g, vmem = rule(*args)
        return rows, vmem // g * rows
    monkeypatch.setattr(fa, "_rows_per_program", forced)
    return fa


def _grad_jaxpr(dtype, causal=True, b=2):
    q, k, v = (x.astype(dtype) for x in _qkv(b=b, s=32))

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal, 8, 8, 0, True)
        return (o.astype(jnp.float32) ** 2).sum()

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr


def _kernels(jaxpr):
    return {e.params["name"]: e for e in _eqns(jaxpr)
            if e.primitive.name == "pallas_call"}


def test_bf16_gradients_leave_the_kernels_as_bf16():
    """The backward kernels store their f32 accumulators in the inputs'
    dtype themselves: no f32 gradient array, and no cast of one, outside."""
    jaxpr = _grad_jaxpr(jnp.bfloat16)
    kernels = _kernels(jaxpr)
    assert set(kernels) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    results = set()
    for name in ("flash_bwd_dq", "flash_bwd_dkv"):
        for out in kernels[name].outvars:
            assert out.aval.dtype == jnp.bfloat16, (name, out.aval)
            results.add(out)
    assert len(results) == 3
    # Whatever consumes a result reshapes it (to b, h, s, d): none is cast.
    consumers = 0
    for eqn in _eqns(jaxpr):
        if any(v in results for v in eqn.invars
               if not isinstance(v, jex_core.Literal)):
            assert eqn.primitive.name == "reshape", eqn
            results.update(eqn.outvars)
            consumers += 1
    assert consumers == 3


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_bf16_flash_matches_f32_dense(causal):
    """bf16 inputs, several blocks a side: forward and the three gradients
    against the dense reference computed in f32 from the same values."""
    qf, kf, vf = (x.astype(jnp.bfloat16).astype(jnp.float32)
                  for x in _qkv(s=64, d=32, seed=3))
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))
    w = jax.random.normal(jax.random.PRNGKey(9), qf.shape, jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, 16, 32, 0, True)
        return (o.astype(jnp.float32) * w).sum()

    def loss_dense(q, k, v):
        return (_dense_reference(q, k, v, causal) * w).sum()

    got = flash_attention(qb, kb, vb, causal, 16, 32, 0, True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_dense_reference(qf, kf, vf, causal)),
        rtol=2e-2, atol=2e-2)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(qf, kf, vf)
    for a, b in zip(gf, gd):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


def test_block_attn_bwd_keeps_f32_partials_for_bf16_inputs():
    """Ring attention sums per-hop partials in f32, so its per-block
    kernels are asked for f32 results whatever the inputs are."""
    from autodist_tpu.ops.flash_attention import (block_attn_bwd,
                                                  block_attn_fwd)
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(s=32))
    o, lse = block_attn_fwd(q, k, v, True, 0, 0, 8, 8, True)
    assert o.dtype == jnp.float32 and lse.dtype == jnp.float32
    do = jnp.ones_like(q)
    delta = (do.astype(jnp.float32) * o).sum(-1, keepdims=True)
    grads = block_attn_bwd(q, k, v, do, lse, delta, True, 0, 0, 8, 8, True)
    assert [g.dtype for g in grads] == [jnp.float32] * 3
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]


@pytest.mark.parametrize("rows", [1, 6], ids=["one-row", "six-rows"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kernel,products", [("flash_fwd", 2),
                                             ("flash_bwd_dq", 3),
                                             ("flash_bwd_dkv", 4)])
def test_mxu_operands_follow_the_inputs(kernel, products, dtype, rows,
                                        monkeypatch):
    """All nine products take operands of the inputs' dtype and accumulate
    in f32: with bf16 inputs none runs multi-pass on the MXU, with f32
    inputs the casts are the identity.  A program of six rows loops over
    them on the device: its code holds one row's products, not six."""
    _force_rows(monkeypatch, rows)
    body = _kernels(_grad_jaxpr(dtype, b=3))[kernel].params["jaxpr"]
    dots = [e for e in _eqns(body) if e.primitive.name == "dot_general"]
    assert len(dots) == products
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype], eqn
        assert eqn.outvars[0].aval.dtype == jnp.float32, eqn


# ---------------------------------------------------------------------------
# several (batch, head) rows a program: the rule, and what it may not change


def _fwd_and_bwd(fa, q, k, v, do, causal, block_q, block_k):
    """(o, lse, dq, dk, dv) of the interpreted kernels, compiled without
    LLVM's optimisations: with them XLA's CPU backend gives a program of
    several rows other code than a program of one, and one row's ``lse``
    then differs in its last bit (bf16, causal, one k block)."""
    def run(q, k, v, do):
        o, lse = fa._flash_fwd(q, k, v, causal, block_q, block_k, 0, 0, True)
        delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
            .sum(-1, keepdims=True)
        return (o, lse) + tuple(fa._flash_bwd(
            q, k, v, do, lse, delta, causal, block_q, block_k, 0, 0, True))
    return jax.jit(run).lower(q, k, v, do).compile(
        compiler_options={"xla_backend_optimization_level": 0})(q, k, v, do)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("block_k", [32, 8], ids=["one-k-block",
                                                  "four-k-blocks"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("rows", [2, 3, 6])
def test_rows_a_program_change_no_bit(rows, causal, block_k, dtype,
                                      monkeypatch):
    """A row's arithmetic does not depend on how many rows share its
    program: o, lse, dq, dk, dv of the interpreted kernels at G = 2 (one
    step of two rows), 3 (a loop of three) and 6 (a loop of three steps of
    two rows) are those of G = 1, with the accumulators carried across k
    blocks too."""
    q, k, v, do = (x.astype(dtype) for x in
                   _qkv(b=3, s=32, seed=5) + (_qkv(b=3, s=32, seed=6)[0],))
    fa = _force_rows(monkeypatch, 1)
    want = _fwd_and_bwd(fa, q, k, v, do, causal, 16, block_k)
    monkeypatch.undo()
    fa = _force_rows(monkeypatch, rows)
    # Two of these small tiles fill a step of the loop.
    monkeypatch.setattr(fa, "_STEP_TILE", 2 * 16 * block_k)
    got = _fwd_and_bwd(fa, q, k, v, do, causal, 16, block_k)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)
    assert np.isfinite(np.asarray(got[0], np.float32)).all()


# ---------------------------------------------------------------------------
# the packed layout: (batch, s, heads x d) as the projections write it


def _swap(x):
    return x.transpose(0, 2, 1, 3)


def _attend(hook, q, k, v):
    """What ``layers.mha`` makes of a hook for q/k/v of (batch, seq, heads,
    head_dim): the hook's own function of that layout where it gives one for
    these heads, else the hook behind the head split's transposes."""
    packed = hook.bshd(*q.shape[2:])
    if packed is not None:
        return packed(q, k, v)
    return _swap(hook(_swap(q), _swap(k), _swap(v)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("shape,blocks,rows", [
    ((8, 128, 2, 64), (128, 128), 16),     # s = 128: eight batch rows grouped
    ((1, 128, 4, 64), (64, 32), 2),        # one batch row, two lane blocks
    ((4, 128, 2, 128), (128, 128), 4),     # d = 128: one head a block
    ((1, 256, 2, 128), (128, 64), 1),      # ... and one row a program
], ids=["d64-grouped", "d64-one-row", "d128-grouped", "d128-one-row"])
def test_packed_matches_split(shape, blocks, rows, causal, dtype):
    """The kernels on (batch, s, heads x d), two heads a 128-lane block at
    d = 64 and one at d = 128, against the same kernels on the transposed
    (batch, heads, s, d): o and the three gradients, with batch rows sharing
    a program and not, one block a side and several.  A head's product over
    the block's whole width adds exact zeros, so the two agree to the
    rounding of a sum's order."""
    b, s, h, d = shape
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                  for kk in ks)
    picked, rule = [], fa._rows_per_program

    def recording(*args):
        picked.append(rule(*args)[0])
        return rule(*args)

    def packed(q, k, v):
        return fa._flash_attention_packed(q, k, v, causal, *blocks, True)

    def split(q, k, v):
        return _swap(flash_attention(_swap(q), _swap(k), _swap(v), causal,
                                     *blocks, 0, True))

    def grads(attn):
        return jax.grad(lambda *x: (attn(*x).astype(jnp.float32)
                                    * w.astype(jnp.float32)).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    fa._rows_per_program = recording
    try:
        got = (packed(q, k, v),) + grads(packed)
    finally:
        fa._rows_per_program = rule
    assert picked[-3:] == [rows] * 3, picked
    want = (split(q, k, v),) + grads(split)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for name, a, e in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == shape and a.dtype == dtype, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(e, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32),
        np.asarray(_swap(_dense_reference(_swap(q), _swap(k), _swap(v),
                                          causal)), np.float32),
        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("heads,d,packed", [
    (2, 64, True), (3, 64, False), (25, 64, False), (1, 128, False),
    (16, 128, False), (8, 16, True), (4, 16, False)])
def test_hook_takes_the_layout_the_shape_gives(heads, d, packed, monkeypatch):
    """``make_flash_attn_fn``'s hook for heads of a shape: where several
    fill a block of 128 lanes it gives ``mha`` a function of (batch, s,
    heads, d), the kernels read that layout and the jaxpr has no transpose
    around them (inside, the forward turns its statistics once a q block to
    the rows that cross HBM); an odd count of 64-wide heads (gpt2-xl's 25) does not
    pack, a 128-wide head (OLMoE's) is a block alone and gains nothing, and
    there the hook gives None and ``mha`` transposes as ever."""
    hook = _interpreted_hook(monkeypatch, causal=True)
    assert (hook.bshd(heads, d) is not None) == packed
    assert (fa._heads_per_block(heads, d) is not None) == packed
    x = jax.ShapeDtypeStruct((2, 32, heads, d), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *x: _attend(hook, *x))(x, x, x).jaxpr
    kernel = _kernels(jaxpr)["flash_fwd"]
    transposes = [e for e in _eqns(jaxpr, kernels=False)
                  if e.primitive.name == "transpose"]
    if packed:
        assert kernel.outvars[0].aval.shape == (2, 32, heads * d)
        assert not transposes
    else:
        assert kernel.outvars[0].aval.shape == (2 * heads, 32, d)
        assert len(transposes) == 4
    q, k, v = (jax.random.normal(kk, x.shape) for kk in
               jax.random.split(jax.random.PRNGKey(2), 3))
    np.testing.assert_allclose(
        np.asarray(_attend(hook, q, k, v)),
        np.asarray(_swap(_dense_reference(_swap(q), _swap(k), _swap(v),
                                          True))), rtol=2e-5, atol=2e-5)


def test_hook_in_its_own_layout_falls_back_to_the_dense_reference():
    """Off the TPU (and with a mask, or a length no block divides) the
    hook's (batch, s, heads, d) function is the dense reference behind the
    transposes it reads through, as the hook itself is."""
    hook = fa.make_flash_attn_fn(causal=True)
    q, k, v = (_swap(x) for x in _qkv(h=2, s=32, d=64))
    want = _swap(_dense_reference(_swap(q), _swap(k), _swap(v), True))
    packed = hook.bshd(2, 64)
    np.testing.assert_array_equal(np.asarray(packed(q, k, v)),
                                  np.asarray(want))
    mask = L.causal_mask(32)
    np.testing.assert_allclose(np.asarray(packed(q, k, v, mask)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_hook_keeps_the_split_layout_where_a_mesh_axis_splits_the_heads(
        monkeypatch):
    """Heads split over ``model`` are dimension 1 of (batch, heads, s, d)
    under ``_under_full_manual``: the hook gives no function of its own
    layout there; over ``data`` alone it does, the batch split on dimension
    0."""
    from jax.sharding import Mesh
    from autodist_tpu.parallel import context as parallel_ctx
    hook = _interpreted_hook(monkeypatch)
    q, k, v = (_swap(x) for x in _qkv(b=4, h=8, s=32))    # (4, 32, 8, 16)
    want = _swap(_dense_reference(_swap(q), _swap(k), _swap(v), False))
    devices = np.array(jax.devices())
    for mesh, inner in (
            (Mesh(devices.reshape(4, 2), ("data", "model")), (4, 32, 16)),
            (Mesh(devices[:4], ("data",)), (1, 32, 128))):
        with parallel_ctx.use(parallel_ctx.ParallelContext(mesh)):
            # A function of its own a mesh: jax keeps a function's trace.
            jaxpr = jax.make_jaxpr(lambda *x: _attend(hook, *x))(q, k, v).jaxpr
            got = jax.jit(lambda *x: _attend(hook, *x))(q, k, v)
        # Local views: batch 4 / data 4, and heads 8 / model 2 where split.
        assert _kernels(jaxpr)["flash_fwd"].outvars[0].aval.shape == inner
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_mha_transposes_nothing_for_a_hook_that_takes_its_layout(monkeypatch):
    """``layers.mha`` with the flash hook against the dense reference, rotary
    positions and QK-norm on as the OLMoE block sets them: outputs and every
    parameter's gradient agree, and the packed path's jaxpr, forward and
    backward, transposes nothing the size of q, k, v or o (the row
    statistics' (batch, s, heads) still turn to (batch, heads, s))."""
    heads, d, b, s = 2, 64, 2, 32
    dim = heads * d
    p = L.mha_init(jax.random.PRNGKey(0), dim, heads, use_bias=False,
                   qk_norm=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, dim))
    rope = L.rope_tables(s, d)
    hook = _interpreted_hook(monkeypatch, causal=True)

    def dense(q, k, v, mask):
        return _dense_reference(q, k, v, True)

    def loss(attn_fn):
        return lambda p, x: (L.mha(p, x, heads, attn_fn=attn_fn, rope=rope)
                             ** 2).sum()
    np.testing.assert_allclose(
        np.asarray(L.mha(p, x, heads, attn_fn=hook, rope=rope)),
        np.asarray(L.mha(p, x, heads, attn_fn=dense, rope=rope)),
        rtol=1e-4, atol=1e-4)
    got = jax.grad(loss(hook), argnums=(0, 1))(p, x)
    want = jax.grad(loss(dense), argnums=(0, 1))(p, x)
    for a, e in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-3, atol=2e-3)
    jaxpr = jax.make_jaxpr(jax.grad(loss(hook), argnums=(0, 1)))(p, x).jaxpr
    assert set(_kernels(jaxpr)) == {"flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"}
    for eqn in _eqns(jaxpr, kernels=False):
        if eqn.primitive.name == "transpose":
            size = max(v.aval.size for v in eqn.invars)
            assert size < b * s * dim or eqn.invars[0].aval.ndim == 2, eqn
    # The same layer with a hook that does not say so is transposed as ever.
    plain = jax.make_jaxpr(loss(dense))(p, x).jaxpr
    assert sum(e.primitive.name == "transpose" and e.invars[0].aval.ndim == 4
               for e in _eqns(plain)) == 4


# (batch, heads, seq, head width) of a kernel call, a chip, the layout its
# shape gives with the heads a block, and the least and most rows a program:
# the five cells of the benchmark, then counts of rows that are prime.
_CELL_SHAPES = {
    "gpt2-medium.train-s1024": ((8, 16, 1024, 64), ("packed", 2), (2, 2)),
    "gpt2-xl.train-s1024-x4": ((2, 25, 1024, 64), ("split", 1), (1, 1)),
    "olmoe-1b-7b.train-s4096": ((2, 16, 4096, 128), ("split", 1), (1, 1)),
    "bert-base.mlm-s512": ((64, 12, 512, 64), ("packed", 2), (2, 2)),
    "bert-base.mlm-s128": ((256, 12, 128, 64), ("packed", 2), (8, 32)),
    "seven-rows-s128": ((7, 1, 128, 64), ("split", 1), (7, 7)),
    "thirty-seven-rows-s128": ((37, 1, 128, 64), ("split", 1), (1, 1)),
    "seven-rows-of-two-heads-s128": ((7, 2, 128, 64), ("packed", 2), (14, 14)),
    "thirty-seven-rows-of-two-heads-s128": ((37, 2, 128, 64), ("packed", 2),
                                            (2, 2)),
}
_plans = {}


def _interpreted_hook(monkeypatch=None, causal=False):
    """``make_flash_attn_fn``'s hook with the kernels interpreted: the hook
    asks the backend, which reads ``cpu`` here, and takes no argument for
    it."""
    if monkeypatch is not None:
        monkeypatch.setattr(fa, "_pallas_interpret", lambda *_: True)
    return fa.make_flash_attn_fn(causal)


def _plan(cell):
    """Trace forward and backward at the cell's shape through the hook
    ``models.layers.mha`` reads the layout off (nothing runs): ``(kernels of
    the jaxpr, [(G, vmem bytes)] as the rule returned them)``."""
    if cell not in _plans:
        (b, h, s, d), _, _ = _CELL_SHAPES[cell]
        x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
        rule, resolve, picked = fa._rows_per_program, fa._pallas_interpret, []

        def recording(*args):
            picked.append(rule(*args))
            return picked[-1]

        def loss(q, k, v):
            o = _attend(_interpreted_hook(), q, k, v)
            return (o.astype(jnp.float32) ** 2).sum()
        fa._rows_per_program = recording
        fa._pallas_interpret = lambda *_: True
        try:
            jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
        finally:
            fa._rows_per_program, fa._pallas_interpret = rule, resolve
        _plans[cell] = _kernels(jaxpr.jaxpr), picked
    return _plans[cell]


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_rows_a_program_follow_the_shape(cell):
    """The rule alone: the heads of one 128-lane block a program where a
    row's tile is as large as a long-sequence program's (one row in the split
    layout, whose kernels are the programs they were; two heads at d = 64 in
    the packed one), 8 to 32 rows at s = 128; always whole blocks' heads
    times a divisor of the batch (of batch x heads in the split layout), and
    the padded VMEM estimate within the budget."""
    (b, h, s, d), (layout, heads), (least, most) = _CELL_SHAPES[cell]
    _, picked = _plan(cell)
    assert len(picked) == 3                      # fwd, dq, dkv
    assert fa._heads_per_block(h, d) == (heads if layout == "packed" else None)
    for g, vmem in picked:
        assert least <= g <= most, picked
        assert g % heads == 0, picked
        assert (b if layout == "packed" else b * h) % (g // heads) == 0
        assert g * min(s, 512) * min(s, 1024) <= fa._MAX_TILE or g == heads
        assert 0 < vmem <= fa._VMEM_BUDGET, picked


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_grid_starts_with_programs_not_rows(cell):
    """Each layer still makes three ``pallas_call``s under their three
    names (the benchmark's trace reader finds them by name and counts
    calls); the grid's first dimension is batch x heads / G, and the
    results are (batch, s, heads x d) in the packed layout, (batch x heads,
    s, d) in the split one; in the packed layout ``lse`` leaves the forward
    with the sequence along the lanes, (batch, heads, 1, s), and enters both
    backward kernels so, ``delta`` beside it; the split layout keeps the
    (batch x heads, s, 1) its arrays had (what XLA sees of a split-layout
    step is what it was)."""
    (b, h, s, d), (layout, _), _ = _CELL_SHAPES[cell]
    kernels, picked = _plan(cell)
    assert list(kernels) == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    for (name, eqn), (g, _) in zip(kernels.items(), picked):
        grid = eqn.params["grid_mapping"].grid
        assert grid[0] == b * h // g, (name, grid, g)
        assert grid[1:] == (s // min(s, 512), s // min(s, 1024)) or \
            name == "flash_bwd_dkv" and \
            grid[1:] == (s // min(s, 1024), s // min(s, 512)), (name, grid)
        results = eqn.outvars if name != "flash_fwd" else eqn.outvars[:1]
        for out in results:
            assert out.aval.shape == ((b, s, h * d) if layout == "packed"
                                      else (b * h, s, d)), (name, out.aval)
            assert out.aval.dtype == jnp.bfloat16
    dense = (b, h, 1, s) if layout == "packed" else (b * h, s, 1)
    lse = kernels["flash_fwd"].outvars[1].aval
    assert lse.shape == dense and lse.dtype == jnp.float32
    for name in ("flash_bwd_dq", "flash_bwd_dkv"):
        # offsets, q, k, v, do, then lse and delta.
        for stat in kernels[name].invars[5:7]:
            assert stat.aval.shape == dense and stat.aval.dtype == jnp.float32


@pytest.mark.parametrize("rows,heads,tile,steps,a_step", [
    (1, 1, 128 * 128, 1, 1), (16, 1, 128 * 128, 4, 4), (6, 1, 128 * 128, 2, 3),
    (7, 1, 128 * 128, 7, 1), (2, 1, 512 * 512, 2, 1), (4, 1, 64 * 64, 1, 4),
    (1, 2, 512 * 1024, 1, 1), (8, 2, 128 * 128, 2, 4), (4, 2, 128 * 128, 1, 4),
    (3, 8, 512 * 512, 3, 1)])
def test_rows_a_step_follow_the_tile(rows, heads, tile, steps, a_step):
    """A step of a program's loop takes the rows whose score tiles fit the
    vector registers, four at 128 x 128 and one at 512 x 512: one row as an
    index (the two-dimensional arithmetic of a one-row program), several as
    a slice of the block.  Where a block holds several heads the loop takes
    them one a step, each an index on the device, inside the rows'."""
    from jax.experimental import pallas as pl
    seen = []
    jaxpr = jax.make_jaxpr(lambda: fa._for_rows(
        rows, heads, tile, lambda at, head: seen.append((at, head))))()
    loops = [e.params["length"] for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "scan"]
    assert loops == ([steps * heads] if steps * heads > 1 else [])
    # The body is traced once, whatever the rows and the heads.
    ((at, head),) = seen
    if heads == 1:
        assert head is None
    else:
        assert head.shape == () and head.dtype == jnp.int32
    if rows == 1:
        assert at == 0
    elif steps == 1:
        assert at == slice(None)
    elif a_step == 1:
        assert at.shape == () and at.dtype == jnp.int32
    else:
        assert isinstance(at, pl.Slice) and at.size == a_step


def test_padded_bytes_count_whole_lanes_and_sublanes():
    # A width of 64 occupies 128 lanes; bf16 packs 16 rows a tile; a
    # statistic's (1, block) row occupies a tile of 8 sublanes.
    assert fa._padded_bytes((128, 64), jnp.bfloat16) == 128 * 128 * 2
    assert fa._padded_bytes((128, 1), jnp.float32) == 128 * 128 * 4
    assert fa._padded_bytes((1, 512), jnp.float32) == 8 * 512 * 4
    assert fa._padded_bytes((2, 1, 128), jnp.float32) == 2 * 8 * 128 * 4
    assert fa._padded_bytes((8, 128), jnp.bfloat16) == 16 * 128 * 2
    assert fa._padded_bytes((3, 512, 128), jnp.float32) == 3 * 512 * 128 * 4


@pytest.mark.parametrize("heads,layout,lanes,shape", [
    (4, "packed", "2 heads a block of 128 lanes", "3,128,256"),
    (3, "split", "1 heads a block of 64 lanes", "9,128,64")])
def test_flash_event_and_gauges_carry_the_program_at_trace_time(
        heads, layout, lanes, shape, monkeypatch):
    """Telemetry says which layout a call's shape gave it and which program
    the rule made of it: the gauges ``flash.rows_per_program`` and
    ``flash.heads_per_block``, ``flash.stat_bytes_per_call`` (what ``lse``
    takes in HBM) and one ``flash`` event a kernel and shape, naming the row
    statistics' array, written while tracing (nothing runs); the info line of the log is the
    same text, so a head count that does not pack (three heads of 64) says
    ``split`` there."""
    import logging
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    from autodist_tpu.utils import logging as ad_logging
    observability.reset()
    monkeypatch.setattr(fa, "_logged_paths", set())
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    ad_logging.get_logger().addHandler(handler)
    x = jax.ShapeDtypeStruct((3, 128, heads, 64), jnp.bfloat16)
    hook = _interpreted_hook(monkeypatch)

    def loss(q, k, v):
        return (_attend(hook, q, k, v).astype(jnp.float32) ** 2).sum()
    try:
        for _ in range(2):
            jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    finally:
        ad_logging.get_logger().removeHandler(handler)
    gauges = observability.registry().snapshot()["gauges"]
    rows = 3 * heads if layout == "split" else 6
    assert gauges["flash.rows_per_program"] == rows
    assert gauges["flash.heads_per_block"] == (2 if layout == "packed" else 1)
    # lse in HBM: packed, 4 bytes a value, the 128 positions one row of
    # lanes; split, a 128-lane row a value.
    stat_bytes = 3 * heads * 128 * (4 if layout == "packed" else 512)
    assert gauges["flash.stat_bytes_per_call"] == stat_bytes
    stats = (f"row statistics f32[3,{heads},1,128]" if layout == "packed"
             else f"row statistics f32[{3 * heads},128,1]")
    events = [e["detail"] for e in recorder.events() if e["kind"] == "flash"]
    assert len(events) == len(set(events)) == 3
    for kernel, detail in zip(("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
                              events):
        assert detail.startswith(f"{kernel} bfloat16[{shape}] over 128 keys: "
                                 f"{layout} layout, {lanes}, "), detail
        assert f"blocks 128 x 128, G = {rows} " in detail, detail
        programs = 1 if layout == "split" else 2
        assert f" {programs} programs a call" in detail and "VMEM" in detail
        assert f"{stats} ({stat_bytes} bytes a call in HBM); " in detail, \
            detail
        assert f"flash_attention: pallas path ({detail})" in lines


# ---------------------------------------------------------------------------
# the causal walk: a program takes its k block in sub-tiles and runs those
# that hold a seen score


# The JoyAI cell's kernel call (two-product, split layout) beside the others.
_JOYAI = (1, 32, 4096, 128)


@pytest.mark.parametrize("shape,causal,want", [
    (_CELL_SHAPES["gpt2-medium.train-s1024"][0], True, (4, 3, 3)),
    (_CELL_SHAPES["gpt2-xl.train-s1024-x4"][0], True, (4, 3, 3)),
    (_CELL_SHAPES["olmoe-1b-7b.train-s4096"][0], True, (64, 36, 12)),
    (_JOYAI, True, (64, 36, 12)),
    (_CELL_SHAPES["bert-base.mlm-s512"][0], True, (1, 1, 1)),
    (_CELL_SHAPES["bert-base.mlm-s128"][0], True, (1, 1, 1)),
    (_CELL_SHAPES["bert-base.mlm-s512"][0], False, (0, 0, 0)),
    (_CELL_SHAPES["bert-base.mlm-s128"][0], False, (0, 0, 0)),
], ids=["gpt2-medium", "gpt2-xl", "olmoe", "joyai", "s512-causal",
        "s128-causal", "bert-s512", "bert-s128"])
def test_causal_plan_at_the_cells_shapes(shape, causal, want, monkeypatch):
    """Three of four 512 x 512 sub-tiles of a causal 1,024-token row are
    visited (all three through the mask: no block of such a row lies wholly
    below the diagonal), 36 of 64 at 4,096 (12 through the mask: a row's
    twelve blocks wholly below the diagonal run their 24 without it), the
    one tile of a row of 512 or 128; a call that is not causal has no walk,
    and its line and gauges say so."""
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    _, _, s, _ = shape
    block_q, block_k = min(s, 512), min(s, 1024)
    sub = fa._sub_tile(causal, block_k)
    assert sub == min(block_k, fa._SUB_TILE) == min(s, 512)
    plan = fa._causal_plan(s, s, block_q, block_k, sub) if causal \
        else (0, 0, 0)
    assert plan == want
    observability.reset()
    monkeypatch.setattr(fa, "_logged_paths", set())
    monkeypatch.setattr(fa, "_announced", set())
    layout = fa._Layout(False, 1, 1, 64)
    fa._announce("flash_fwd", layout, jax.ShapeDtypeStruct((1, s, 64),
                                                           jnp.bfloat16),
                 s, block_q, block_k, 1, 0, (0, 0) if causal else None)
    (detail,) = [e["detail"] for e in recorder.events()
                 if e["kind"] == "flash"]
    total, visited, masked = want
    assert detail.endswith(
        f"; causal: {visited} of {total} sub-tiles of {block_q} x {sub} "
        f"visited, {masked} masked" if causal
        else "; not causal: every score computed"), detail
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["flash.causal_subtiles_visited"] == visited
    assert gauges["flash.causal_subtiles_total"] == total


@pytest.mark.parametrize("q_offset,k_offset,want", [
    (0, 1024, (2, 0, 0)),        # a block wholly above the diagonal
    (0, 512, (2, 0, 0)),         # its last row still left of the first key
    (1024, 0, (2, 2, 0)),        # wholly below: all visited, none masked
    (1022, 0, (2, 2, 2)),        # one row short of that: the block is masked
    (512, 0, (2, 2, 2)),         # straddling: one below, one on the diagonal
    (0, 0, (2, 1, 1)),
    (512, 256, (2, 2, 2)),       # keys offset by half a sub-tile: both masked
    (0, -1024, (2, 2, 0)),
], ids=["above", "just-above", "below", "nearly-below", "straddling",
        "diagonal", "half-a-sub-tile", "keys-before"])
def test_causal_plan_follows_the_offsets(q_offset, k_offset, want):
    """One 512 x 1,024 program of ring attention's hop, by its offsets; the
    device's rule (traced integers through ``lax.div``) counts what the
    Python one does, and both count the sub-tiles that hold a seen score."""
    assert fa._causal_plan(512, 1024, 512, 1024, 512, q_offset,
                           k_offset) == want
    below, visited = jax.jit(lambda q, k: fa._walk(q, k, 512, 1024, 512))(
        jnp.int32(q_offset), jnp.int32(k_offset))
    assert (2, int(visited), 0 if int(below) == 2 else int(visited)) == want
    q_pos = q_offset + np.arange(512)[:, None]
    k_pos = k_offset + np.arange(1024)[None, :]
    seen = (q_pos >= k_pos).reshape(512, 2, 512)
    assert [bool(seen[:, j].any()) for j in range(2)] == \
        [j < int(visited) for j in range(2)]
    assert [bool(seen[:, j].all()) for j in range(2)] == \
        [j < int(below) for j in range(2)]


def _masked_dense(q, k, v, q_offset, k_offset):
    """Causal dense attention of (batch, heads, s, d) operands at the given
    offsets with the kernels' convention for a row that sees no key: o = 0
    and lse at the finite sentinel."""
    with jax.default_matmul_precision("highest"):
        o, lse = fa._dense_fwd(q, k, v, True, q_offset, k_offset)
    empty = lse <= fa._NEG_INF / 2
    return jnp.where(empty, 0.0, o), jnp.where(empty, fa._NEG_INF, lse)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("q_offset,k_offset", [(0, 0), (0, 16), (64, 0)],
                         ids=["diagonal", "a-q-block-sees-nothing",
                              "wholly-below"])
@pytest.mark.parametrize("packed,units", [(False, 1), (False, 4), (True, 1),
                                          (True, 2)],
                         ids=["split-one-row", "split-four-rows",
                              "packed-one-unit", "packed-two-units"])
@pytest.mark.parametrize("sub", [32, 16], ids=["two-sub-tiles",
                                               "four-sub-tiles"])
def test_sub_tiles_of_a_causal_block_match_dense(sub, packed, units, q_offset,
                                                 k_offset, dtype,
                                                 monkeypatch):
    """A 16 x 64 block walked in two and in four sub-tiles (the module's
    constant steered, as the rule for the rows is: no argument selects it),
    interpreted, against the dense reference in f32: o, lse and the three
    gradients, both layouts, one and several rows a program, and offsets
    that leave the first q block with no key to see (its rows' o is 0, their
    lse the finite sentinel, and they add nothing to dk and dv)."""
    monkeypatch.setattr(fa, "_SUB_TILE", sub)
    b, h, sq, sk, d = 2, 2, 32, 64, 64 if packed else 16
    _force_rows(monkeypatch, units * (2 if packed else 1))
    ks = jax.random.split(jax.random.PRNGKey(sub + q_offset + k_offset), 4)
    q, do = (jax.random.normal(kk, (b, h, sq, d)).astype(dtype)
             for kk in ks[:2])
    k, v = (jax.random.normal(kk, (b, h, sk, d)).astype(dtype)
            for kk in ks[2:])
    lay = _swap if packed else (lambda x: x)
    assert fa._sub_tile(True, 64) == sub
    o, lse = fa._flash_fwd(lay(q), lay(k), lay(v), True, 16, 64, q_offset,
                           k_offset, True, packed=packed)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want_o, want_lse = _masked_dense(*f32, q_offset, k_offset)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lay(o), np.float32),
                               np.asarray(want_o), **tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5 if dtype == jnp.float32 else 2e-2)
    if (q_offset, k_offset) == (0, 16):
        assert np.all(np.asarray(lse)[:, :, :16] == fa._NEG_INF)
        assert np.all(np.asarray(lay(o), np.float32)[:, :, :16] == 0)
    delta = (do.astype(jnp.float32) * lay(o).astype(jnp.float32)) \
        .sum(-1, keepdims=True)
    got = fa._flash_bwd(lay(q), lay(k), lay(v), lay(do), lse, delta, True,
                        16, 64, q_offset, k_offset, True, packed=packed)
    want = jax.grad(lambda *x: (_masked_dense(*x, q_offset, k_offset)[0]
                                * do.astype(jnp.float32)).sum(),
                    argnums=(0, 1, 2))(*f32)
    scale = 8 if dtype == jnp.bfloat16 else 1
    for g, e, x in zip(got, want, (q, k, v)):
        assert g.dtype == dtype and lay(g).shape == x.shape
        np.testing.assert_allclose(np.asarray(lay(g), np.float32),
                                   np.asarray(e), rtol=tol["rtol"],
                                   atol=scale * tol["atol"])


def _count(jaxpr):
    """Equations of a jaxpr and of every jaxpr under them, each once."""
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for param in eqn.params.values():
            for x in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    n += _count(inner)
    return n


def _traced_kernels(shape, causal, interpret=False):
    """``{kernel: (equations, hash of the body's text)}`` of the three
    kernels' traced bodies at a cell's shape, through the hook, in the form
    the chip runs (``interpret`` False: sub-tiles skipped by the device's
    offsets) or the interpreter's; nothing is lowered or run."""
    import hashlib
    b, h, s, d = shape
    resolve = fa._pallas_interpret
    fa._pallas_interpret = lambda *_: interpret
    try:
        hook = fa.make_flash_attn_fn(causal)
        x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: (_attend(hook, q, k, v).astype(jnp.float32)
                             ** 2).sum(), argnums=(0, 1, 2)))(x, x, x)
    finally:
        fa._pallas_interpret = resolve
    return {name: (_count(eqn.params["jaxpr"]), hashlib.sha1(
        str(eqn.params["jaxpr"]).encode()).hexdigest()[:16])
        for name, eqn in _kernels(jaxpr.jaxpr).items()}


# What this tree (PR 45: the statistics along the lanes, the dk/dv kernel's
# scores transposed) traces with one sub-tile a block: each kernel's
# equations, nested ones counted once, and where the walk must change nothing
# the hash of the body's text.  Not causal, that is the cell's own program;
# causal, the same kernels traced with a sub-tile as long as a k block
# (``_SUB_TILE`` = 1,024: no walk).  Re-pinned by PR 45 from c556520's (PR 31,
# the commit before the walk), whose bodies held the statistics as columns.
_ONE_SUB_TILE = {
    "bert-base.mlm-s512": {"flash_fwd": (118, "ed35ecdf0e61ac57"),
                           "flash_bwd_dq": (76, "676a540209eb6dfe"),
                           "flash_bwd_dkv": (80, "cb6753c2cb69ae69")},
    "bert-base.mlm-s128": {"flash_fwd": (119, "a7a08f618665e4be"),
                           "flash_bwd_dq": (76, "6fe581faa9fa870d"),
                           "flash_bwd_dkv": (82, "6ad2bcbc5e9d2f09")},
    "gpt2-medium.train-s1024": {"flash_fwd": (129, None),
                                "flash_bwd_dq": (87, None),
                                "flash_bwd_dkv": (91, None)},
    "gpt2-xl.train-s1024-x4": {"flash_fwd": (88, None),
                               "flash_bwd_dq": (60, None),
                               "flash_bwd_dkv": (76, None)},
    "olmoe-1b-7b.train-s4096": {"flash_fwd": (86, None),
                                "flash_bwd_dq": (60, None),
                                "flash_bwd_dkv": (76, None)},
}


@pytest.mark.parametrize("cell", list(_ONE_SUB_TILE))
def test_the_walk_leaves_a_program_of_one_sub_tile_as_it_was(cell,
                                                             monkeypatch):
    """Not causal (both BERT cells), the kernels' bodies are the pinned text,
    hash for hash: an edit that moves a non-causal body shows here.  Causal
    with two sub-tiles a block, the chip's form holds a tile's arithmetic
    three times (the block whole without the mask, whole with it, and a
    sub-tile's in the loop's body) beside the walk's own few equations, so
    what a step's tracing costs stays within three times what the same
    kernels cost with no walk (pinned, and traced again with the module's
    constant steered); the interpreter's form, one loop over every sub-tile,
    adds the loop's few equations to what the body was."""
    before = _ONE_SUB_TILE[cell]
    causal = "bert" not in cell
    now = _traced_kernels(_CELL_SHAPES[cell][0], causal)
    assert list(now) == list(before)
    if not causal:
        assert now == before, now
        return
    interpreted = _traced_kernels(_CELL_SHAPES[cell][0], causal, True)
    monkeypatch.setattr(fa, "_SUB_TILE", 1024)
    unwalked = _traced_kernels(_CELL_SHAPES[cell][0], causal)
    for name, (count, _) in before.items():
        assert unwalked[name][0] == count, (name, unwalked[name])
        assert 2 * count <= now[name][0] <= 3 * count, (name, now[name])
        assert count < interpreted[name][0] <= count + 10, interpreted


# ---------------------------------------------------------------------------
# the row statistics: the sequence along the lanes, in HBM and in VMEM


def _two_product_kernels():
    """The three kernels of the two-product form's jaxpr (nothing runs) at
    JoyAI's widths: 128 + 64 lanes a score, values of 128."""
    b, h, s, d, r, dv = 1, 2, 1024, 128, 64, 128
    shapes = ((b, h, s, d), (b, h, s, r), (b, h, s, d), (b, s, r),
              (b, h, s, dv))
    ops = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in shapes]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: fa.flash_attention_two_product(
            *a, (d + r) ** -0.5, True, 512, 1024, False)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))(*ops)
    return _kernels(jaxpr.jaxpr)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
@pytest.mark.parametrize("form", ["packed", "split", "two-product"])
def test_no_statistic_is_a_column_of_one_lane(form, kernel):
    """Packed layout: no operand, result, block or scratch of a kernel has a
    last dimension of 1 (128 lanes a value in HBM and in VMEM): ``lse`` and
    ``delta`` cross HBM as f32 with the sequence along the lanes, their
    blocks are (1, block_q) rows a head that take at most a tile of 8
    sublanes.  Split layout (and the two-product form on it): the arrays XLA
    sees keep their (batch x heads, s, 1), and they are the only ones with a
    last dimension of 1.  In both, what the forward keeps of its running
    maximum and sum is lane-replicated scratch."""
    if form == "two-product":
        eqn = _two_product_kernels()[kernel]
    else:
        cell = ("gpt2-medium.train-s1024" if form == "packed"
                else "gpt2-xl.train-s1024-x4")
        eqn = _plan(cell)[0][kernel]
    arrays = [v.aval for v in eqn.invars + eqn.outvars]
    refs = [v.aval for v in eqn.params["jaxpr"].invars]
    assert len(refs) > len(arrays)                 # blocks, then scratch
    for aval in refs[len(arrays):]:                # scratch
        assert aval.shape[-1] != 1, (kernel, aval)
    stats = [(array, ref) for array, ref in zip(arrays, refs)
             if array.dtype == jnp.float32 and 1 in array.shape[-2:]]
    # lse leaves the forward; lse and delta enter each backward kernel.
    assert len(stats) == (1 if kernel == "flash_fwd" else 2), arrays
    columns = [a for a in arrays + refs if a.shape[-1] == 1]
    if form != "packed":
        assert len(columns) == 2 * len(stats)      # an array and its block
        assert all(array.shape[-2:] == (1024, 1) and ref.shape[-2:] == (512, 1)
                   for array, ref in stats)
        return
    assert not columns
    for array, ref in stats:
        assert array.shape[-1] == 1024 and ref.shape[-2:] == (1, 512)
        true_bytes = 4 * np.prod(ref.shape)
        assert fa._padded_bytes(ref.shape, jnp.float32) <= 8 * true_bytes


# ---------------------------------------------------------------------------
# the inner sweep: a causal call's innermost grid dimension addresses only
# blocks its outer block can see


# A row's positions in the ten cells' kernel calls (blocks 512 x 1,024 capped
# by the row), in BENCHMARK.json's order.
_SWEEP_ROWS = {
    "gpt2-medium": 1024, "bert-s512": 512, "bert-s128": 128, "gpt2-xl": 1024,
    "olmoe": 4096, "olmo-hybrid": 4096, "joyai": 4096, "laguna": 4096,
    "qwen3-next": 8192, "ouro": 2048}
_SWEEP_OFFSETS = {"diagonal": lambda bq, bk, s: (0, 0),
                  "q-a-block-on": lambda bq, bk, s: (bq, 0),
                  "k-a-block-on": lambda bq, bk, s: (0, bk),
                  "k-ahead-of-q": lambda bq, bk, s: (0, s)}


def _pairs_seen(sq, sk, block_q, block_k, q_offset, k_offset, window):
    """``[q block][k block]``: whether the pair's tile holds a seen score, a
    query position at a time from the mask's own words (position t sees the
    keys s with ``t - window < s <= t``)."""
    t = q_offset + np.arange(sq)
    first_key = t - window + 1 if window else np.full_like(t, -2 ** 40)
    starts = k_offset + np.arange(0, sk, block_k)
    holds = (first_key[:, None] <= starts[None, :] + block_k - 1) \
        & (t[:, None] >= starts[None, :])
    return holds.reshape(sq // block_q, block_q, -1).any(1)


@pytest.mark.parametrize("offsets", list(_SWEEP_OFFSETS))
@pytest.mark.parametrize("window", [None, 16, 512, 10000],
                         ids=["no-window", "window-16", "window-512",
                              "window-longer-than-the-row"])
@pytest.mark.parametrize("cell", list(_SWEEP_ROWS))
def test_the_sweep_addresses_what_an_outer_block_sees(cell, window, offsets):
    """``_Sweep`` as a pure function of integers, against brute force, for
    the q-outer kernels (forward, dq) and the k-outer one (dk/dv): the seen
    blocks of every outer block are ``[behind, visited)``; the grid's extent
    covers them (the whole row with no window, fewer under one); for every
    program the block its operands are mapped to is the nominal one wherever
    ``_visible`` says the tile holds a seen score, and always a block of the
    row; an empty program is mapped to a seen block of its own outer block
    or, past the last of them in the forward and dq kernels, to the block the
    next outer block starts on; ``fetched`` counts the seen blocks, and the
    blocks a row's programs are mapped to change no more often than that
    along the row (the copies the pipeline issues an operand)."""
    s = _SWEEP_ROWS[cell]
    block_q, block_k = min(s, 512), min(s, 1024)
    offs = _SWEEP_OFFSETS[offsets](block_q, block_k, s)
    pairs = _pairs_seen(s, s, block_q, block_k, *offs, window)
    for keys_outer in (False, True):
        sweep = fa._Sweep.of(keys_outer, s, s, block_q, block_k, window, offs)
        want = pairs.T if keys_outer else pairs
        assert (sweep.outers, sweep.blocks) == want.shape
        assert 1 <= sweep.extent <= sweep.blocks
        assert window is not None or sweep.extent == sweep.blocks
        changes, before, rests = 0, None, 0
        for at in range(sweep.outers):
            behind, visited = sweep.seen(offs, at)
            seen = [b for b in range(sweep.blocks) if want[at, b]]
            assert seen == list(range(behind, max(visited, behind))), \
                (keys_outer, at, behind, visited, seen)
            assert len(seen) <= sweep.extent
            mapped = [sweep.place(offs, at, j) for j in range(sweep.extent)]
            nominal = [sweep.nominal(offs, at, j)
                       for j in range(sweep.extent)]
            assert nominal == list(range(nominal[0],
                                         nominal[0] + sweep.extent))
            assert 0 <= nominal[0] and nominal[-1] < sweep.blocks
            assert set(seen) <= set(nominal), (keys_outer, at, seen, nominal)
            for block, at_block in zip(nominal, mapped):
                iq, ik = (block, at) if keys_outer else (at, block)
                visible = bool(fa._visible(
                    True, True, offs[0] + iq * block_q, offs[1] + ik * block_k,
                    block_q, block_k, window))
                assert visible == bool(want[at, block])
                assert 0 <= at_block < sweep.blocks
                ahead = at + 1 < sweep.outers and not keys_outer \
                    and block >= visited
                if visible:
                    assert at_block == block
                elif ahead:         # what the next outer block starts on
                    assert at_block == min(sweep.seen(offs, at + 1)[0],
                                           sweep.blocks - 1)
                elif seen:          # an empty program rests on a seen block
                    assert at_block in seen
            rests += not seen
            for at_block in mapped:
                changes += at_block != before
                before = at_block
        assert sweep.fetched(offs) == (int(want.sum()),
                                       sweep.outers * sweep.blocks)
        assert changes <= int(want.sum()) + rests, (keys_outer, changes)


@pytest.mark.parametrize("cell,window,fwd,dkv", [
    ("laguna", 512, (2, 11, 32), (3, 11, 32)),
    ("laguna", None, (4, 20, 32), (8, 20, 32)),
    ("qwen3-next", None, (8, 72, 128), (16, 72, 128)),
    ("ouro", None, (2, 6, 8), (4, 6, 8)),
    ("gpt2-medium", None, (1, 2, 2), (2, 2, 2)),
    ("bert-s512", None, (1, 1, 1), (1, 1, 1))],
    ids=["laguna-sliding", "laguna-full", "qwen3-next", "ouro", "gpt2-medium",
         "s512-causal"])
def test_the_sweep_at_the_cells_shapes(cell, window, fwd, dkv, monkeypatch):
    """``(extent, fetched, total)`` a (batch, head) row: under Laguna's
    window of 512 the forward's grid steps through 2 of a q block's 4 k
    blocks and the dk/dv kernel's through 3 of a k block's 8 q blocks, and
    11 of a row's 32 blocks are addressed; a causal row of 4,096 addresses
    20 of 32, of 8,192 72 of 128, of 2,048 6 of 8, of 1,024 both its two.
    The gauges and the ``flash`` event's line carry them; a call that is not
    causal, or whose offsets are traced, reads 0 of 0."""
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    s = _SWEEP_ROWS[cell]
    block_q, block_k = min(s, 512), min(s, 1024)
    for keys_outer, want in ((False, fwd), (True, dkv)):
        sweep = fa._Sweep.of(keys_outer, s, s, block_q, block_k, window,
                             (0, 0))
        assert (sweep.extent,) + sweep.fetched((0, 0)) == want
    layout = fa._Layout(False, 1, 1, 64)
    operand = jax.ShapeDtypeStruct((1, s, 64), jnp.bfloat16)

    def announced(offsets, sweep):
        observability.reset()
        monkeypatch.setattr(fa, "_logged_paths", set())
        monkeypatch.setattr(fa, "_announced", set())
        fa._announce("flash_fwd", layout, operand, s, block_q, block_k, 1, 0,
                     offsets, 1, window, sweep)
        (detail,) = [e["detail"] for e in recorder.events()
                     if e["kind"] == "flash"]
        gauges = observability.registry().snapshot()["gauges"]
        return detail, (gauges["flash.inner_blocks_fetched"],
                        gauges["flash.inner_blocks_total"])
    detail, gauges = announced((0, 0), sweep := fa._Sweep.of(
        False, s, s, block_q, block_k, window, (0, 0)))
    assert gauges == fwd[1:]
    assert (f"; the grid steps through {fwd[0]} of an outer block's "
            f"{s // block_k} k blocks, {fwd[1]} of a row's {fwd[2]} k blocks "
            f"fetched") in detail, detail
    assert f" {s // block_q * fwd[0]} programs a call" in detail
    detail, gauges = announced((jnp.int32(0), 0), sweep)
    assert gauges == (0, 0)
    assert "the k blocks fetched by the offsets on the device" in detail
    detail, gauges = announced(None, None)
    assert gauges == (0, 0) and "the grid steps" not in detail


def test_traced_offsets_take_the_extent_any_alignment_needs():
    """Where an offset is traced the windowed grid's extent is what a span
    of ``outer + window - 1`` positions can touch however it lies against
    the inner blocks (never less than the integers' exact count), and the
    maps read the offsets where they run: on traced values ``place`` and
    ``nominal`` give what the integers give."""
    for keys_outer, exact in ((False, 2), (True, 3)):
        traced = fa._Sweep.of(keys_outer, 4096, 4096, 512, 1024, 512,
                              (jnp.int32(0), 0))
        assert traced.extent == (2 if not keys_outer else 4)
        assert fa._Sweep.of(keys_outer, 4096, 4096, 512, 1024, 512,
                            (0, 0)).extent == exact
    for offs in ((0, 0), (512, 0), (0, 1024), (96, 40), (0, 4096)):
        for keys_outer in (False, True):
            sweep = fa._Sweep.of(keys_outer, 4096, 4096, 512, 1024, 512,
                                 (jnp.int32(0), 0))
            at, j = np.meshgrid(np.arange(sweep.outers),
                                np.arange(sweep.extent), indexing="ij")
            got = jax.jit(jax.vmap(lambda o, a, b: (
                sweep.place(o, a, b), sweep.nominal(o, a, b)),
                in_axes=(None, 0, 0)))(
                    jnp.asarray(offs, jnp.int32), at.ravel(), j.ravel())
            want = [(sweep.place(offs, int(a), int(b)),
                     sweep.nominal(offs, int(a), int(b)))
                    for a, b in zip(at.ravel(), j.ravel())]
            assert [tuple(int(x) for x in pair) for pair in zip(*got)] == want
            pairs = _pairs_seen(4096, 4096, 512, 1024, *offs, 512)
            pairs = pairs.T if keys_outer else pairs
            for a in range(sweep.outers):   # every seen block has a program
                behind, visited = sweep.seen(offs, a)
                nominal = {sweep.nominal(offs, a, b)
                           for b in range(sweep.extent)}
                assert set(np.flatnonzero(pairs[a])) <= nominal


@pytest.mark.parametrize("group", [1, 6, 9])
def test_the_fanned_map_places_a_query_heads_blocks(group):
    """The dk/dv kernel's q side where the grid fans a key-value head over
    its ``group`` query heads: step ``head x extent + j`` of k block ``at``
    reads query head ``kv head x group + head`` at the block the sweep maps
    ``j`` to, inside that head's run of q blocks."""
    s, block_q, block_k, window, kv_heads = 4096, 512, 1024, 512, 2
    sweep = fa._Sweep.of(True, s, s, block_q, block_k, window, (0, 0))
    layout = fa._Layout(False, 1, kv_heads, 128)
    spec = layout.spec(1, block_q, 2, False, None, (group, sweep.extent),
                       sweep=sweep)
    plain = layout.spec(1, block_q, 2, False, None, (group, s // block_q))
    offs = jnp.zeros((2,), jnp.int32)
    for i in range(kv_heads):
        for at in range(sweep.outers):
            for step in range(group * sweep.extent):
                head, j = divmod(step, sweep.extent)
                row, block, lane = (int(x) for x in
                                    spec.index_map(i, at, step, offs))
                assert (row, block, lane) == (
                    i * group + head, sweep.place((0, 0), at, j), 0)
    # Without a sweep (a call that is not causal) the map is the plain one.
    assert [int(x) for x in plain.index_map(1, 2, 8 * (group - 1) + 5, offs)] \
        == [group + group - 1, 5, 0]


def _on_the_whole_rectangle(monkeypatch):
    """The kernels as the parent (PR 45) built them: every call on the whole
    rectangle of blocks with the plain index maps.  A call with no
    ``_Sweep`` builds just that (the bodies read one only under a window,
    and without one take the grid's own index for a step's block), so the
    parent's values are computed beside the tree's in one process; the
    builder's chip run compared the parent's module itself (PERF.md, PR
    46)."""
    grid = fa._grid
    monkeypatch.setattr(fa, "_grid",
                        lambda causal, *a, **k: grid(False, *a, **k))
    return fa


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("packed,rows", [(False, 1), (False, 3), (True, 2)],
                         ids=["split-one-row", "split-three-rows", "packed"])
@pytest.mark.parametrize("blocks", [(16, 16), (16, 32), (32, 16)],
                         ids=["16x16", "16x32", "32x16"])
def test_the_clamped_maps_change_no_bit(blocks, packed, rows, dtype,
                                        monkeypatch):
    """A causal call with empty programs (a row of 64 in 4 x 4, 4 x 2 and 2
    x 4 blocks: 6, 2 and 2 of its programs a kernel see no score): o, lse,
    dq, dk, dv with the inner blocks clamped to what the outer block sees
    are, bit for bit, those of the whole rectangle with the plain maps.  The
    interpreter runs every program through the masked arithmetic, so an
    empty program really computes on the block it was mapped to, and its
    positions, which come from the nominal block, mask every score of it to
    an exact zero."""
    d = 64 if packed else 16
    q, k, v, do = (jax.random.normal(key, (3, 2, 64, d)).astype(dtype)
                   for key in jax.random.split(jax.random.PRNGKey(11), 4))
    if packed:
        q, k, v, do = (_swap(x) for x in (q, k, v, do))

    def run(fa):
        def both(q, k, v, do):
            o, lse = fa._flash_fwd(q, k, v, True, *blocks, 0, 0, True,
                                   packed=packed)
            delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
            delta = (delta.transpose(0, 2, 1) if packed else delta)[..., None]
            return (o, lse) + tuple(fa._flash_bwd(
                q, k, v, do, lse, delta, True, *blocks, 0, 0, True,
                packed=packed))
        return jax.jit(both).lower(q, k, v, do).compile(
            compiler_options={"xla_backend_optimization_level": 0})(
                q, k, v, do)
    _force_rows(monkeypatch, rows)
    got = run(fa)
    want = run(_on_the_whole_rectangle(monkeypatch))
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)
    assert np.isfinite(np.asarray(got[0], np.float32)).all()


def _maps(jaxpr):
    """``{kernel: (grid, hash of its operands' and results' index maps)}``
    of a jaxpr's three kernels."""
    import hashlib
    found = {}
    for name, eqn in _kernels(jaxpr).items():
        mapping = eqn.params["grid_mapping"]
        text = "".join(str(block.index_map_jaxpr)
                       for block in mapping.block_mappings)
        found[name] = (tuple(mapping.grid),
                       hashlib.sha1(text.encode()).hexdigest()[:16])
    return found


# The grids and index maps of the parent (e9a4239, PR 45) at the cells whose
# calls have no empty program, hashed from its module: a call that is not
# causal (both BERT cells) builds what it built, and so does a causal one
# whose every program holds a seen score (1,024 keys are one k block:
# ``_Sweep.plain``).
_PLAIN_MAPS = {
    "gpt2-medium.train-s1024": {
        "flash_fwd": ((64, 2, 1), "80d880011ff37ec2"),
        "flash_bwd_dq": ((64, 2, 1), "9480987d4e003165"),
        "flash_bwd_dkv": ((64, 1, 2), "dee9f29f9432cdd4")},
    "gpt2-xl.train-s1024-x4": {
        "flash_fwd": ((50, 2, 1), "b4b97249210e5ae0"),
        "flash_bwd_dq": ((50, 2, 1), "3c11561d75611447"),
        "flash_bwd_dkv": ((50, 1, 2), "c003ec10857a6b82")},
    "bert-base.mlm-s512": {"flash_fwd": ((384, 1, 1), "931099718a67d2ad"),
                           "flash_bwd_dq": ((384, 1, 1), "c6d1ad6dcb717a87"),
                           "flash_bwd_dkv": ((384, 1, 1), "c58802ab5fb7cc12")},
    "bert-base.mlm-s128": {"flash_fwd": ((96, 1, 1), "931099718a67d2ad"),
                           "flash_bwd_dq": ((96, 1, 1), "c6d1ad6dcb717a87"),
                           "flash_bwd_dkv": ((96, 1, 1), "c58802ab5fb7cc12")},
}


@pytest.mark.parametrize("cell", list(_PLAIN_MAPS))
def test_a_call_with_no_empty_program_builds_the_maps_it_built(cell):
    (b, h, s, d), _, _ = _CELL_SHAPES[cell]
    resolve = fa._pallas_interpret
    fa._pallas_interpret = lambda *_: False
    try:
        hook = fa.make_flash_attn_fn("bert" not in cell)
        x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: (_attend(hook, q, k, v).astype(jnp.float32)
                             ** 2).sum(), argnums=(0, 1, 2)))(x, x, x)
    finally:
        fa._pallas_interpret = resolve
    assert _maps(jaxpr.jaxpr) == _PLAIN_MAPS[cell]
