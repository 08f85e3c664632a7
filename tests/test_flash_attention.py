"""Flash attention kernel vs the dense reference (interpret mode on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex_core

from autodist_tpu.graph_item import _sub_jaxprs
from autodist_tpu.models import layers as L
from autodist_tpu.ops.flash_attention import flash_attention, _dense_reference


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
    import importlib
    from jax.sharding import Mesh
    from autodist_tpu.parallel import context as parallel_ctx
    # ``autodist_tpu.ops`` exports the function under the module's name.
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")

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


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones (jit and custom_vjp bodies,
    a kernel's ``pl.when`` branches) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _grad_jaxpr(dtype, causal=True):
    q, k, v = (x.astype(dtype) for x in _qkv(s=32))

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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kernel,products", [("flash_fwd", 2),
                                             ("flash_bwd_dq", 3),
                                             ("flash_bwd_dkv", 4)])
def test_mxu_operands_follow_the_inputs(kernel, products, dtype):
    """All nine products take operands of the inputs' dtype and accumulate
    in f32: with bf16 inputs none runs multi-pass on the MXU, with f32
    inputs the casts are the identity."""
    body = _kernels(_grad_jaxpr(dtype))[kernel].params["jaxpr"]
    dots = [e for e in _eqns(body) if e.primitive.name == "dot_general"]
    assert len(dots) == products
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype], eqn
        assert eqn.outvars[0].aval.dtype == jnp.float32, eqn
