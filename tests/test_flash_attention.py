"""Flash attention kernel vs the dense reference (interpret mode on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

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
