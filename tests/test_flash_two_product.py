"""The flash kernels in their two-product form (latent attention): a score
that is the sum of two products, the second against ONE key a position that
a batch row's heads share, and values of a width of their own.  Interpreted
Pallas against plain jnp: the forward and all five gradients."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("autodist_tpu.ops.flash_attention")


def _operands(b, h, s, d, r, dv, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((b, h, s, d), (b, h, s, r), (b, h, s, d), (b, s, r),
              (b, h, s, dv), (b, h, s, dv))
    return [jax.random.normal(k, shape).astype(dtype)
            for k, shape in zip(ks, shapes)]


def _dense_assembled(q, q_rope, k, k_rope, v, scale, causal):
    """Plain attention on keys assembled as [k ; k_rope broadcast]."""
    b, h, s, _ = q.shape
    keys = jnp.concatenate(
        [k, jnp.broadcast_to(k_rope[:, None], (b, h, s, k_rope.shape[-1]))],
        axis=-1)
    scores = jnp.einsum("bhqd,bhkd->bhqk",
                        jnp.concatenate([q, q_rope], axis=-1), keys) * scale
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [
    # b, h, s, d, r, dv, block_q, block_k: a value width other than the
    # scores' in every case; several blocks each way; one block.
    (2, 4, 256, 32, 16, 48, 64, 128),
    (1, 3, 128, 16, 8, 8, 128, 128),
    (2, 2, 256, 128, 64, 128, 128, 64)])
def test_forward_and_five_gradients_match_dense_attention(shape, causal):
    b, h, s, d, r, dv, bq, bk = shape
    *ops, w = _operands(b, h, s, d, r, dv)
    scale = (d + r) ** -0.5

    def kernels(*a):
        return fa.flash_attention_two_product(*a, scale, causal, bq, bk, True)

    def dense(*a):
        return _dense_assembled(*a, scale, causal)

    np.testing.assert_allclose(kernels(*ops), dense(*ops), atol=2e-6)
    np.testing.assert_allclose(fa.two_product_reference(*ops, scale, causal),
                               dense(*ops), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * w), (0, 1, 2, 3, 4))(*ops)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2, 3, 4))(*ops)
    for g, e, x in zip(got, want, ops):
        assert g.shape == x.shape and g.dtype == x.dtype
        np.testing.assert_allclose(g, e, atol=1e-5)


def test_the_shared_keys_gradient_is_the_sum_over_the_heads():
    b, h, s, d, r, dv = 1, 4, 128, 16, 8, 16
    q, q_rope, k, k_rope, v, w = _operands(b, h, s, d, r, dv)
    scale = (d + r) ** -0.5

    def shared(kr):
        return jnp.sum(fa.flash_attention_two_product(
            q, q_rope, k, kr, v, scale, True, 64, 64, True) * w)

    def per_head(kr4):      # (b, h, s, r): a key of its own for every head
        full_k = jnp.concatenate([k, kr4], axis=-1)
        full_q = jnp.concatenate([q, q_rope], axis=-1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", full_q, full_k) * scale
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd",
                                  jax.nn.softmax(scores, -1), v) * w)

    each = jax.grad(per_head)(jnp.broadcast_to(k_rope[:, None],
                                               (b, h, s, r)))
    np.testing.assert_allclose(jax.grad(shared)(k_rope), each.sum(1),
                               atol=1e-5)


def test_bf16_operands_give_bf16_results_close_to_f32():
    b, h, s, d, r, dv = 1, 2, 256, 128, 64, 128
    *ops, w = _operands(b, h, s, d, r, dv, jnp.bfloat16)
    scale = (d + r) ** -0.5
    out = fa.flash_attention_two_product(*ops, scale, True, 128, 128, True)
    assert out.dtype == jnp.bfloat16 and out.shape == (b, h, s, dv)
    want = _dense_assembled(*(x.astype(jnp.float32) for x in ops), scale,
                            True)
    np.testing.assert_allclose(out.astype(jnp.float32), want, atol=3e-2)
    grads = jax.grad(lambda *a: jnp.sum(
        fa.flash_attention_two_product(*a, scale, True, 128, 128, True)
        .astype(jnp.float32) * w.astype(jnp.float32)), (0, 1, 2, 3, 4))(*ops)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 5
    assert grads[3].shape == (b, s, r)


def test_off_the_tpu_the_plain_path_runs_and_blocks_that_do_not_divide():
    *ops, _ = _operands(1, 2, 96, 16, 8, 24)
    scale = 24 ** -0.5
    auto = fa.flash_attention_two_product(*ops, scale)       # interpret=None
    np.testing.assert_allclose(auto, _dense_assembled(*ops, scale, True),
                               atol=2e-6)
    # 96 positions do not divide blocks of 64: the plain path, not an error.
    odd = fa.flash_attention_two_product(*ops, scale, True, 64, 64, True)
    np.testing.assert_allclose(odd, auto, atol=2e-6)


def test_the_hook_carries_the_two_product_form():
    hook = fa.make_flash_attn_fn(causal=True)
    *ops, _ = _operands(1, 2, 64, 16, 8, 24)
    np.testing.assert_allclose(
        hook.two_product(*ops, 24 ** -0.5),
        _dense_assembled(*ops, 24 ** -0.5, True), atol=2e-6)


def test_the_one_width_kernels_trace_as_before():
    """The one-product call passes no scale to the kernels' bodies and reads
    five operands less: its jaxpr names no two-product operand."""
    q = jnp.ones((1, 2, 128, 16))
    text = str(jax.make_jaxpr(lambda q: fa.flash_attention(
        q, q, q, True, 64, 64, 0, True))(q))
    assert text.count("flash_fwd") >= 1
    two = str(jax.make_jaxpr(lambda q: fa.flash_attention_two_product(
        q, q[..., :8], q, q[:, 0, :, :8], q, 0.2, True, 64, 64, True))(q))
    assert two.count("flash_fwd") >= 1 and two != text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("sub,block_q,block_k", [
    (32, 32, 64), (16, 16, 64), (16, 32, 128)],
    ids=["two-sub-tiles", "four-sub-tiles", "eight-sub-tiles-two-k-blocks"])
def test_sub_tiles_of_a_causal_block_match_dense(sub, block_q, block_k,
                                                 dtype, monkeypatch):
    """The causal walk in the two-product form: a k block taken in two, four
    and eight sub-tiles (the module's constant steered; no argument selects
    it), interpreted, against dense attention on assembled keys: the forward
    and all five gradients, the shared key and its gradient sliced a
    sub-tile like the head's own."""
    monkeypatch.setattr(fa, "_SUB_TILE", sub)
    assert fa._sub_tile(True, block_k) == sub
    assert fa._sub_tile(False, block_k) == block_k
    b, h, s, d, r, dv = 2, 2, 256, 32, 16, 48
    *ops, w = _operands(b, h, s, d, r, dv, dtype, seed=sub)
    scale = (d + r) ** -0.5
    f32 = [x.astype(jnp.float32) for x in ops]

    def kernels(*a):
        return fa.flash_attention_two_product(*a, scale, True, block_q,
                                              block_k, True)

    def dense(*a):
        return _dense_assembled(*a, scale, True)

    tol = 2e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(kernels(*ops).astype(jnp.float32),
                               dense(*f32), atol=tol)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a).astype(jnp.float32)
                                      * w.astype(jnp.float32)),
                   (0, 1, 2, 3, 4))(*ops)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w.astype(jnp.float32)),
                    (0, 1, 2, 3, 4))(*f32)
    for g, e, x in zip(got, want, ops):
        assert g.shape == x.shape and g.dtype == x.dtype
        np.testing.assert_allclose(g.astype(jnp.float32), e,
                                   atol=1e-5 if dtype == jnp.float32
                                   else 0.25)


def test_causal_plan_at_the_joyai_cells_shape():
    """The JoyAI cell's call (32 heads of 4,096 positions, blocks 512 x
    1,024): 36 of a row's 64 sub-tiles of 512 x 512 are visited, 12 of them
    through the mask (the 24 of its twelve blocks wholly below the diagonal
    without it); the whole-block rule visited 20 of 32 blocks, 40 of 64."""
    assert fa._sub_tile(True, 1024) == 512
    assert fa._causal_plan(4096, 4096, 512, 1024, 512) == (64, 36, 12)
    assert fa._causal_plan(4096, 4096, 512, 1024, 1024) == (32, 20, 8)


def _whole_rectangle(monkeypatch):
    """The parent's program: every call on the whole rectangle of blocks
    with the plain index maps, which is what a call with no ``_Sweep`` (one
    that is not causal, to ``_grid``) builds."""
    grid = fa._grid
    monkeypatch.setattr(fa, "_grid",
                        lambda causal, *a, **k: grid(False, *a, **k))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block_q,block_k", [(32, 64), (64, 32)])
def test_the_clamped_maps_change_no_bit_of_the_two_product_form(
        block_q, block_k, dtype, monkeypatch):
    """The two-product form goes through the same ``_kernel_call`` and gets
    the same maps (the shared key's block clamps with its head's): o and the
    five gradients of a causal row of 256 in 8 x 4 and 4 x 8 blocks, many of
    whose programs see no score, are bit for bit those of the whole
    rectangle with the plain maps (a call with no ``_Sweep``: the parent's
    program)."""
    monkeypatch.setattr(fa, "_SUB_TILE", 16)
    b, h, s, d, r, dv = 2, 2, 256, 32, 16, 48
    *ops, w = _operands(b, h, s, d, r, dv, dtype, seed=3)
    scale = (d + r) ** -0.5

    def both(*ops):
        o, lse = fa._flash_fwd2(*ops, scale, True, block_q, block_k, True)
        delta = (w.astype(jnp.float32) * o.astype(jnp.float32)) \
            .sum(-1, keepdims=True)
        return (o, lse) + tuple(fa._flash_bwd2(
            *ops, w, lse, delta, scale, True, block_q, block_k, True))

    def level_0():
        return jax.jit(both).lower(*ops).compile(compiler_options={
            "xla_backend_optimization_level": 0})(*ops)
    grids = [e.params["grid_mapping"].grid
             for e in jax.make_jaxpr(both)(*ops).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert grids == [(b * h, s // block_q, s // block_k)] * 2 \
        + [(b * h, s // block_k, s // block_q)]
    got = level_0()
    _whole_rectangle(monkeypatch)
    want = level_0()
    names = ("o", "lse", "dq", "dq_rope", "dk", "dk_rope", "dv")
    for name, x, y in zip(names, got, want):
        assert x.dtype == y.dtype and x.shape == y.shape, name
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32), err_msg=name)
