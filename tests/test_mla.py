"""``models.layers.mla`` against the plain reference's layer
(``chipbench/reference_mla_moe.py``), from the same values: the output and
every gradient, through the plain core and through the hook's two-product
kernels (interpreted), at a value width other than the scores'."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import layers as L
from chipbench import reference_mla_moe as ref

fa = importlib.import_module("autodist_tpu.ops.flash_attention")

DIM, HEADS, Q_RANK, KV_RANK, NOPE, ROPE, VALUE = 64, 4, 48, 32, 16, 8, 24
THETA, EPS = 32000000.0, 1e-6


def _layer(seed=0, rows=2, seq=128):
    p = L.mla_init(jax.random.PRNGKey(seed), DIM, HEADS, Q_RANK, KV_RANK,
                   NOPE, ROPE, VALUE)
    # Norm scales that are not all one, so that a norm left out shows.
    p["q_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (Q_RANK,))
    p["kv_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 2), (KV_RANK,))
    x = jax.random.normal(jax.random.PRNGKey(seed + 3), (rows, seq, DIM))
    return p, x


def _program(hook):
    def apply(p, x):
        return L.mla(p, x, HEADS, NOPE, ROPE, VALUE,
                     L.rope_pair_tables(x.shape[1], ROPE, THETA),
                     attn_fn=hook, norm_eps=EPS)
    return apply


def _reference(p, x):
    return ref.latent_attention(p, x, heads=HEADS, nope=NOPE, rope=ROPE,
                                eps=EPS, theta=THETA)


@pytest.fixture
def kernels_hook(monkeypatch):
    """The default hook with the kernels interpreted, as the TPU runs them."""
    monkeypatch.setattr(fa, "_pallas_interpret", lambda *_: True)
    return fa.make_flash_attn_fn(causal=True, block_q=64, block_k=64)


@pytest.mark.parametrize("core", ["plain", "kernels"])
def test_mla_matches_the_reference_layer(core, request):
    hook = request.getfixturevalue("kernels_hook") if core == "kernels" \
        else None
    p, x = _layer()
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    with jax.default_matmul_precision("highest"):
        got = _program(hook)(p, x)
        want = _reference(p, x)
        np.testing.assert_allclose(got, want, atol=2e-5)
        g_got = jax.grad(lambda p, x: jnp.sum(_program(hook)(p, x) * w),
                         (0, 1))(p, x)
        g_want = jax.grad(lambda p, x: jnp.sum(_reference(p, x) * w),
                          (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max())
                                   + 1e-6)


def test_mla_has_the_published_parameter_count_at_the_published_widths():
    shapes = jax.eval_shape(lambda: L.mla_init(
        jax.random.PRNGKey(0), 2048, 32, 1536, 512, 128, 64, 128))
    assert sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(shapes)) == 26_347_520


def _rotate_half(t, tables):
    """Pairs (i, i + w/2) in place of (2i, 2i + 1)."""
    return L.apply_rope(t, tuple(jnp.concatenate([c, c], -1)
                                 for c in tables))


def _narrow_scale(attention):
    """1/sqrt(nope) in place of 1/sqrt(nope + rope)."""
    return lambda q, q_rope, k, k_rope, v, scale, *rest: attention(
        q, q_rope, k, k_rope, v, NOPE ** -0.5, *rest)


def _no_kv_norm(rmsnorm):
    return lambda p, t, eps=1e-5: t if t.shape[-1] == KV_RANK \
        else rmsnorm(p, t, eps)


@pytest.mark.parametrize("module, name, broken", [
    (L, "apply_rope_pairs", lambda _: _rotate_half),
    (fa, "two_product_reference", _narrow_scale),
    (L, "rmsnorm", _no_kv_norm)])
def test_planted_faults_move_the_output(module, name, broken, monkeypatch):
    """What the comparison above must see: each is 1e-2 or more away."""
    p, x = _layer(seq=64)
    monkeypatch.setattr(module, name, broken(getattr(module, name)))
    assert float(jnp.abs(_program(None)(p, x) - _reference(p, x)).max()) \
        > 1e-2


def test_a_hook_without_the_two_product_form_is_refused():
    p, x = _layer(seq=32)
    with pytest.raises(NotImplementedError, match="two_product"):
        _program(lambda q, k, v, mask=None: q)(p, x)


def test_the_event_and_gauges_name_the_widths(kernels_hook):
    from autodist_tpu import observability
    p, x = _layer(seq=64)
    _program(kernels_hook)(p, x)
    gauges = observability.registry().snapshot()["gauges"]
    assert (gauges["mla.heads"], gauges["mla.q_rank"], gauges["mla.kv_rank"],
            gauges["mla.nope_width"], gauges["mla.rope_width"],
            gauges["mla.value_width"]) == (HEADS, Q_RANK, KV_RANK, NOPE,
                                           ROPE, VALUE)
    events = [e for e in observability.tracing.events()
              if e.get("name") in ("mla", "flash")]
    assert any("two-product" in str(e) for e in events)
