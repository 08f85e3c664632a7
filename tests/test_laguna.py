"""The Laguna-S-2.1 block at a toy size on the CPU: grouped key-value heads,
sliding layers of more heads beside full ones, rotary tables by layer kind
(YaRN on half the lanes of a full layer), a gate a head, softmax-routed
experts of which a share is held beside a shared one; program against the
plain reference (``chipbench/reference_swa_moe.py``)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, strategy
from autodist_tpu.autodist import _reset_default
from autodist_tpu.models import layers as L
from autodist_tpu.models import lm
from autodist_tpu.models import transformer as T
from chipbench import reference_swa_moe as ref

fa = importlib.import_module("autodist_tpu.ops.flash_attention")

HELD, WINDOW, KV, HEAD = (4, 4), 8, 2, 16
YARN = dict(factor=128.0, original_len=16, beta_fast=32.0, beta_slow=1.0,
            attention_factor=1.4852)
ROPE = {T.FULL: {"theta": 500000.0, "lanes": 8, "yarn": YARN},
        T.SLIDING: {"theta": 10000.0, "lanes": None, "yarn": None}}


def _cfg(**kw):
    """Two periods of full, sliding, sliding, sliding; 4 query heads in a
    full layer and 6 in a sliding one over 2 key-value heads of 16."""
    kinds = [T.FULL, T.SLIDING, T.SLIDING, T.SLIDING] * 2
    args = dict(
        vocab=64, dim=32, num_heads=4, num_layers=8, mlp_dim=48, max_len=64,
        causal=True, dtype=jnp.float32, norm="rmsnorm", norm_eps=1e-6,
        positions="rope", bias=False, tied_head=False, ffn="moe",
        num_experts=16, experts_per_token=5, expert_dim=24, norm_topk=True,
        load_balance_coef=0.001, layer_types=kinds,
        expert_scoring="softmax", route_scale=2.5, shared_experts=1,
        experts_held=HELD, first_dense=1, head_dim=HEAD, kv_heads=KV,
        heads_by_layer=[4 if k == T.FULL else 6 for k in kinds],
        window=WINDOW, attn_gate=True, rope_by_type=ROPE)
    args.update(kw)
    return T.TransformerConfig(**args)


def _model(cfg):
    return dict(layer_types=cfg.layer_types, rope=cfg.rope_by_type,
                head_dim=cfg.head_dim, eps=cfg.norm_eps,
                kv_heads=cfg.kv_heads, window=cfg.window,
                top_k=cfg.moe.top_k, route_scale=cfg.moe.route_scale,
                held=cfg.moe.held, balance_coef=cfg.load_balance_coef)


def _tokens(rows=2, seq=32, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              64)


@pytest.mark.parametrize("core", ["dense", "kernels"])
def test_the_loss_and_every_leafs_gradient_match_the_reference(core,
                                                               monkeypatch):
    """Two periods through ``lm.make_loss_fn``: the loss within 1e-5 of the
    reference's and every leaf's gradient within 2e-4 of its largest entry,
    on the dense path and through the interpreted kernels."""
    if core == "kernels":
        monkeypatch.setattr(fa, "_pallas_interpret", lambda *_: True)
    cfg = _cfg()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(
            lm.make_loss_fn(cfg), has_aux=True)(params, (tokens,))
        want, want_grads = jax.value_and_grad(
            lambda p: ref.loss(p, tokens, **_model(cfg)))(params)
        _, routed = ref.loss_and_held_output_rms(params, tokens,
                                                **_model(cfg))
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(got) == len(jax.tree_util.tree_leaves(want_grads))
    for (path, g), e in zip(got, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.abs(e).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, e, atol=2e-4 * float(jnp.abs(e).max()),
            err_msg=jax.tree_util.keystr(path))
    np.testing.assert_allclose(aux["moe.held_output_rms"], routed, rtol=1e-4)
    assert float(aux["moe.dropped"]) == 0.0
    assert 0 < float(aux["moe.held_assignments"]) < 7 * 2 * 32 * 5
    assert sorted(aux) == [
        "moe.dropped", "moe.held_assignments", "moe.held_buffer_rows",
        "moe.held_output_rms",
        "moe.load_balance_loss", "moe.load_max_over_mean",
        "moe.router_z_loss", "xent"]


def test_heads_follow_the_layer_and_keys_stay_as_wide_as_their_heads():
    cfg = _cfg()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    for i, kind in enumerate(cfg.layer_types):
        attn = params[f"layer{i}"]["attn"]
        heads = 4 if kind == T.FULL else 6
        assert cfg.layer_heads(i) == heads
        assert attn["query"]["kernel"].shape == (32, heads * HEAD)
        assert attn["out"]["kernel"].shape == (heads * HEAD, 32)
        assert attn["gate"]["kernel"].shape == (32, heads)
        assert attn["key"]["kernel"].shape == (32, KV * HEAD) \
            == attn["value"]["kernel"].shape
        assert "q_norm" not in attn and "bias" not in attn["query"]
    assert "mlp" in params["layer0"] and "moe" in params["layer1"]
    assert params["layer1"]["moe"]["up"]["kernel"].shape[0] == HELD[1]
    assert params["layer1"]["moe"]["gate"]["kernel"].shape == (32, 16)
    # No key or value is ever as wide as the query heads: the jaxpr of a
    # sliding layer holds no (.., 6, s, 16) array made from k or v.
    seen = []

    def spy(q, k, v, mask=None, window=None):
        seen.append((q.shape, k.shape, v.shape, window))
        return fa._dense_reference(q, k, v, True, 0, window)
    spy.grouped = spy.windowed = True
    lm.make_loss_fn(cfg, attn_fn=spy)(params, (_tokens(),))
    assert seen[0] == ((2, 4, 32, HEAD), (2, KV, 32, HEAD),
                       (2, KV, 32, HEAD), None)
    assert seen[1] == ((2, 6, 32, HEAD), (2, KV, 32, HEAD),
                       (2, KV, 32, HEAD), WINDOW)
    assert [w for *_, w in seen] == [None, WINDOW, WINDOW, WINDOW] * 2


def test_an_explicit_mask_holds_the_window_and_a_hook_that_cannot_is_refused():
    """A caller's own ``attn_fn`` gets the boolean mask, narrowed to the
    window in a sliding layer; one that does not say it reads grouped heads
    is refused by name, not handed repeated keys."""
    cfg = _cfg(num_layers=2, layer_types=[T.FULL, T.SLIDING],
               heads_by_layer=[4, 6])
    params = lm.init(jax.random.PRNGKey(0), cfg)
    tokens = _tokens()
    masks = []

    def hook(q, k, v, mask=None, window=None):
        masks.append((mask, window))
        return L.dot_product_attention(q, k, v, mask)
    hook.grouped = hook.windowed = True
    got = lm.make_loss_fn(cfg, attn_fn=hook)(params, (tokens,))[0]
    want = lm.make_loss_fn(cfg)(params, (tokens,))[0]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    (full, none), (narrow, window) = masks
    assert none is None and window == WINDOW
    np.testing.assert_array_equal(full, L.causal_mask(32))
    np.testing.assert_array_equal(narrow, L.causal_mask(32, WINDOW))
    assert int(narrow[0, 0, 20].sum()) == WINDOW
    with pytest.raises(NotImplementedError, match=r"\.grouped"):
        lm.make_loss_fn(cfg, attn_fn=lambda q, k, v, mask=None: q)(
            params, (tokens,))


def test_three_adam_steps_through_the_runner_match_the_reference(steps=3):
    from chipbench import reference
    _reset_default()
    cfg = _cfg()
    params = lm.init(jax.random.PRNGKey(2), cfg)
    batches = [(np.asarray(_tokens(8, seed=10 + i)),) for i in range(steps)]
    model = _model(cfg)
    want = reference.train_losses(
        lambda p, batch: ref.loss(p, batch[0], **model), params, batches,
        1e-3, chunk_rows=1)
    ad = AutoDist(strategy_builder=strategy.PartitionedPS())
    with jax.default_matmul_precision("highest"):
        item = ad.capture(lm.make_loss_fn(cfg), params, optax.adam(1e-3),
                          example_batch=batches[0])
        runner = ad.create_distributed_session(item)
        state = runner.create_state()
        got = []
        for batch in batches:
            state, metrics = runner.step(state, batch)
            got.append(float(metrics["loss"]))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got[-1] < got[0]
    assert sorted(runner.last_aux) == [
        "moe.dropped", "moe.held_assignments", "moe.held_buffer_rows",
        "moe.held_output_rms",
        "moe.load_balance_loss", "moe.load_max_over_mean",
        "moe.router_z_loss", "xent"]
    _reset_default()


def test_the_published_configuration_counts_its_parameters():
    def count(cfg):
        shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
        return sum(int(np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(shapes))
    cut = lm.laguna_s_2_1(num_layers=5, vocab=12544, experts_held=(0, 8))
    assert count(cut) == 811_017_216
    assert cut.layer_types == (T.FULL,) + (T.SLIDING,) * 3 + (T.FULL,)
    assert cut.heads_by_layer == (48, 72, 72, 72, 48)
    assert count(lm.laguna_s_2_1()) == 117_561_953_280


@pytest.mark.parametrize("wrong, message", [
    (dict(scan_layers=True, ffn="swiglu"), "scan_layers stacks one kind"),
    (dict(window=None), "needs window"),
    (dict(layer_types=[T.FULL] * 8), "needs such a layer"),
    (dict(heads_by_layer=[4] * 7), "heads_by_layer"),
    (dict(kv_heads=4), "do not group"),
    (dict(layer_types=["local_attention"] * 8), "layer_types")])
def test_the_configuration_refuses_what_it_cannot_build(wrong, message):
    with pytest.raises((ValueError, NotImplementedError), match=message):
        _cfg(**wrong)


@pytest.mark.parametrize("field, named", [
    (dict(kv_heads=2), "grouped key-value heads"),
    (dict(layer_types=[T.FULL, T.SLIDING], window=8), "a window"),
    (dict(heads_by_layer=[4, 4]), "heads by layer"),
    (dict(attn_gate=True), "a gate"),
    (dict(head_dim=16), "head width")])
def test_decoding_refuses_each_new_field_by_name(field, named):
    """No silent full-length cache of ``dim / heads`` wide heads: the two
    callables the serving engine is built from (``serve/decode.py`` sizes its
    buckets with ``init_cache_fn`` and compiles ``decode_fn``) refuse what
    they would get wrong."""
    cfg = T.TransformerConfig(vocab=64, dim=32, num_heads=4, num_layers=2,
                              max_len=64, causal=True, dtype=jnp.float32,
                              **field)
    with pytest.raises(NotImplementedError, match=named):
        lm.init_decode_cache(cfg, slots=2, cache_len=16)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match=named):
        lm.make_decode_fn(cfg)(params, {}, jnp.zeros((2,), jnp.int32),
                               jnp.zeros((2,), jnp.int32))


def test_the_profilers_tables_fold_and_split_attentions_scopes():
    from autodist_tpu.observability import profile
    place = profile._scope_and_phase
    for inner in ("qkv", "rope", "core", "window_core", "gate", "out"):
        assert place(f"jit(step)/jvp(layer3)/attn/{inner}/dot_general")[0] \
            == "attn"
    text = """HloModule m
ENTRY %main (p: f32[8]) -> f32[8] {
  %a = f32[8] add(%p, %p), metadata={op_name="jit(step)/jvp(layer1)/attn/window_core/flash_fwd"}
  %b = f32[8] add(%a, %a), metadata={op_name="jit(step)/transpose(jvp(layer4))/attn/core/flash_bwd_dq"}
  %c = f32[8] add(%b, %b), metadata={op_name="jit(step)/jvp(layer1)/attn/rope/mul"}
  %d = f32[8] add(%c, %c), metadata={op_name="jit(step)/jvp(layer1)/attn/add"}
  %e = f32[8] add(%d, %d), metadata={op_name="jit(step)/jvp(layer1)/moe/router/dot_general"}
  ROOT %f = f32[8] add(%e, %e)
}
"""
    table = profile.subscope_table(text, "attn")
    assert table["a"] == ("attn/window_core", "forward")
    assert table["b"] == ("attn/core", "backward")
    assert table["c"][0] == "attn/rope" and table["d"][0] == "attn"
    assert table["e"][0] == "elsewhere"
    assert table["f"][0] == profile.UNATTRIBUTED
    assert profile.scope_table(text)["a"] == ("attn", "forward")


def test_the_attn_event_and_gauges_name_both_kinds_of_layer():
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    cfg = _cfg()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    lm.make_loss_fn(cfg)(params, (_tokens(),))
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["attn.heads_full"] == 4 and gauges["attn.heads_window"] == 6
    assert gauges["attn.kv_heads"] == KV and gauges["attn.window"] == WINDOW
    assert gauges["attn.rotary_lanes_full"] == 8
    assert gauges["moe.softmax_scoring"] == 1
    said = [e["detail"] for e in recorder.events() if e["kind"] == "attn"]
    assert any("6 heads of 16 read 2 key-value heads (3 a group), a window "
               "of 8 keys, 16 of a head's 16 lanes rotated, a sigmoid gate"
               in e for e in said)
    assert any("4 heads of 16 read 2 key-value heads (2 a group), every key "
               "behind the diagonal, 8 of a head's 16 lanes rotated" in e
               for e in said)
    assert any("softmax scores" in e["detail"]
               for e in recorder.events() if e["kind"] == "moe")
