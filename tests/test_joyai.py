"""The JoyAI-LLM-Flash-shaped model at a toy size, in float32, against the
plain reference (``chipbench/reference_mla_moe.py``) from the same values:
the three terms of the loss, every leaf's gradient, the biases' update, and
three Adam steps through ``AutoDist -> capture -> Runner.step``."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, strategy
from autodist_tpu.autodist import _reset_default
from autodist_tpu.models import lm
from autodist_tpu.models import transformer as T
from chipbench import reference_mla_moe as ref

VOCAB, SEQ, EXPERTS, HELD = 64, 32, 16, (4, 4)


def _cfg(layers=3, **kw):
    base = dict(
        vocab=VOCAB, dim=32, num_heads=4, num_layers=layers, mlp_dim=48,
        max_len=64, causal=True, dtype=jnp.float32, norm="rmsnorm",
        norm_eps=1e-6, positions="none", rope_theta=32000000.0, bias=False,
        tied_head=False, ffn="moe", num_experts=EXPERTS, experts_per_token=4,
        expert_dim=24, norm_topk=True, load_balance_coef=1e-2,
        layer_types=[T.LATENT] * layers, expert_scoring="sigmoid",
        route_scale=2.5, shared_experts=1, select_bias=True,
        bias_update_rate=0.001, experts_held=HELD, first_dense=1, q_rank=24,
        kv_rank=16, nope_dim=8, rope_dim=4, value_dim=12, mtp_depth=1,
        mtp_coef=0.3)
    return T.TransformerConfig(**{**base, **kw})


def _model(cfg):
    return dict(layers=cfg.num_layers, heads=cfg.num_heads,
                nope=cfg.nope_dim, rope=cfg.rope_dim, eps=cfg.norm_eps,
                theta=cfg.rope_theta, top_k=4, route_scale=2.5, held=HELD,
                mtp_coef=cfg.mtp_coef, balance_coef=cfg.load_balance_coef)


def _tokens(rows, seed=0):
    return np.random.RandomState(seed).randint(
        0, VOCAB, (rows, SEQ + 2)).astype(np.int32)


def test_the_loss_its_terms_and_every_gradient_match_the_reference():
    cfg = _cfg(layers=2)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    tokens = _tokens(2)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lm.make_loss_fn(cfg), has_aux=True))(params, (tokens,))
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p, t: ref.loss(p, t, **_model(cfg))))(params, tokens)
        xent, mtp_xent, balance, counts, routed_rms = jax.jit(lambda p, t: ref.forward(
            p, t, **{k: v for k, v in _model(cfg).items()
                     if k not in ("mtp_coef", "balance_coef")}))(params,
                                                                 tokens)
        moved = jax.jit(lambda p, t: ref.state_updates(
            p, t, bias_update_rate=0.001, **_model(cfg)))(params, tokens)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(aux["xent"], xent, rtol=2e-6)
    np.testing.assert_allclose(aux["mtp.xent"], mtp_xent, rtol=2e-6)
    np.testing.assert_allclose(aux["moe.load_balance_loss"], balance,
                               rtol=1e-5)
    assert float(loss) == pytest.approx(float(
        aux["xent"] + 0.3 * aux["mtp.xent"]
        + 1e-2 * aux["moe.load_balance_loss"]), rel=1e-6)
    # One expert layer and the module's: two biases, two counts.
    assert set(aux["state_updates"]) == set(counts) == set(moved) == {
        "layer1/moe/bias", "mtp/block/moe/bias"}
    assert float(aux["moe.held_assignments"]) == sum(
        float(c[4:8].sum()) for c in counts.values())
    assert float(aux["moe.dropped"]) == 0.0
    np.testing.assert_allclose(aux["moe.held_output_rms"], routed_rms,
                               rtol=1e-5)
    for name, value in moved.items():
        np.testing.assert_allclose(aux["state_updates"][name], value,
                                   atol=1e-9)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            g, w, atol=2e-4 * float(jnp.abs(w).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))
    # Embedding and head are used twice; both uses reach their gradients.
    alone = jax.jit(jax.grad(lambda p: lm.make_loss_fn(
        _cfg(layers=2, mtp_coef=0.0))(p, (tokens,))[0]))(params)
    assert float(jnp.abs(grads["lm_head"]["kernel"]
                         - alone["lm_head"]["kernel"]).max()) > 1e-6


def test_the_modules_loss_reads_the_token_after_next():
    cfg = _cfg()
    params = lm.init(jax.random.PRNGKey(1), cfg)
    tokens = _tokens(2, seed=3)
    changed = tokens.copy()
    changed[:, -1] = (changed[:, -1] + 1) % VOCAB      # only t_(s+1)
    loss_fn = jax.jit(lm.make_loss_fn(cfg))
    a, b = loss_fn(params, (tokens,))[1], loss_fn(params, (changed,))[1]
    assert float(a["xent"]) == float(b["xent"])
    assert float(a["mtp.xent"]) != float(b["mtp.xent"])
    # Without the module a row holds one token less and the loss is one term.
    plain = _cfg(mtp_depth=0, select_bias=False, experts_held=None,
                 shared_experts=0)
    p = lm.init(jax.random.PRNGKey(1), plain)
    assert "mtp" not in p
    assert "mtp.xent" not in jax.jit(lm.make_loss_fn(plain))(
        p, (tokens[:, :-1],))[1]


def test_three_adam_steps_through_the_runner_match_the_reference(steps=3):
    _reset_default()
    cfg = _cfg()
    params = lm.init(jax.random.PRNGKey(2), cfg)
    batches = [(_tokens(8, seed=10 + i),) for i in range(steps)]
    want, want_params = ref.train(params, batches, 1e-3,
                                  bias_update_rate=0.001, **_model(cfg))
    ad = AutoDist(strategy_builder=strategy.AllReduce())
    with jax.default_matmul_precision("highest"):
        item = ad.capture(lm.make_loss_fn(cfg), params, optax.adam(1e-3),
                          example_batch=batches[0])
        runner = ad.create_distributed_session(item)
        assert not runner.program.use_explicit_path
        # The bucket plan orders gradients by where the backward pass makes
        # them: a variable used twice (embedding, head) has one place.
        order = runner.grad_production_order()
        assert {"embed/embedding", "lm_head/kernel"} <= set(order)
        state = runner.create_state()
        got = []
        for batch in batches:
            state, metrics = runner.step(state, batch)
            got.append(float(metrics["loss"]))
    np.testing.assert_allclose(got, want, rtol=5e-6)
    assert got[-1] < got[0]
    now = jax.device_get(state.params)
    for name in ("layer1", "layer2"):
        bias = now[name]["moe"]["bias"]
        np.testing.assert_allclose(bias, want_params[name]["moe"]["bias"],
                                   atol=1e-8)
        steps_moved = np.round(bias / 0.001)
        assert np.abs(steps_moved).max() >= 1
        np.testing.assert_allclose(bias, 0.001 * steps_moved, atol=1e-8)
        assert np.abs(steps_moved).max() <= steps
    np.testing.assert_allclose(now["mtp"]["block"]["moe"]["bias"],
                               want_params["mtp"]["block"]["moe"]["bias"],
                               atol=1e-8)
    mu = jax.device_get(state.opt_state[0].mu)
    assert float(np.abs(mu["layer1"]["moe"]["bias"]).max()) == 0.0
    assert sorted(runner.last_aux) == [
        "moe.bias_absmax", "moe.dropped", "moe.held_assignments",
        "moe.held_buffer_rows", "moe.held_output_rms", "moe.load_balance_loss",
        "moe.load_max_over_mean", "mtp.xent",
        "xent"]
    _reset_default()


def test_the_published_configuration_counts_its_parameters():
    def count(cfg):
        shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
        return sum(int(np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(shapes))
    assert count(lm.joyai_llm_flash(num_layers=5, vocab=16160,
                                    experts_held=(0, 16))) == 680_441_088
    # 48,942,542,592 without the prediction module's block and projection.
    assert count(lm.joyai_llm_flash()) == 48_942_542_592 + 1_247_949_056


@pytest.mark.parametrize("wrong, message", [
    (dict(mtp_depth=2), "one block deep"),
    (dict(scan_layers=True), "scan_layers"),
    (dict(kv_rank=0), "kv_rank"),
    (dict(expert_scoring="tanh"), "scoring")])
def test_the_configuration_refuses_what_it_cannot_build(wrong, message):
    with pytest.raises((ValueError, NotImplementedError), match=message):
        _cfg(**wrong)


def test_the_profilers_table_folds_the_modules_scopes():
    from autodist_tpu.observability import profile
    place = profile._scope_and_phase
    assert place("jit(f)/jvp(mtp)/block/attn/core/dot_general")[0] == "attn"
    assert place("jit(f)/mtp/block/moe/shared/dot_general")[0] == "moe/shared"
    assert place("jit(f)/mtp/lm_head/logits/dot_general")[0] == "head"
    assert place("jit(f)/mtp/proj/dot_general")[0] == "mtp/proj"
    assert place("jit(f)/layer3/attn/q_latent/dot_general")[0] == "attn"
    assert place("jit(f)/layer3/moe/shared/dot_general")[0] == "moe/shared"
    text = """
%fused_a (p: f32[4]) -> f32[4] {
  %a = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %p), metadata={op_name="jit(f)/mtp/block/attn/out/add"}
  ROOT %b = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %a), metadata={op_name="jit(f)/mtp/block/attn/out/add"}
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %x = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%fused_a, metadata={op_name="jit(f)/layer0/attn/out/add"}
  %y = f32[4]{0} add(f32[4]{0} %x, f32[4]{0} %x), metadata={op_name="jit(f)/layer0/attn/out/add"}
  %z = f32[4]{0} copy(f32[4]{0} %y)
}
"""
    overlay = profile.overlay_table(text, "mtp")
    assert overlay["x"][0] == "mtp"            # by the vote of what it fused
    assert overlay["y"][0] == "elsewhere"
    assert overlay["z"][0] == profile.UNATTRIBUTED
    assert profile.scope_table(text)["x"][0] == "attn"
