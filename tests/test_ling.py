"""The Ling-3.0-flash language model's block at a toy size on the CPU: KDA
layers (the delta rule with a decay a channel, its gate bounded) to a latent
layer with full-rank queries and a gate a head, a leading dense layer, then
sigmoid-routed experts chosen inside the best groups of which a share is
held beside a shared one; program against the plain reference
(``chipbench/reference_kda_mla_moe.py``)."""
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
from autodist_tpu.parallel import moe
from chipbench import reference_kda_mla_moe as ref

fa = importlib.import_module("autodist_tpu.ops.flash_attention")


def _cfg(**kw):
    """A dense layer with a KDA mixer, a KDA layer and a latent one: 4 heads
    of 8 / 8; latent scores 8 + 4 wide, values 8, a latent of 16; 16 experts
    in 4 groups of which 2 are kept, 3 a token, experts 4-7 held."""
    args = dict(
        vocab=64, dim=32, num_heads=4, num_layers=3, mlp_dim=48, max_len=64,
        causal=True, dtype=jnp.float32, norm="rmsnorm", norm_eps=1e-6,
        positions="none", rope_theta=6000000.0, bias=False, tied_head=False,
        ffn="moe", num_experts=16, experts_per_token=3, expert_dim=24,
        norm_topk=True, layer_types=(T.KDA, T.KDA, T.LATENT), linear_heads=4,
        linear_key_dim=8, linear_value_dim=8, conv_width=4,
        linear_gate_bound=-5.0, expert_scoring="sigmoid", route_scale=2.5,
        shared_experts=1, select_bias=True, bias_update_rate=0.001,
        experts_held=(4, 4), expert_groups=4, expert_groups_kept=2,
        first_dense=1, q_rank=0, kv_rank=16, nope_dim=8, rope_dim=4,
        value_dim=8, attn_gate=True)
    args.update(kw)
    return T.TransformerConfig(**args)


def _model(cfg):
    return dict(layer_types=cfg.layer_types, heads=cfg.num_heads,
                nope=cfg.nope_dim, rope=cfg.rope_dim, eps=cfg.norm_eps,
                theta=cfg.rope_theta, gate_bound=cfg.linear_gate_bound,
                top_k=cfg.moe.top_k, route_scale=cfg.moe.route_scale,
                groups=cfg.moe.groups, groups_kept=cfg.moe.groups_kept,
                held=cfg.moe.held)


def _tokens(rows=2, seq=32, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              64)


def _with_biases(params, seed=3):
    """The selection biases away from zero, so that they choose."""
    out = dict(params)
    for name, layer in params.items():
        if isinstance(layer, dict) and "moe" in layer:
            bias = 0.2 * jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(seed), len(name)),
                layer["moe"]["bias"].shape)
            out[name] = {**layer, "moe": {**layer["moe"], "bias": bias}}
    return out


# -- the model against the reference ------------------------------------------

def test_the_loss_and_every_leafs_gradient_match_the_reference(monkeypatch):
    """``lm.make_loss_fn`` over the three kinds of layer: the loss within
    1e-5 of the reference's and every leaf's gradient within 2e-4 of its
    largest entry (the biases' is zero on both sides), the latent layer
    through the flash kernels' two-product form, interpreted."""
    monkeypatch.setattr(fa, "_pallas_interpret", lambda *_: True)
    cfg = _cfg(mixer_stats=True)
    params = _with_biases(lm.init(jax.random.PRNGKey(0), cfg))
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lm.make_loss_fn(cfg), has_aux=True))(params, (tokens,))
        (want, probed), want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_and_probes(p, tokens, **_model(cfg)),
            has_aux=True))(params)
        moved = jax.jit(lambda p: ref.bias_updates(
            p, tokens, bias_update_rate=0.001, **_model(cfg)))(params)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(got) == len(jax.tree_util.tree_leaves(want_grads))
    for (path, g), e in zip(got, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            assert float(jnp.abs(g).max()) == float(jnp.abs(e).max()) == 0
            continue
        assert float(jnp.abs(e).max()) > 0, name
        np.testing.assert_allclose(
            g, e, atol=2e-4 * float(jnp.abs(e).max()), err_msg=name)
    np.testing.assert_allclose(aux["moe.held_output_rms"],
                               probed["held_output_rms"], rtol=1e-4)
    np.testing.assert_allclose(aux["moe.groups_reached"],
                               probed["groups_reached"], rtol=1e-6)
    assert float(aux["moe.dropped"]) == 0.0
    assert 0 < float(aux["moe.held_assignments"]) < 2 * 2 * 32 * 3
    assert 1.0 <= float(aux["moe.groups_reached"]) <= 2.0
    assert -75.0 <= float(aux["kda.gate_min"]) < 0.0
    assert float(aux["kda.state_absmax"]) > 0
    for name, value in aux["state_updates"].items():
        np.testing.assert_array_equal(value, moved[name])
    mixers = ["attn.output_std", "kda.output_std"] if cfg.mixer_stats else []
    for name in mixers:
        np.testing.assert_allclose(aux[name], probed[name.replace(".", "_")],
                                   rtol=1e-4)
    assert sorted(aux) == sorted(mixers + [
        "kda.gate_min", "kda.state_absmax", "moe.bias_absmax", "moe.dropped",
        "moe.groups_reached", "moe.held_assignments", "moe.held_buffer_rows",
        "moe.held_output_rms", "moe.load_balance_loss",
        "moe.load_max_over_mean", "state_updates", "xent"])
    # No balance term enters the loss: the family balances by the bias.
    np.testing.assert_allclose(loss, aux["xent"], rtol=1e-7)


def test_three_adam_steps_through_the_runner_match_the_reference(steps=3):
    """``AutoDist.capture`` and the Runner's step (the GSPMD one: a loss whose
    ``aux`` carries ``state_updates``), with the linear mixers recomputed."""
    from chipbench import reference
    _reset_default()
    cfg = _cfg(recompute="linear_mixer", bias_update_rate=0.0)
    params = lm.init(jax.random.PRNGKey(2), cfg)
    batches = [(np.asarray(_tokens(8, seed=10 + i)),) for i in range(steps)]
    model = _model(cfg)
    want = reference.train_losses(
        lambda p, batch: ref.loss(p, batch[0], **model), params, batches,
        1e-3, chunk_rows=1)
    ad = AutoDist(strategy_builder=strategy.AllReduce())
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
    assert "kda.state_absmax" in runner.last_aux
    assert "moe.groups_reached" in runner.last_aux
    _reset_default()


def test_the_preset_holds_the_published_sizes():
    """``lm.ling_3_0_flash`` under ``jax.eval_shape``: 42 layers in periods
    of five KDA and one latent, two dense layers, 512 experts in 8 groups;
    a KDA mixer holds 52.7 M parameters and a latent one 32.0 M (ISSUE 47's
    arithmetic)."""
    cfg = lm.ling_3_0_flash(num_layers=12, vocab=1024, experts_held=(0, 8))
    assert cfg.layer_types == ((T.KDA,) * 5 + (T.LATENT,)) * 2
    assert (cfg.moe.groups, cfg.moe.groups_kept, cfg.moe.top_k) == (8, 4, 8)
    shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))
    d, wide = 2560, 32 * 128
    assert count(shapes["layer0"]["kda"]) == 52_650_016 == (
        4 * d * wide + wide * d + 2 * d * 32 + 4 * 3 * wide + 32 + 2 * wide)
    assert count(shapes["layer5"]["attn"]) == 31_965_696 == (
        d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + wide * d + d * 32)
    assert "mlp" in shapes["layer1"] and "moe" in shapes["layer2"]
    assert count(shapes["layer0"]["mlp"]) == 3 * 2560 * 6144
    assert shapes["layer2"]["moe"]["gate"]["kernel"].shape == (2560, 512)
    assert shapes["layer2"]["moe"]["up"]["kernel"].shape == (8, 2560, 768)
    assert lm.ling_3_0_flash().num_layers == 42


# -- the group-limited choice -------------------------------------------------

def _numpy_choice(choice, groups, kept, top_k):
    """Sort and mask in numpy: stable sorts, ties to the lower index."""
    tokens, experts = choice.shape
    size = experts // groups
    chosen = np.zeros((tokens, top_k), np.int64)
    for t in range(tokens):
        by_group = choice[t].reshape(groups, size)
        score = np.sort(by_group, axis=-1)[:, -2:].sum(-1)
        best = np.argsort(-score, kind="stable")[:kept]
        masked = np.full(experts, -np.inf)
        for g in best:
            held = slice(g * size, (g + 1) * size)
            masked[held] = choice[t, held]
        chosen[t] = np.argsort(-masked, kind="stable")[:top_k]
    return chosen


@pytest.mark.parametrize("ties", [False, True])
def test_the_choice_stays_inside_the_best_groups(ties):
    """``_route_sigmoid`` with groups against a sort-and-mask in numpy, and
    the reference's ``route`` against both; with ``ties`` the logits take
    three values only, so groups and experts tie by the dozen and the lower
    index wins everywhere."""
    cfg = moe.MoEConfig(num_experts=32, top_k=4, d_model=8, d_hidden=8,
                        expert="swiglu", scoring="sigmoid", route_scale=2.5,
                        select_bias=True, groups=8, groups_kept=3)
    key = jax.random.PRNGKey(5)
    logits = jax.random.normal(key, (64, 32))
    if ties:
        logits = jnp.round(logits)
        logits = jnp.clip(logits, -1, 1)
    bias = jnp.zeros((32,)) if ties else 0.3 * jax.random.normal(
        jax.random.fold_in(key, 1), (32,))
    top_vals, top_idx, _ = moe._route_sigmoid(logits, {"bias": bias}, cfg)
    scores = np.asarray(jax.nn.sigmoid(logits))
    want = _numpy_choice(scores + np.asarray(bias), 8, 3, 4)
    np.testing.assert_array_equal(np.asarray(top_idx), want)
    assert (len({int(i) // 4 for i in row}) <= 3 for row in want)
    picked = np.take_along_axis(scores, want, axis=-1)
    np.testing.assert_allclose(
        top_vals, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # The reference routes by logits of x @ gate: feed it the logits.
    weights, chosen, _ = ref.route(
        {"gate": {"kernel": jnp.eye(32)}, "bias": bias}, logits[None],
        top_k=4, route_scale=2.5, groups=8, groups_kept=3)
    mask = np.zeros((64, 32), bool)
    np.put_along_axis(mask, want, True, axis=-1)
    np.testing.assert_array_equal(np.asarray(chosen[0]), mask)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights[0]), want, axis=-1), top_vals,
        rtol=1e-6)
    # Without the limit some token's choice spans more groups.
    free = moe.MoEConfig(num_experts=32, top_k=4, d_model=8, d_hidden=8,
                         expert="swiglu", scoring="sigmoid", select_bias=True)
    _, free_idx, _ = moe._route_sigmoid(logits, {"bias": bias}, free)
    if not ties:
        assert max(len({int(i) // 4 for i in row})
                   for row in np.asarray(free_idx)) == 4


@pytest.mark.parametrize("kwargs", [
    dict(groups=8), dict(groups=5, groups_kept=2),
    dict(groups=8, groups_kept=9), dict(groups=8, groups_kept=1, top_k=5),
    dict(groups=8, groups_kept=4, scoring="softmax")])
def test_groups_that_do_not_fit_are_refused(kwargs):
    args = dict(num_experts=32, top_k=4, expert="swiglu", scoring="sigmoid")
    args.update(kwargs)
    with pytest.raises(ValueError, match="groups / groups_kept"):
        moe.MoEConfig(**args)


# -- latent attention's two new forms -----------------------------------------

def test_full_rank_queries_and_a_head_gate_are_their_parameters():
    """``mla_init(q_rank=0, gate=True)``: one ``q`` matrix, no ``q_down`` /
    ``q_norm`` / ``q_up``, a gate a head; ``mla`` of them is the reference's
    latent attention, and the gate is in it.  JoyAI's form (a low-rank
    query, no gate) keeps its variables and its equations."""
    p = L.mla_init(jax.random.PRNGKey(0), 32, 4, 0, 16, 8, 4, 8, gate=True)
    assert sorted(p) == ["gate", "kv_down", "kv_norm", "kv_up", "out", "q"]
    assert p["q"]["kernel"].shape == (32, 4 * 12)
    assert p["gate"]["kernel"].shape == (32, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    rope = L.rope_pair_tables(24, 4, 6e6)
    with jax.default_matmul_precision("highest"):
        got = L.mla(p, x, 4, 8, 4, 8, rope)
        want = ref.latent_attention(p, x, heads=4, nope=8, rope=4, eps=1e-6,
                                    theta=6e6)
        ungated = L.mla({k: v for k, v in p.items() if k != "gate"}, x, 4, 8,
                        4, 8, rope)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(got - ungated).max()) > 1e-2
    low = L.mla_init(jax.random.PRNGKey(0), 32, 4, 12, 16, 8, 4, 8)
    assert sorted(low) == ["kv_down", "kv_norm", "kv_up", "out", "q_down",
                           "q_norm", "q_up"]
    for name in ("kv_down", "kv_up", "out"):    # the same keys draw them
        np.testing.assert_array_equal(low[name]["kernel"], p[name]["kernel"])
    assert "logistic" not in str(jax.make_jaxpr(
        lambda p, x: L.mla(p, x, 4, 8, 4, 8, rope))(low, x))


def test_a_gate_under_the_rules_bound_is_refused():
    p = L.kda_init(jax.random.PRNGKey(0), 32, 4, 8, 8)
    x = jnp.zeros((1, 16, 32))
    with pytest.raises(ValueError, match="overflows the chunked rule"):
        L.kda(p, x, 4, gate_lower_bound=-6.0)
    with pytest.raises(ValueError, match="linear_gate_bound"):
        _cfg(linear_gate_bound=None)


def test_the_initial_decays_are_the_draws():
    """At a zero projection the bounded gate gives ``-exp(A_log) dt`` with
    ``dt`` in [1e-3, 1e-1] and ``exp(A_log)`` in [1, 16]."""
    p = L.kda_init(jax.random.PRNGKey(4), 32, 4, 8, 8)
    rate = jnp.exp(p["A_log"])[:, None]
    g = -5.0 * jax.nn.sigmoid(rate * p["dt_bias"].reshape(4, 8))
    step = -g / rate
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert 0.999e-3 <= float(step.min()) and float(step.max()) <= 1.001e-1


# -- the share: the ranks add up to the whole layer ---------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: each of 4 ranks holds 4 of 16
    experts (one of 4 groups) and computes its held experts' part plus the
    shared expert; the 4 routed parts, and the shared expert counted once,
    add up to what the uncut reference gives for the whole layer, whose
    router is limited to 2 of 4 groups."""
    ranks, count, experts = 4, 4, 16
    args = dict(num_experts=experts, top_k=5, d_model=32, d_hidden=24,
                expert="swiglu", norm_topk=True, scoring="sigmoid",
                route_scale=2.5, shared=1, select_bias=True, groups=4,
                groups_kept=2)
    params = moe.init(jax.random.PRNGKey(0), moe.MoEConfig(**args))
    params["bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(7),
                                             (experts,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    router = dict(top_k=5, route_scale=2.5, groups=4, groups_kept=2)
    with jax.default_matmul_precision("highest"):
        want, counts, _, whole = ref.experts_layer(
            params, x, held=(0, experts), **router)
        shared = ref.swiglu(params["shared"], x)
        total, rows, reached = jnp.zeros_like(x), 0.0, []
        for rank in range(ranks):
            held = (rank * count, count)
            cfg = moe.MoEConfig(**args, held=held)
            mine = {**params, **{
                name: {"kernel": params[name]["kernel"][
                    held[0]:held[0] + count]}
                for name in ("glu", "up", "down")}}
            out, stats = jax.jit(
                lambda p, x, cfg=cfg: moe.dropless_apply(p, cfg, x))(mine, x)
            total = total + (out - shared)
            rows += float(stats["held_assignments"])
            reached.append(float(stats["groups_reached"]))
            assert float(stats["dropped"]) == 0.0
    assert rows == 2 * 24 * 5 == float(counts.sum())
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    assert all(1.0 <= r <= 2.0 for r in reached) and len(set(reached)) == 1
    np.testing.assert_allclose(reached[0], whole, rtol=1e-6)


# -- what JoyAI's cell traces is what it traced -------------------------------

def _text_hash(jaxpr):
    import hashlib
    import re
    return hashlib.sha1(re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr)).encode()) \
        .hexdigest()[:16]


def test_a_low_rank_query_and_a_router_without_groups_trace_what_they_traced():
    """Pinned on PR 46's tree (2afff19), before ``mla`` took full-rank
    queries and a gate and ``_route_sigmoid`` took groups: ``grad`` of JoyAI's
    latent mixer (a low-rank query, no gate) and of its held expert layer (256
    sigmoid experts, no groups) trace the same text."""
    p = jax.eval_shape(lambda: L.mla_init(jax.random.PRNGKey(0), 2048, 32,
                                          1536, 512, 128, 64, 128))
    x = jax.ShapeDtypeStruct((1, 256, 2048), jnp.bfloat16)
    rope = jax.eval_shape(lambda: L.rope_pair_tables(256, 64, 32e6))
    assert _text_hash(jax.make_jaxpr(jax.grad(
        lambda p, x, r: L.mla(p, x, 32, 128, 64, 128, r, dtype=jnp.bfloat16)
        .astype(jnp.float32).sum()))(p, x, rope)) == "e2f72717e893f832"
    cfg = moe.MoEConfig(
        num_experts=256, top_k=8, d_model=256, d_hidden=128,
        dtype=jnp.bfloat16, expert="swiglu", scoring="sigmoid",
        route_scale=2.5, shared=1, select_bias=True, bias_update_rate=0.001,
        held=(0, 16))
    mp = jax.eval_shape(lambda: moe.init(jax.random.PRNGKey(0), cfg))
    xx = jax.ShapeDtypeStruct((1, 512, 256), jnp.bfloat16)
    assert _text_hash(jax.make_jaxpr(jax.grad(
        lambda p, x: moe.dropless_apply(p, cfg, x)[0].astype(jnp.float32)
        .sum()))(mp, xx)) == "5607022bd0a02627"
