"""The Olmo-Hybrid block (``models/transformer.py`` with ``layer_types``,
output norms, a SwiGLU MLP, no positions; ``layers.gdn`` over
``ops/gated_delta.py``) against the plain reference
(``chipbench/reference_olmo_hybrid.py``) on seeded random weights at a small
size on the CPU: logits, loss and every leaf's gradient in float32; each
planted fault of the cell's check fails the comparison; the pattern decides
which parameters a layer holds; the preset's parameter count; the defaults'
jaxpr is what it was."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models import layers as L
from autodist_tpu.models import lm
from autodist_tpu.models import transformer as T
from autodist_tpu.observability import profile
from autodist_tpu.ops import gated_delta
from chipbench import reference_olmo_hybrid as ref

TOY = dict(vocab=97, dim=48, num_heads=4, num_layers=4, mlp_dim=80,
           max_len=64, causal=True, dtype=jnp.float32, norm="rmsnorm",
           norm_eps=1e-6, positions="none", qk_norm=True, bias=False,
           tied_head=False, ffn="swiglu",
           layer_types=[T.LINEAR] * 3 + [T.FULL], linear_heads=3,
           linear_key_dim=8, linear_value_dim=16, conv_width=4,
           allow_neg_eigval=True, norm_position="output")
SEQ = 40


def _toy(**changes):
    """A configuration, loud parameters and a batch: the gates' projections
    and the norms' scales away from their initial values, so that each
    mechanism moves the loss."""
    cfg = T.TransformerConfig(**{**TOY, **changes})
    params = lm.init(jax.random.PRNGKey(3), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    for name, layer in params.items():
        if not name.startswith("layer"):
            continue
        for norm in ("ln1", "ln2"):
            layer[norm]["scale"] = 1.0 + 0.3 * jax.random.normal(
                next(keys), layer[norm]["scale"].shape)
        if "gdn" in layer:
            for gate in ("a", "b"):
                layer["gdn"][gate]["kernel"] = 4.0 * layer["gdn"][gate]["kernel"]
            layer["gdn"]["norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
                next(keys), layer["gdn"]["norm"]["scale"].shape)
        else:
            for norm in ("q_norm", "k_norm"):
                layer["attn"][norm]["scale"] = 3.0 * layer["attn"][norm]["scale"]
    batch = lm.synthetic_batch(cfg, batch_size=2, seq_len=SEQ, seed=5)
    return cfg, params, batch


def _reference(cfg):
    model = dict(layer_types=cfg.layer_types, heads=cfg.num_heads,
                 linear_heads=cfg.linear_heads, eps=cfg.norm_eps,
                 neg_eigval=cfg.allow_neg_eigval)
    return (lambda params, batch: ref.loss(params, batch[0], **model)), model


def _scalar(loss_fn):
    return lambda params, batch: loss_fn(params, batch)[0]


def _worst_leaf(got, want):
    """The largest difference of any leaf, relative to the leaf's largest
    magnitude, and the leaf's path."""
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-30)), got, want)
    path, worst = max(jax.tree_util.tree_flatten_with_path(diffs)[0],
                      key=lambda item: item[1])
    return worst, jax.tree_util.keystr(path)


# -- the block is the reference's ----------------------------------------------

def test_logits_loss_and_every_gradient_equal_the_references():
    cfg, params, batch = _toy()
    reference_loss, model = _reference(cfg)
    with jax.default_matmul_precision("highest"):
        hidden, stats = jax.jit(lambda p, ids: T.encode_with_stats(
            p, cfg, ids))(params, batch[0][:, :-1])
        logits = T.logits(params, cfg, hidden)
        want_logits = jax.jit(lambda p, ids: ref.logits(p, ids, **model))(
            params, batch[0][:, :-1])
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lm.make_loss_fn(cfg), has_aux=True))(params, batch)
        want, want_grads = jax.jit(jax.value_and_grad(reference_loss))(
            params, batch)
    assert len(stats) == 3 and set(stats[0]) == {"gdn_state_absmax"}
    # float32 on both sides, sums in another order (a chunk at a time
    # against a position at a time) through four layers of norms: the worst
    # logit is 1e-5 to 1.2e-4 of the largest away, by the parameters drawn.
    assert float(jnp.max(jnp.abs(logits - want_logits))
                 / jnp.max(jnp.abs(want_logits))) < 5e-4
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert set(aux) == {"xent", "gdn.state_absmax"}
    assert float(aux["xent"]) == float(loss)
    assert 0.0 < float(aux["gdn.state_absmax"]) < 100.0
    assert jax.tree_util.tree_structure(grads) == \
        jax.tree_util.tree_structure(want_grads)
    # Every leaf's gradient, against the leaf's largest magnitude: float32
    # on both sides leaves 1e-4 to 2.4e-4 between them on the worst leaf
    # (the program with the rule a position at a time is as far from the
    # reference as the chunked one), and the planted faults below leave
    # 0.3 or more: the limit is 1e-3.
    worst, where = _worst_leaf(grads, want_grads)
    assert worst < 1e-3, (worst, where)


def test_the_state_absmax_is_the_final_states():
    """``aux["gdn.state_absmax"]`` is the largest magnitude of a linear
    layer's state after the row's last position."""
    cfg, params, batch = _toy()
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, cfg.dim))
    p = params["layer0"]["gdn"]
    y, state = L.gdn(p, x, cfg.linear_heads, dtype=jnp.float32)
    assert y.shape == x.shape
    assert state.shape == (2, cfg.linear_heads, 8, 16)
    _, stats = T.block_apply(params["layer0"], x, cfg)
    assert float(stats["gdn_state_absmax"]) == pytest.approx(
        float(jnp.max(jnp.abs(state))), rel=1e-6)


# -- each planted fault fails the comparison -------------------------------------

def _no_decay(monkeypatch):
    rule = gated_delta.gated_delta_rule
    monkeypatch.setattr(
        gated_delta, "gated_delta_rule",
        lambda q, k, v, g, beta, **kw: rule(q, k, v, 0.0 * g, beta, **kw))
    return {}


def _beta_without_its_factor(monkeypatch):
    return {"allow_neg_eigval": False}


def _no_convolution(monkeypatch):
    monkeypatch.setattr(L, "causal_depthwise_conv", lambda kernel, x: x)
    return {}


def _k_not_normalised(monkeypatch):
    unit, calls = L.l2_unit, itertools.count()
    monkeypatch.setattr(
        L, "l2_unit", lambda t, eps=1e-6: unit(t, eps)
        if next(calls) % 2 == 0 else t.astype(jnp.float32))
    return {}


def _no_output_gate(monkeypatch):
    monkeypatch.setattr(L, "gated_rmsnorm",
                        lambda p, x, gate, eps=1e-6: L.rmsnorm(p, x, eps))
    return {}


def _no_delta_term(monkeypatch):
    """``S_t = alpha_t S_(t-1) + beta_t k_t v_t^T``: in the chunked form,
    ``T = I`` and nothing read of ``S_0``, ``U = diag(beta) V``."""
    monkeypatch.setattr(gated_delta, "_chunk_reads",
                        lambda k, v, s0, into: (0.0 * v, 1.0 * v))
    monkeypatch.setattr(
        gated_delta, "_chunk_writes",
        lambda t, beta, rhs: gated_delta._column(
            beta, gated_delta._masks(beta.shape[1])[0]) * rhs)
    # The rule is an inlined ``jit``: around its cache, which would hand
    # this test a sound trace of another's and a later test this one's.
    monkeypatch.setattr(gated_delta, "_chunked_rule",
                        gated_delta._chunked_rule.__wrapped__)
    return {}


def _rotary_on_the_full_layer(monkeypatch):
    return {"positions": "rope"}


def _pre_norm(monkeypatch):
    return {"norm_position": "pre"}


FAULTS = [_no_decay, _beta_without_its_factor, _no_convolution,
          _k_not_normalised, _no_output_gate, _no_delta_term,
          _rotary_on_the_full_layer, _pre_norm]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """The program with one thing wrong, against the reference that is
    right: the loss is 2e-4 away or more (the right program: 2e-7), and some
    leaf's gradient by more than a third of its own size (the right
    program: 2.4e-4 at most)."""
    cfg, params, batch = _toy()
    reference_loss, _ = _reference(cfg)
    broken = T.TransformerConfig(**{**TOY, **fault(monkeypatch)})
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(_scalar(lm.make_loss_fn(broken)))(
            params, batch)
        want, want_grads = jax.jit(jax.value_and_grad(reference_loss))(
            params, batch)
    assert abs(float(loss) - float(want)) / float(want) > 1e-4
    assert _worst_leaf(grads, want_grads)[0] > 0.3


# -- the configuration's fields --------------------------------------------------

def test_the_pattern_decides_which_parameters_a_layer_holds():
    cfg, params, _ = _toy()
    assert sorted(params) == ["embed", "layer0", "layer1", "layer2",
                              "layer3", "lm_head", "ln_f"]
    for i in range(3):
        assert sorted(params[f"layer{i}"]) == ["gdn", "ln1", "ln2", "mlp"]
    assert sorted(params["layer3"]) == ["attn", "ln1", "ln2", "mlp"]
    gdn = params["layer0"]["gdn"]
    assert sorted(gdn) == ["A_log", "a", "b", "conv", "dt_bias", "k", "norm",
                           "out", "q", "v", "z"]
    assert gdn["conv"]["kernel"].shape == (4, 2 * 3 * 8 + 3 * 16)
    assert gdn["A_log"].shape == gdn["dt_bias"].shape == (3,)
    assert gdn["norm"]["scale"].shape == (16,)
    assert gdn["q"]["kernel"].shape == (48, 24)
    assert gdn["out"]["kernel"].shape == (48, 48)
    assert all(set(gdn[name]) == {"kernel"} for name in "qkvzab")
    assert sorted(params["layer3"]["mlp"]) == ["down", "gate", "up"]
    assert sorted(params["layer3"]["attn"]) == ["k_norm", "key", "out",
                                                "q_norm", "query", "value"]
    # The decays start inside (0, 1) and the steps inside [1e-3, 1e-1].
    assert bool((jax.nn.softplus(gdn["dt_bias"]) >= 1e-3 - 1e-9).all())
    assert bool((jax.nn.softplus(gdn["dt_bias"]) <= 1e-1 + 1e-9).all())
    assert bool((jnp.exp(gdn["A_log"]) <= 16.0).all())
    # Another pattern, other parameters.
    other = lm.init(jax.random.PRNGKey(0), T.TransformerConfig(
        **{**TOY, "layer_types": [T.FULL, T.LINEAR, T.FULL, T.LINEAR]}))
    assert ["gdn" in other[f"layer{i}"] for i in range(4)] == [
        False, True, False, True]


def test_no_new_variable_meets_a_rule_meant_for_another():
    """The Megatron-name rules split ``attn/{query,key,value,out}`` and
    ``mlp/{up,down}``: a linear layer's variables match none of them, the
    SwiGLU MLP's ``up`` and ``down`` are the MLP's, and its ``gate`` is
    left whole."""
    import re
    from autodist_tpu.parallel import sharding_rules
    cfg, params, _ = _toy()
    names = ["/".join(str(k.key) for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    rules = [pattern for pattern, _ in sharding_rules.MEGATRON_RULES]
    matched = {n for n in names if any(re.search(r, n) for r in rules)}
    assert not any("/gdn/" in n for n in matched)
    assert {n.split("/", 1)[1] for n in matched} == {
        "attn/query/kernel", "attn/key/kernel", "attn/value/kernel",
        "attn/out/kernel", "mlp/up/kernel", "mlp/down/kernel", "embedding"}


def test_what_the_configuration_refuses():
    with pytest.raises(NotImplementedError, match="scan_layers"):
        T.TransformerConfig(**{**TOY, "scan_layers": True})
    with pytest.raises(NotImplementedError, match="scan_layers"):
        T.TransformerConfig(**{**TOY, "scan_layers": True,
                               "layer_types": [T.LINEAR] * 4})
    with pytest.raises(ValueError, match="layer_types"):
        T.TransformerConfig(**{**TOY, "layer_types": [T.LINEAR] * 3})
    with pytest.raises(ValueError, match="layer_types"):
        T.TransformerConfig(**{**TOY, "layer_types": ["window"] * 4})
    with pytest.raises(ValueError, match="linear_heads"):
        T.TransformerConfig(**{**TOY, "linear_heads": 0})
    with pytest.raises(ValueError, match="positions must be one of"):
        T.TransformerConfig(positions="alibi")
    with pytest.raises(ValueError, match="norm_position must be one of"):
        T.TransformerConfig(norm_position="both")
    cfg, params, _ = _toy()
    with pytest.raises(NotImplementedError, match="R2"):
        T.init_cache(cfg, 2, 8)
    with pytest.raises(NotImplementedError, match="R2"):
        T.decode_step(params, cfg, {}, jnp.zeros((2,), jnp.int32),
                      jnp.zeros((2,), jnp.int32))
    # One kind throughout may be stacked, if it is full attention.
    stacked = T.TransformerConfig(**{**TOY, "scan_layers": True,
                                     "layer_types": [T.FULL] * 4})
    assert "blocks" in lm.init(jax.random.PRNGKey(0), stacked)


def test_the_preset_has_the_published_sizes():
    def count(shapes):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(shapes))

    cfg = lm.olmo_hybrid_7b()
    assert cfg.layer_types == ((T.LINEAR,) * 3 + (T.FULL,)) * 8
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    assert count(shapes) == 7_430_870_688
    assert count(shapes["layer0"]) == 215_570_172
    assert count(shapes["layer3"]) == 185_809_920
    gdn = shapes["layer0"]["gdn"]
    assert gdn["conv"]["kernel"].shape == (4, 11520)
    assert gdn["q"]["kernel"].shape == (3840, 2880)
    assert gdn["z"]["kernel"].shape == (3840, 5760)
    assert gdn["norm"]["scale"].shape == (192,)
    assert shapes["layer3"]["mlp"]["gate"]["kernel"].shape == (3840, 11008)
    assert shapes["lm_head"]["kernel"].shape == (3840, 100352)
    assert "pos_embed" not in shapes
    # The benchmark's cut: one period and an eighth of the vocabulary.
    cut = lm.olmo_hybrid_7b(num_layers=4, vocab=12544)
    assert cut.layer_types == (T.LINEAR,) * 3 + (T.FULL,)
    assert count(jax.eval_shape(lambda k: lm.init(k, cut),
                                jax.random.PRNGKey(0))) == 928_862_196


def test_the_default_blocks_jaxpr_does_not_know_the_new_fields():
    """``tests/test_olmoe.py`` holds the default block to the jaxpr of
    before PR 25; here: stating the new fields' defaults changes nothing."""
    plain = lm.lm_tiny()
    stated = T.TransformerConfig(
        vocab=256, dim=64, num_heads=4, num_layers=2, max_len=64, causal=True,
        dtype=jnp.float32, layer_types=[T.FULL, T.FULL], norm_position="pre",
        ffn="mlp", positions="learned")
    params = lm.init(jax.random.PRNGKey(0), plain)
    batch = lm.synthetic_batch(plain, batch_size=2, seq_len=16)
    for fn in (lambda f: f, jax.grad):
        assert str(jax.make_jaxpr(fn(lm.make_loss_fn(plain)))(params, batch)) \
            == str(jax.make_jaxpr(fn(lm.make_loss_fn(stated)))(params, batch))
    same = jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), params,
        lm.init(jax.random.PRNGKey(0), stated))
    assert all(jax.tree_util.tree_leaves(same))


# -- the convolution ---------------------------------------------------------------

def test_the_convolutions_gradient_is_autodiffs():
    """``causal_depthwise_conv`` writes its own gradient; against autodiff
    through the same four shifted multiplies."""
    kernel = jax.random.normal(jax.random.PRNGKey(0), (4, 6))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 6))
    weights = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def plain(kernel, x):
        return jnp.sum(L._conv(kernel, x) * weights)

    def ours(kernel, x):
        return jnp.sum(L.causal_depthwise_conv(kernel, x) * weights)

    np.testing.assert_allclose(L.causal_depthwise_conv(kernel, x),
                               ref.short_convolution(kernel, x), atol=1e-6)
    for got, want in zip(jax.grad(ours, (0, 1))(kernel, x),
                         jax.grad(plain, (0, 1))(kernel, x)):
        np.testing.assert_allclose(got, want, atol=1e-5)
    # Causal: position t sees t - 3 .. t and nothing later.
    bumped = x.at[:, 5].add(1.0)
    moved = jnp.abs(L.causal_depthwise_conv(kernel, bumped)
                    - L.causal_depthwise_conv(kernel, x)).sum(axis=(0, 2))
    assert [bool(m > 0) for m in moved] == [False] * 5 + [True] * 4


# -- tracing: scopes, aux through the Runner -----------------------------------

@pytest.mark.parametrize("op_name, scope, phase", [
    ("jit(step)/jvp(layer0)/gdn/proj/dot_general", "gdn/proj", "forward"),
    ("jit(step)/jvp(layer1)/gdn/conv/mul", "gdn/conv", "forward"),
    ("jit(step)/jvp(layer2)/gdn/gates/rsqrt", "gdn/gates", "forward"),
    ("jit(step)/jvp(layer0)/gdn/scan/checkpoint/while/body/dot_general",
     "gdn/scan", "forward"),
    ("jit(step)/transpose(jvp(layer1))/gdn/scan/triangular_solve",
     "gdn/scan", "backward"),
    ("jit(step)/transpose(jvp(layer2))/gdn/out/dot_general", "gdn/out",
     "backward"),
    ("jit(step)/jvp(layer0)/gdn/mul", "gdn", "forward"),
    ("jit(step)/jvp(layer3)/attn/dot_general", "attn", "forward"),
    ("jit(step)/jvp(layer0)/mlp/dot_general", "mlp", "forward"),
])
def test_the_mixers_scopes_fold_over_the_layers(op_name, scope, phase):
    assert profile._scope_and_phase(op_name) == (scope, phase)


def test_the_step_carries_the_state_absmax_and_names_its_scopes():
    from autodist_tpu import AutoDist, observability, strategy
    observability.reset()
    gated_delta._announced.clear()
    cfg, params, batch = _toy()
    batch = (np.tile(batch[0], (4, 1)),)            # 8 rows on 8 devices
    loss_fn = lm.make_loss_fn(cfg)
    ad = AutoDist(strategy_builder=strategy.PartitionedPS())
    item = ad.capture(loss_fn, params, optax.adam(1e-3), example_batch=batch)
    assert item.aux_output is True
    assert {v.shape for v in item.variables
            if v.name.endswith("gdn/conv/kernel")} == {(4, 96)}
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    state, metrics = runner.step(state, batch)
    assert runner.last_aux is metrics["aux"]
    assert set(metrics["aux"]) == {"xent", "gdn.state_absmax"}
    want, aux = loss_fn(params, batch)
    assert float(metrics["loss"]) == pytest.approx(float(want), rel=1e-5)
    # One chip's rows under the explicit lowering, averaged over the chips.
    assert 0.0 < float(metrics["aux"]["gdn.state_absmax"]) \
        <= float(aux["gdn.state_absmax"]) * (1 + 1e-5)
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["gdn.heads"] == 3 and gauges["gdn.chunk"] == 64
    assert gauges["gdn.chunks_per_row"] == 1
    from autodist_tpu.observability import recorder
    details = [e["detail"] for e in recorder.events() if e["kind"] == "gdn"]
    assert details and all(
        "inverse: block products" in d and gated_delta.BACKWARD in d
        for d in details)
    scopes = {scope for scope, _ in runner.scope_table().values()}
    assert {"gdn/proj", "gdn/conv", "gdn/gates", "gdn/scan", "gdn/out",
            "attn", "mlp", "head"} <= scopes
