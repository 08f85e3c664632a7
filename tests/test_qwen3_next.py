"""The Qwen3-Next block at a toy size on the CPU: gated-delta layers whose
value heads read fewer key heads, a full-attention layer with a norm a head, a
part of the lanes rotated and a sigmoid gate a lane, and in every layer
softmax-routed experts of which a share is held beside a gated shared one;
program against the plain reference (``chipbench/reference_gdn_moe.py``)."""
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
from autodist_tpu.ops.gated_delta import gated_delta_rule
from autodist_tpu.parallel import moe
from chipbench import reference_gdn_moe as ref

fa = importlib.import_module("autodist_tpu.ops.flash_attention")

PERIOD = (T.LINEAR,) * 3 + (T.FULL,)
ROTARY, HEAD = 4, 16


def _cfg(periods=2, **kw):
    """Periods of linear, linear, linear, full: 4 value heads of 8 on 2 key
    heads; 4 query heads of 16 over 2 key-value heads; 16 experts, 4 held."""
    args = dict(
        vocab=64, dim=32, num_heads=4, num_layers=4 * periods, mlp_dim=48,
        max_len=64, causal=True, dtype=jnp.float32, norm="rmsnorm",
        norm_eps=1e-6, positions="rope", qk_norm="head", bias=False,
        tied_head=False, ffn="moe", num_experts=16, experts_per_token=5,
        expert_dim=24, norm_topk=True, load_balance_coef=0.001,
        layer_types=PERIOD * periods, linear_heads=4, linear_key_heads=2,
        linear_key_dim=8, linear_value_dim=8, conv_width=4,
        allow_neg_eigval=False, expert_scoring="softmax", shared_experts=1,
        shared_gate=True, experts_held=(4, 4), head_dim=HEAD, kv_heads=2,
        attn_gate="lane",
        rope_by_type={T.FULL: {"theta": 1e7, "lanes": ROTARY, "yarn": None}})
    args.update(kw)
    return T.TransformerConfig(**args)


def _model(cfg):
    return dict(layer_types=cfg.layer_types, rotary_lanes=ROTARY, theta=1e7,
                eps=cfg.norm_eps, heads=cfg.linear_heads,
                key_heads=cfg.linear_key_heads, head_dim=cfg.head_dim,
                top_k=cfg.moe.top_k, held=cfg.moe.held,
                balance_coef=cfg.load_balance_coef)


def _tokens(rows=2, seq=32, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              64)


# -- the rule with fewer key heads ----------------------------------------------

@pytest.mark.parametrize("ratio", [1, 2, 4])
@pytest.mark.parametrize("s, chunk", [(48, 16), (20, 8)])
def test_the_grouped_rule_is_the_recurrence(ratio, s, chunk):
    """Value head ``h`` reads key head ``h // ratio``: the chunked rule's
    output and the gradients of q, k, v, g and beta against the reference's
    recurrence one position at a time, which indexes the key heads."""
    heads, d_k, d_v = 4, 8, 12
    ks = jax.random.split(jax.random.PRNGKey(s + ratio), 6)
    q = L.l2_unit(jax.random.normal(ks[0], (2, s, heads // ratio, d_k))) \
        * d_k ** -0.5
    k = L.l2_unit(jax.random.normal(ks[1], (2, s, heads // ratio, d_k)))
    v = jax.random.normal(ks[2], (2, s, heads, d_v))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (2, s, heads)))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (2, s, heads)))
    weights = jax.random.normal(ks[5], v.shape)

    def chunked(*a):
        return jnp.sum(gated_delta_rule(*a, chunk=chunk)[0] * weights)

    def stepwise(q, k, v, g, beta):
        return jnp.sum(ref.delta_rule(q, k, v, jnp.exp(g), beta) * weights)

    with jax.default_matmul_precision("highest"):
        got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4)))(
            q, k, v, g, beta) for f in (chunked, stepwise))
        o, state = gated_delta_rule(q, k, v, g, beta, chunk=chunk)
    assert o.shape == v.shape and state.shape == (2, heads, d_k, d_v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip("q k v g beta".split(), got[1], want[1]):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)


def test_key_heads_that_do_not_divide_the_heads_are_refused():
    q = jnp.zeros((1, 8, 3, 4))
    with pytest.raises(ValueError, match="key heads that divide"):
        gated_delta_rule(q, q, jnp.zeros((1, 8, 4, 4)), jnp.zeros((1, 8, 4)),
                         jnp.zeros((1, 8, 4)))
    with pytest.raises(ValueError, match="do not group"):
        L.gdn_init(jax.random.PRNGKey(0), 32, 4, 8, 8, key_heads=3)


def test_as_many_key_heads_as_heads_trace_the_equations_of_none():
    """``key_heads`` None, or equal to the heads, is the mixer of before the
    field: the same values from the same key and the same jaxpr."""
    plain = L.gdn_init(jax.random.PRNGKey(0), 32, 4, 8, 12)
    stated = L.gdn_init(jax.random.PRNGKey(0), 32, 4, 8, 12, key_heads=4)
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), plain, stated)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    traced = [str(jax.make_jaxpr(jax.grad(
        lambda p, x, kh=kh: L.gdn(p, x, 4, key_heads=kh)[0].sum()))(plain, x))
        for kh in (None, 4)]
    assert traced[0] == traced[1]
    assert "broadcast_in_dim[broadcast_dimensions=(0, 1, 2, 4, 5)" \
        not in traced[0]


# -- the model against the reference -----------------------------------------------

@pytest.mark.parametrize("core, periods", [("dense", 2), ("kernels", 1)])
def test_the_loss_and_every_leafs_gradient_match_the_reference(core, periods,
                                                               monkeypatch):
    """``lm.make_loss_fn`` over whole periods: the loss within 1e-5 of the
    reference's and every leaf's gradient within 2e-4 of its largest entry,
    two periods on the dense path and one through the interpreted kernels."""
    if core == "kernels":
        monkeypatch.setattr(fa, "_pallas_interpret", lambda *_: True)
    cfg = _cfg(periods, mixer_stats=core == "dense")
    params = lm.init(jax.random.PRNGKey(0), cfg)
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lm.make_loss_fn(cfg), has_aux=True))(params, (tokens,))
        (want, probed), want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_and_probes(p, tokens, **_model(cfg)),
            has_aux=True))(params)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(got) == len(jax.tree_util.tree_leaves(want_grads))
    for (path, g), e in zip(got, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.abs(e).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, e, atol=2e-4 * float(jnp.abs(e).max()),
            err_msg=jax.tree_util.keystr(path))
    np.testing.assert_allclose(aux["moe.held_output_rms"],
                               probed["held_output_rms"], rtol=1e-4)
    assert float(aux["moe.dropped"]) == 0.0
    assert 0 < float(aux["moe.held_assignments"]) < 4 * periods * 2 * 32 * 5
    # What the mixers add to the stream, where the configuration asks.
    mixers = ["attn.output_std", "gdn.output_std"] if cfg.mixer_stats else []
    for name in mixers:
        np.testing.assert_allclose(aux[name], probed[name.replace(".", "_")],
                                   rtol=1e-4)
    assert sorted(aux) == sorted(mixers + [
        "gdn.state_absmax", "moe.dropped", "moe.held_assignments",
        "moe.held_buffer_rows", "moe.held_output_rms",
        "moe.load_balance_loss", "moe.load_max_over_mean",
        "moe.router_z_loss", "xent"])


def test_three_adam_steps_through_the_runner_match_the_reference(steps=3):
    from chipbench import reference
    _reset_default()
    cfg = _cfg(1)
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
    assert "gdn.state_absmax" in runner.last_aux
    assert "moe.held_output_rms" in runner.last_aux
    _reset_default()


# -- the share: sixteen ranks add up to the whole layer ---------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: each of 16 ranks holds 2 of 32
    experts and computes its held experts' part plus the gated shared expert;
    the 16 routed parts, and the shared expert counted once, add up to what
    the uncut reference gives for the whole layer."""
    ranks, count, experts = 16, 2, 32
    whole = moe.MoEConfig(num_experts=experts, top_k=5, d_model=32,
                          d_hidden=24, expert="swiglu", norm_topk=True,
                          shared=1, shared_gate=True)
    params = moe.init(jax.random.PRNGKey(0), whole)
    assert params["shared_gate"]["kernel"].shape == (32, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.experts_layer(params, x, top_k=5,
                                       held=(0, experts))
        shared = ref.shared_expert(params, x)
        total, rows = jnp.zeros_like(x), 0.0
        for rank in range(ranks):
            held = (rank * count, count)
            cfg = moe.MoEConfig(num_experts=experts, top_k=5, d_model=32,
                                d_hidden=24, expert="swiglu", norm_topk=True,
                                shared=1, shared_gate=True, held=held)
            mine = {**params, **{
                name: {"kernel": params[name]["kernel"][
                    held[0]:held[0] + count]}
                for name in ("glu", "up", "down")}}
            out, stats = jax.jit(
                lambda p, x, cfg=cfg: moe.dropless_apply(p, cfg, x))(mine, x)
            # Every rank computes the gated shared expert whole.
            total = total + (out - shared)
            rows += float(stats["held_assignments"])
            assert float(stats["dropped"]) == 0.0
    assert rows == 2 * 24 * 5       # every assignment is some rank's
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    # And the gate is in it: without it the shared part is another number.
    ungated = ref.swiglu(params["shared"], x)
    assert float(jnp.abs(shared - ungated).max()) > 1e-2


def test_a_gate_without_a_shared_expert_is_refused():
    with pytest.raises(ValueError, match="shared_gate"):
        moe.MoEConfig(expert="swiglu", shared=0, shared_gate=True)


@pytest.mark.parametrize("assignments, chunks, chunk, rungs", [
    (81920, None, 5120, 17), (81920, 12, 7168, 13), (81920, 24, 3584, 24),
    (40960, None, 2560, 17), (2560, 4, 1024, 4), (512, 1, 512, 2)])
def test_a_deployment_names_the_chunks_of_its_held_rows(assignments, chunks,
                                                        chunk, rungs):
    """Qwen3-Next's rank of sixteen: at the default a chunk is its even
    share (5,120 of 81,920), so its configuration names 12 and the share
    sits inside one chunk of 7,168; Laguna's layer at the default is as it
    was.  The rungs end on the first that holds every assignment."""
    cfg = moe.MoEConfig(num_experts=512, top_k=10, expert="swiglu",
                        held=(0, 32), held_chunks=chunks)
    assert cfg.held_chunks == (chunks or 16)
    assert moe.held_chunk_rows(assignments, cfg.held_chunks) == chunk
    ladder = moe.held_rungs(assignments, cfg.held_chunks)
    assert ladder == tuple(chunk * i for i in range(rungs))
    assert ladder[-2] < assignments <= ladder[-1]


@pytest.mark.parametrize("chunks", [1, 3, 7])
def test_the_held_part_is_the_same_at_every_chunk_count(chunks, monkeypatch):
    """``held_chunks`` moves rows between the loop's trips and changes no
    number: the layer's output, every gradient and the held count equal the
    default's; only the buffer's rows follow the chunk."""
    from autodist_tpu import observability
    monkeypatch.setattr(moe, "GMM_TILING", (16,) + moe.GMM_TILING[1:])
    args = dict(num_experts=16, top_k=5, d_model=32, d_hidden=24,
                expert="swiglu", norm_topk=True, shared=1, shared_gate=True,
                held=(4, 8))
    default, named = moe.MoEConfig(**args), moe.MoEConfig(
        **args, held_chunks=chunks)
    params = moe.init(jax.random.PRNGKey(0), default)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def value_and_grads(cfg):
        def f(p, x):
            out, stats = moe.dropless_apply(p, cfg, x)
            return jnp.sum(out * w), stats
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
                params, x)
    (want, want_stats), want_grads = value_and_grads(default)
    (got, stats), grads = value_and_grads(named)
    gauges = observability.registry().snapshot()["gauges"]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5),
        grads, want_grads)
    held = float(stats["held_assignments"])
    assert held == float(want_stats["held_assignments"]) > 0
    assert float(stats["dropped"]) == 0.0
    chunk = moe.held_chunk_rows(2 * 64 * 5, chunks)
    assert chunk != moe.held_chunk_rows(2 * 64 * 5)
    assert float(stats["held_buffer_rows"]) == -(-held // chunk) * chunk
    assert gauges["moe.held_chunk_rows"] == chunk


@pytest.mark.parametrize("kwargs", [dict(held_chunks=4),
                                    dict(held=(0, 4), held_chunks=0)])
def test_chunks_without_a_share_or_under_one_are_refused(kwargs):
    with pytest.raises(ValueError, match="held_chunks"):
        moe.MoEConfig(num_experts=16, top_k=5, expert="swiglu", **kwargs)


# -- attention's two new forms ------------------------------------------------------

def test_a_norm_a_head_and_a_gate_a_lane_are_their_parameters_shapes():
    cfg = _cfg(1)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    attn = params["layer3"]["attn"]
    assert attn["q_norm"]["scale"].shape == (HEAD,) \
        == attn["k_norm"]["scale"].shape
    assert attn["gate"]["kernel"].shape == (32, 4 * HEAD)
    assert attn["key"]["kernel"].shape == (32, 2 * HEAD)
    gdn = params["layer0"]["gdn"]
    assert gdn["q"]["kernel"].shape == (32, 2 * 8) == gdn["k"]["kernel"].shape
    assert gdn["v"]["kernel"].shape == (32, 4 * 8)
    assert gdn["conv"]["kernel"].shape == (4, 2 * 16 + 32)
    assert gdn["A_log"].shape == (4,) == gdn["b"]["kernel"].shape[1:]
    assert "moe" in params["layer0"] and "mlp" not in params["layer0"]
    assert params["layer0"]["moe"]["shared_gate"]["kernel"].shape == (32, 1)
    for wrong in (dict(qk_norm="lane"), dict(gate="head")):
        with pytest.raises(ValueError, match="must be False, True or"):
            L.mha_init(jax.random.PRNGKey(0), 32, 4, **wrong)
    # The whole-vector norm and the scalar gate are what they were.
    old = L.mha_init(jax.random.PRNGKey(0), 32, 4, False, True, HEAD, 2, True)
    assert old["q_norm"]["scale"].shape == (4 * HEAD,)
    assert old["gate"]["kernel"].shape == (32, 4)


@pytest.mark.parametrize("field, named", [
    (dict(qk_norm="head", norm="rmsnorm"), "default block"),
    (dict(attn_gate="lane"), "a gate")])
def test_decoding_refuses_the_new_forms_by_name(field, named):
    cfg = T.TransformerConfig(vocab=64, dim=32, num_heads=4, num_layers=2,
                              max_len=64, causal=True, dtype=jnp.float32,
                              **field)
    with pytest.raises(NotImplementedError, match=named):
        lm.init_decode_cache(cfg, slots=2, cache_len=16)


def test_the_published_configuration_counts_its_parameters():
    def count(tree):
        return sum(int(np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(tree))

    def shapes(cfg):
        return jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    cut = lm.qwen3_next_80b_a3b(num_layers=4, vocab=18992,
                                experts_held=(0, 32))
    assert cut.layer_types == PERIOD
    held = shapes(cut)
    assert count(held) == 625_667_136
    assert count(held["layer0"]) == 138_582_208
    assert count(held["layer3"]) == 132_127_232
    assert count(held["layer0"]["gdn"]) == 33_718_464
    assert count(held["layer3"]["attn"]) == 27_263_488
    assert count({k: held["layer0"]["moe"][k]
                  for k in ("glu", "up", "down")}) == 32 * 3_145_728
    assert count(shapes(lm.qwen3_next_80b_a3b())) == 79_674_391_296


# -- tracing -------------------------------------------------------------------------

def test_the_events_and_gauges_say_the_grouping_the_lanes_and_the_gate():
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    from autodist_tpu.ops import gated_delta
    cfg = _cfg(1)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    # Gauges and events are written where the layers are traced, an event
    # once a process for each shape: whatever this process traced before
    # (the suite deals a file's tests over its workers) is forgotten first.
    observability.reset()
    jax.clear_caches()
    for announced in (gated_delta._announced, L._mha_announced,
                      moe._announced):
        announced.clear()
    jax.eval_shape(lm.make_loss_fn(cfg), params, (_tokens(),))
    gauges = observability.registry().snapshot()["gauges"]
    assert (gauges["gdn.heads"], gauges["gdn.key_heads"]) == (4, 2)
    assert gauges["attn.gate_lanes"] == HEAD == gauges["attn.qk_norm_lanes"]
    assert gauges["attn.kv_heads"] == 2
    assert gauges["attn.rotary_lanes_full"] == ROTARY
    assert gauges["moe.shared_gate"] == 1 == gauges["moe.softmax_scoring"]

    def said(kind):
        return [e["detail"] for e in recorder.events() if e["kind"] == kind]
    assert any("2 value heads a key head (2 key heads: K K^T and Q K^T once "
               "a key head)" in e for e in said("gdn"))
    assert any(f"4 heads of 16 read 2 key-value heads (2 a group), every key "
               f"behind the diagonal, {ROTARY} of a head's 16 lanes rotated, "
               f"a sigmoid gate a lane (16 a head) on the output, q and k "
               f"RMS-normalised a head over 16 lanes" in e
               for e in said("attn"))
    assert any("1 shared times the sigmoid of a scalar a token" in e
               for e in said("moe"))
