"""The OLMoE block (RMSNorm, rotary positions, QK-norm, bias-free, untied
head, dropless SwiGLU experts) at a toy size in float32 on the CPU: against
the benchmark's plain reference, against ``moe.dense_apply``, and piece by
piece against a few lines of numpy.  The published widths are checked on
the chip, in every run of the cell (PERF.md, section 4)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models import layers as L
from autodist_tpu.models import lm
from autodist_tpu.models import transformer as T
from autodist_tpu.observability import profile
from autodist_tpu.parallel import moe
from chipbench import reference_olmoe

TOY = dict(vocab=128, dim=32, num_heads=4, num_layers=2,
           max_len=16, causal=True, dtype=jnp.float32, norm="rmsnorm",
           norm_eps=1e-5, positions="rope", qk_norm=True, bias=False,
           tied_head=False, ffn="moe", num_experts=8, experts_per_token=2,
           expert_dim=24, norm_topk=False, load_balance_coef=0.01,
           router_z_coef=0.001)


def _toy(seed=0, **changes):
    cfg = T.TransformerConfig(**{**TOY, **changes})
    params = lm.init(jax.random.PRNGKey(seed), cfg)
    tokens = np.random.RandomState(seed).randint(
        0, cfg.vocab, (4, cfg.max_len + 1)).astype(np.int32)
    return cfg, params, (tokens,)


def _reference_loss(cfg):
    def loss_fn(params, batch):
        return reference_olmoe.loss(
            params, batch[0], layers=cfg.num_layers, heads=cfg.num_heads,
            top_k=cfg.moe.top_k, norm_topk=cfg.moe.norm_topk,
            eps=cfg.norm_eps, theta=cfg.rope_theta,
            aux_coef=cfg.load_balance_coef, z_coef=cfg.router_z_coef)
    return loss_fn


def _scalar(loss_fn):
    return lambda params, batch: loss_fn(params, batch)[0]


@pytest.fixture
def chosen_experts(monkeypatch):
    """Every ``jax.lax.top_k`` result's indices, in call order."""
    seen, real = [], jax.lax.top_k

    def recording(operand, k):
        values, indices = real(operand, k)
        seen.append(np.asarray(indices))
        return values, indices

    monkeypatch.setattr(jax.lax, "top_k", recording)
    return seen


def test_loss_and_routing_equal_the_plain_references(chosen_experts):
    cfg, params, batch = _toy()
    loss, aux = lm.make_loss_fn(cfg)(params, batch)
    ours = [i.reshape(-1, cfg.moe.top_k) for i in chosen_experts]
    del chosen_experts[:]
    with jax.default_matmul_precision("highest"):
        want = _reference_loss(cfg)(params, batch)
    theirs = [i.reshape(-1, cfg.moe.top_k) for i in chosen_experts]
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert len(ours) == len(theirs) == cfg.num_layers
    for got, ref in zip(ours, theirs):
        np.testing.assert_array_equal(got, ref)
    assert float(loss) == pytest.approx(
        float(aux["xent"] + 0.01 * aux["moe.load_balance_loss"]
              + 0.001 * aux["moe.router_z_loss"]), rel=1e-6)
    assert float(aux["moe.dropped"]) == 0.0
    assert float(aux["moe.load_max_over_mean"]) >= 1.0


def test_gradients_equal_the_plain_references():
    cfg, params, batch = _toy(seed=1)
    got = jax.jit(jax.grad(_scalar(lm.make_loss_fn(cfg))))(params, batch)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(_reference_loss(cfg)))(params, batch)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * float(
            np.abs(w).max()), err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("expert", ["swiglu", "gelu"])
def test_dropless_equals_the_dense_oracle(expert, norm_topk):
    cfg = moe.MoEConfig(num_experts=8, top_k=3, d_model=32, d_hidden=24,
                        expert=expert, norm_topk=norm_topk)
    params = moe.init(jax.random.PRNGKey(2), cfg)
    assert ("glu" in params) == (expert == "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 8, 32))
    y, stats = moe.dropless_apply(params, cfg, x)
    want, _ = moe.dense_apply(params, cfg, x)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    assert float(stats["dropped"]) == 0.0

    def through(apply):
        return lambda p, x: jnp.sum(apply(p, cfg, x)[0] ** 2)

    got = jax.grad(through(moe.dropless_apply), argnums=(0, 1))(params, x)
    ref = jax.grad(through(moe.dense_apply), argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_the_capacity_path_takes_the_swiglu_expert_too():
    cfg = moe.MoEConfig(num_experts=4, top_k=2, d_model=16, d_hidden=24,
                        expert="swiglu", norm_topk=False, capacity_factor=2.0)
    params = moe.init(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 16))
    np.testing.assert_allclose(moe.apply(params, cfg, x)[0],
                               moe.dense_apply(params, cfg, x)[0],
                               rtol=1e-5, atol=1e-6)


def test_top_k_weights_are_not_renormalised():
    cfg = moe.MoEConfig(num_experts=8, top_k=2, d_model=16, d_hidden=8,
                        expert="swiglu", norm_topk=False)
    params = moe.init(jax.random.PRNGKey(6), cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 16))
    y, _ = moe.dropless_apply(params, cfg, x)
    # By hand, a token at a time: the softmax's own two largest values.
    gates = jax.nn.softmax(x[0] @ params["gate"]["kernel"])
    want = np.zeros((8, 16), np.float32)
    for t in range(8):
        for e in np.argsort(-np.asarray(gates[t]))[:2]:
            hidden = jax.nn.silu(x[0, t] @ params["glu"]["kernel"][e]) * (
                x[0, t] @ params["up"]["kernel"][e])
            want[t] += gates[t, e] * (hidden @ params["down"]["kernel"][e])
    np.testing.assert_allclose(y[0], want, rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.sum(jax.lax.top_k(gates, 2)[0], -1))) < 0.95
    renormalised, _ = moe.dropless_apply(
        params, moe.MoEConfig(num_experts=8, top_k=2, d_model=16, d_hidden=8,
                              expert="swiglu", norm_topk=True), x)
    assert float(jnp.abs(renormalised - y).max()) > 1e-3


def test_nothing_is_dropped_when_one_expert_gets_every_token():
    cfg = moe.MoEConfig(num_experts=8, top_k=2, d_model=16, d_hidden=8,
                        expert="swiglu", norm_topk=False)
    params = moe.init(jax.random.PRNGKey(8), cfg)
    # A router that prefers expert 5, then 2, whatever the token: positive
    # inputs against columns that are large for those two.
    gate = jnp.zeros((16, 8)).at[:, 5].set(1.0).at[:, 2].set(0.5)
    params["gate"]["kernel"] = gate
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (2, 8, 16))) + 0.1
    y, stats = moe.dropless_apply(params, cfg, x)
    assert float(stats["dropped"]) == 0.0
    # 16 of the 32 assignments on one expert of eight: four times the mean.
    assert float(stats["load_max_over_mean"]) == 4.0
    np.testing.assert_allclose(y, moe.dense_apply(params, cfg, x)[0],
                               rtol=1e-5, atol=1e-6)
    # The capacity path at its default factor loses most of them.
    capped, _ = moe.apply(params, cfg, x)
    assert float(jnp.abs(capped - y).max()) > 1e-3


def test_dropped_counts_the_rows_the_grouped_product_would_miss():
    chosen = jnp.array([0, 0, 0, 2, 2, 3, 3, 3], jnp.int32)     # sorted
    sizes = jnp.bincount(chosen, length=4)
    assert float(moe._uncovered(chosen, sizes)) == 0.0
    # An expert capped at two rows: every group starts a row early, so the
    # last row of each goes through the next expert's matrix or through none.
    assert float(moe._uncovered(chosen, sizes.at[0].set(2))) == 3.0
    # Sizes that cover fewer rows than there are: the tail is dropped.
    assert float(moe._uncovered(chosen, sizes.at[3].set(1))) == 2.0
    # Sizes in another order than the sort's: expert 2's two rows go
    # through expert 1's matrix.
    assert float(moe._uncovered(chosen, sizes[::-1])) == 2.0


def test_rmsnorm_by_hand():
    x = np.random.RandomState(0).randn(3, 5, 8).astype(np.float32)
    scale = np.linspace(0.5, 1.5, 8).astype(np.float32)
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * scale
    np.testing.assert_allclose(L.rmsnorm({"scale": scale}, x, 1e-5), want,
                               rtol=1e-6)
    assert L.rmsnorm({"scale": scale}, x.astype(jnp.bfloat16)).dtype \
        == jnp.bfloat16


def test_rotary_by_hand():
    """Rotate-half: element i of a head turns with element i + w / 2 by
    the angle t * theta^(-2i / w)."""
    seq, width, theta = 6, 8, 10000.0
    x = np.random.RandomState(1).randn(1, 2, seq, width).astype(np.float32)
    got = np.asarray(L.apply_rope(x, L.rope_tables(seq, width, theta)))
    for t in range(seq):
        for i in range(width // 2):
            angle = t * theta ** (-2 * i / width)
            a, b = x[0, :, t, i], x[0, :, t, i + width // 2]
            np.testing.assert_allclose(
                got[0, :, t, i], a * np.cos(angle) - b * np.sin(angle),
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                got[0, :, t, i + width // 2],
                b * np.cos(angle) + a * np.sin(angle), rtol=1e-5, atol=1e-6)
    # Position 0 is left as it is; the norm of every pair is kept.
    np.testing.assert_allclose(got[0, :, 0], x[0, :, 0], rtol=1e-6)
    np.testing.assert_allclose((got ** 2).sum(-1), (x ** 2).sum(-1),
                               rtol=1e-5)


def test_qk_norm_is_over_the_whole_projection_before_the_heads():
    dim, heads = 16, 4
    p = L.mha_init(jax.random.PRNGKey(10), dim, heads, use_bias=False,
                   qk_norm=True)
    assert set(p) == {"query", "key", "value", "out", "q_norm", "k_norm"}
    assert all("bias" not in p[k] for k in ("query", "key", "value", "out"))
    p["q_norm"]["scale"] = jnp.linspace(0.5, 2.0, dim)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 5, dim))
    seen = {}

    def attn_fn(q, k, v, mask):
        seen.update(q=q, k=k)
        return L.dot_product_attention(q, k, v, mask)

    L.mha(p, x, heads, attn_fn=attn_fn, norm_eps=1e-5)
    q = np.asarray(x @ p["query"]["kernel"])
    q = q / np.sqrt((q ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(p["q_norm"]["scale"])
    np.testing.assert_allclose(
        seen["q"], q.reshape(2, 5, heads, dim // heads).transpose(0, 2, 1, 3),
        rtol=1e-5, atol=1e-6)


# -- the defaults are today's block -------------------------------------------

def _block_before_pr25(cfg):
    """``models/transformer.py`` and ``models/lm.py`` as PR 24 left them:
    the loss of the LayerNorm / learned-position / GELU-MLP block."""
    def block(p, x):
        with jax.named_scope("attn"):
            h = L.layernorm(p["ln1"], x)
            x = x + L.mha(p["attn"], h, cfg.num_heads, mask=None,
                          dtype=cfg.dtype, attn_fn=attn_fn)
        with jax.named_scope("mlp"):
            h = L.layernorm(p["ln2"], x)
            h = jax.nn.gelu(L.dense(p["mlp"]["up"], h, cfg.dtype))
            return x + L.dense(p["mlp"]["down"], h, cfg.dtype)

    from autodist_tpu.ops.flash_attention import make_flash_attn_fn
    attn_fn = make_flash_attn_fn(causal=cfg.causal)

    def loss_fn(params, batch):
        (tokens,) = batch
        ids = tokens[:, :-1]
        with jax.named_scope("embed"):
            x = L.embed(params["embed"], ids) \
                + params["pos_embed"][:ids.shape[1]]
            x = x.astype(cfg.dtype)
        for i in range(cfg.num_layers):
            with jax.named_scope(f"layer{i}"):
                x = block(params[f"layer{i}"], x)
        with jax.named_scope("ln_f"):
            hidden = L.layernorm(params["ln_f"], x)
        with jax.named_scope("lm_head"):
            with jax.named_scope("logits"):
                lg = (hidden.astype(jnp.float32)
                      @ params["embed"]["embedding"].T.astype(jnp.float32))
            return L.softmax_xent(lg, tokens[:, 1:])
    return loss_fn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_defaults_are_the_block_of_before(dtype):
    cfg = lm.lm_tiny(dtype=dtype)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    assert sorted(params) == ["embed", "layer0", "layer1", "ln_f",
                              "pos_embed"]
    assert sorted(params["layer0"]) == ["attn", "ln1", "ln2", "mlp"]
    assert sorted(params["layer0"]["attn"]) == ["key", "out", "query",
                                                "value"]
    assert set(params["layer0"]["attn"]["query"]) == {"kernel", "bias"}
    assert set(params["ln_f"]) == {"scale", "bias"}
    batch = lm.synthetic_batch(cfg, batch_size=2, seq_len=16)
    loss_fn = lm.make_loss_fn(cfg)
    for fn in (lambda f: f, jax.grad):
        now = str(jax.make_jaxpr(fn(loss_fn))(params, batch))
        before = str(jax.make_jaxpr(fn(_block_before_pr25(cfg)))(params,
                                                                 batch))
        assert now == before


def test_decoding_through_the_new_block_is_refused():
    cfg, params, _ = _toy()
    with pytest.raises(NotImplementedError, match="R2"):
        T.init_cache(cfg, 2, 8)
    with pytest.raises(NotImplementedError, match="R2"):
        T.decode_step(params, cfg, {}, jnp.zeros((2,), jnp.int32),
                      jnp.zeros((2,), jnp.int32))
    with pytest.raises(NotImplementedError, match="scan_layers"):
        T.TransformerConfig(**{**TOY, "scan_layers": True})
    with pytest.raises(ValueError, match="norm must be one of"):
        T.TransformerConfig(norm="batchnorm")


def test_the_preset_has_the_published_sizes():
    cfg = lm.olmoe_1b_7b(num_layers=1)
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert count == 625_616_896
    layer = shapes["layer0"]
    assert layer["moe"]["glu"]["kernel"].shape == (64, 2048, 1024)
    assert layer["moe"]["down"]["kernel"].shape == (64, 1024, 2048)
    assert layer["moe"]["gate"]["kernel"].shape == (2048, 64)
    assert layer["attn"]["q_norm"]["scale"].shape == (2048,)
    assert shapes["lm_head"]["kernel"].shape == (2048, 50304)
    assert "pos_embed" not in shapes
    assert lm.olmoe_1b_7b().num_layers == 16


# -- tracing: scopes, gauges, the event, aux through the Runner ---------------

@pytest.mark.parametrize("op_name, scope, phase", [
    ("jit(step)/jvp(layer0)/moe/router/dot_general", "moe/router", "forward"),
    ("jit(step)/transpose(jvp(layer11))/moe/experts/ragged_dot_general",
     "moe/experts", "backward"),
    ("jit(step)/jvp(layer3)/moe/dispatch/gather", "moe/dispatch", "forward"),
    ("jit(step)/jvp(layer3)/moe/add", "moe", "forward"),
    ("jit(step)/jvp(layer3)/attn/rmsnorm/mul", "attn", "forward"),
    ("jit(step)/jvp(lm_head)/logits/dot_general", "head", "forward"),
])
def test_the_expert_layers_scopes_fold_over_the_layers(op_name, scope, phase):
    assert profile._scope_and_phase(op_name) == (scope, phase)


def test_the_step_carries_aux_and_the_runner_keeps_the_last():
    from autodist_tpu import AutoDist, observability, strategy
    from autodist_tpu.observability import recorder
    observability.reset()
    cfg, params, batch = _toy(dtype=jnp.float32)
    batch = (np.tile(batch[0], (2, 1)),)            # 8 rows on 8 devices
    loss_fn = lm.make_loss_fn(cfg)
    ad = AutoDist(strategy_builder=strategy.PartitionedPS())
    item = ad.capture(loss_fn, params, optax.adam(1e-3), example_batch=batch)
    assert item.aux_output is True
    runner = ad.create_distributed_session(item)
    assert runner.last_aux is None
    state = runner.create_state()
    state, metrics = runner.step(state, batch)
    assert runner.last_aux is metrics["aux"]
    assert set(metrics["aux"]) == {
        "xent", "moe.load_balance_loss", "moe.router_z_loss",
        "moe.load_max_over_mean", "moe.dropped"}
    assert float(metrics["aux"]["moe.dropped"]) == 0.0
    want, _ = loss_fn(params, batch)
    assert float(metrics["loss"]) == pytest.approx(float(want), rel=1e-5)
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["moe.experts"] == 8 and gauges["moe.top_k"] == 2
    # The explicit lowering traces one chip's rows: 1 x 16 tokens x 2.
    assert gauges["moe.assignments_per_step"] in (32, 8 * 32)
    events = [e for e in recorder.events() if e["kind"] == "moe"]
    assert events and "megablox gmm" in events[-1]["detail"]
    assert len({e["detail"] for e in events}) == len(events)
    scopes = {scope for scope, _ in runner.scope_table().values()}
    assert {"moe/router", "moe/dispatch", "moe/experts", "attn",
            "head"} <= scopes


def test_capture_reads_aux_output_off_the_traced_loss():
    from autodist_tpu.graph_item import GraphItem
    cfg, params, batch = _toy()
    loss_fn = lm.make_loss_fn(cfg)

    def capture(fn, **kwargs):
        return GraphItem.capture(fn, params, optax.sgd(0.1), **kwargs)

    # A plain wrapper around the loss keeps nothing but what it returns.
    assert capture(lambda p, b: loss_fn(p, b), example_batch=batch).aux_output
    assert capture(jax.jit(loss_fn), example_batch=batch,
                   aux_output=True).aux_output
    assert not capture(_scalar(loss_fn), example_batch=batch).aux_output
    # Without a batch there is nothing to read: what the caller says holds.
    assert not capture(loss_fn).aux_output
    assert capture(loss_fn, aux_output=True).aux_output
    with pytest.raises(ValueError, match="returns a .loss, aux. pair"):
        capture(loss_fn, example_batch=batch, aux_output=False)
    with pytest.raises(ValueError, match="returns a bare loss"):
        capture(_scalar(loss_fn), example_batch=batch, aux_output=True)
