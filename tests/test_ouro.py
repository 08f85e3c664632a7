"""The looped language model (``TransformerConfig.loops``: Ouro's stack of
layers run several times with one set of variables, a head and a loss on
every pass, an exit gate that weighs them) at a toy size in float32 on the
CPU: against the plain reference, against an untied stack whose variables
are copies, and against each fault the benchmark's configuration plants."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.models import lm
from autodist_tpu.models import transformer as T
from autodist_tpu.strategy import PartitionedPS
from chipbench import reference_ouro

LAYERS, PASSES, HEADS, THETA, BETA, EPS = 3, 4, 4, 1e6, 0.05, 1e-6
REFERENCE = dict(layers=LAYERS, passes=PASSES, heads=HEADS, eps=EPS,
                 theta=THETA, beta=BETA)


def _config(**changes):
    base = dict(vocab=97, dim=32, num_heads=HEADS, num_layers=LAYERS,
                mlp_dim=48, max_len=16, causal=True, dtype=jnp.float32,
                norm="rmsnorm", norm_eps=EPS, positions="rope",
                rope_theta=THETA, bias=False, tied_head=False, ffn="swiglu",
                norm_position="sandwich", loops=PASSES,
                exit_entropy_coef=BETA)
    return T.TransformerConfig(**{**base, **changes})


@pytest.fixture(scope="module")
def toy():
    """``(cfg, params, tokens)``: every norm's scale moved off one and the
    gate's bias off zero, so that each is seen by the comparisons."""
    cfg = _config()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if leaf.ndim == 1 else leaf for leaf, key in zip(leaves, keys)])
    (tokens,) = lm.synthetic_batch(cfg, batch_size=2, seq_len=16, seed=3)
    return cfg, params, tokens


@pytest.fixture(scope="module")
def program(toy):
    """The program's loss and gradients, exact products."""
    cfg, params, tokens = toy
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lm.make_loss_fn(cfg), has_aux=True))(params, (tokens,))
    return float(loss), aux, grads


def _worst_leaf(got, want):
    """The largest distance between two trees' leaves, each as a share of
    the wanted leaf's norm, with the leaf's name."""
    flat = jax.tree_util.tree_leaves_with_path(got)
    return max((float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-12)),
                jax.tree_util.keystr(path))
               for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)))


def test_every_pass_agrees_with_the_reference(toy, program):
    cfg, params, tokens = toy
    with jax.default_matmul_precision("highest"):
        hidden, stats = T.encode_passes(params, cfg, tokens[:, :-1])
        got = [T.logits(params, cfg, h) for h in hidden]
        want = jax.jit(lambda p: reference_ouro.pass_logits(
            p, reference_ouro.pass_states(
                p, tokens[:, :-1], layers=LAYERS, passes=PASSES, heads=HEADS,
                eps=EPS, theta=THETA)))(params)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_ouro.loss(p, tokens, **REFERENCE)))(params)
    assert len(got) == len(want) == PASSES and stats == []
    for t in range(PASSES):
        np.testing.assert_allclose(got[t], want[t], rtol=2e-5, atol=2e-5)
    # A pass changes the states: the four heads do not see the same thing.
    assert float(jnp.abs(got[0] - got[-1]).max()) > 1e-2
    loss, aux, grads = program
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert _worst_leaf(grads, want_grads)[0] < 2e-5
    assert set(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda g: bool(jnp.any(g != 0)), grads))) == {True}
    assert float(aux["xent"]) == pytest.approx(float(aux["loop.xent"][-1]))
    assert aux["loop.xent"].shape == aux["loop.exit_pdf"].shape == (PASSES,)
    assert float(aux["loop.exit_pdf"].sum()) == pytest.approx(1.0, abs=1e-6)
    assert 0.0 < float(aux["loop.exit_entropy"]) < np.log(PASSES)


def test_pointwise_recomputation_changes_no_number(toy, program):
    """``recompute="pointwise"``: the norms and ``silu(gate) * up`` are made
    again in the backward pass, from the same inputs by the same
    arithmetic."""
    cfg, params, tokens = toy
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(jax.value_and_grad(lm.make_loss_fn(
            _config(recompute="pointwise")), has_aux=True))(params, (tokens,))
    assert float(loss) == pytest.approx(program[0], rel=1e-7)
    assert _worst_leaf(grads, program[2])[0] < 1e-6
    kept = str(jax.make_jaxpr(jax.grad(lambda p: lm.make_loss_fn(
        _config(recompute="pointwise"))(p, (tokens,))[0]))(params))
    assert "checkpoint" in kept or "remat" in kept


def test_the_passes_are_one_scan(toy):
    """One pass's instructions in the program whatever ``loops`` is: the
    layers' products appear once forward, under ``pass``."""
    _, params, tokens = toy

    def products(loops):
        text = str(jax.make_jaxpr(lm.make_loss_fn(_config(loops=loops)))(
            params, (tokens,)))
        return text.count("dot_general")
    # Two passes more are two heads more (the gate is no matrix product).
    assert products(4) - products(2) == 2


def test_the_exit_distribution_sums_to_one_at_every_position(toy):
    cfg, params, tokens = toy
    hidden, _ = T.encode_passes(params, cfg, tokens[:, :-1])
    log_p = T.exit_distribution(params, hidden)
    assert log_p.shape == (PASSES,) + tokens[:, :-1].shape
    assert log_p.dtype == jnp.float32
    np.testing.assert_allclose(jnp.exp(log_p).sum(axis=0), 1.0, atol=1e-6)
    want = reference_ouro.exit_distribution(params, hidden)
    np.testing.assert_allclose(jnp.exp(log_p), want, rtol=1e-5, atol=1e-7)
    # A gate that never lets go still sums to one (nothing is 0 x inf).
    shut = {**params, "exit_gate": {"kernel": params["exit_gate"]["kernel"],
                                    "bias": jnp.full((1,), -200.0)}}
    far = jnp.exp(T.exit_distribution(shut, hidden))
    np.testing.assert_allclose(far[-1], 1.0, atol=1e-6)
    assert bool(jnp.isfinite(T.exit_distribution(shut, hidden)).all())


def test_untied_copies_give_the_same_loss_and_their_gradients_sum(toy,
                                                                  program):
    """``4 x N`` layers whose variables are copies of the N: the same loss,
    and a looped variable's gradient is the sum over its four copies.  Built
    from the program's own block and objective, not from the reference."""
    cfg, params, tokens = toy
    layers = {k: v for k, v in params.items() if k.startswith("layer")}
    rest = {k: v for k, v in params.items() if not k.startswith("layer")}
    copies = [layers] * PASSES
    seq = tokens.shape[1] - 1
    rope = T._rope_tables(cfg, seq)[T.FULL]
    attn_fn, mask = T._resolve_attn(cfg, seq, None)

    def untied(copies, rest):
        x = rest["embed"]["embedding"][tokens[:, :-1]].astype(cfg.dtype)
        hidden = []
        for t in range(PASSES):
            for i in range(LAYERS):
                x, _ = T.block_apply(copies[t][f"layer{i}"], x, cfg,
                                     mask=mask, attn_fn=attn_fn,
                                     rope=rope)
            x = T._norm(cfg, rest["ln_f"], x)
            hidden.append(x)
        return lm.looped_objective(rest, cfg, hidden, tokens[:, 1:])[0]

    with jax.default_matmul_precision("highest"):
        loss, (by_copy, of_rest) = jax.jit(jax.value_and_grad(
            untied, argnums=(0, 1)))(copies, rest)
    want_loss, _, want = program
    assert float(loss) == pytest.approx(want_loss, rel=1e-6)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *by_copy)
    assert _worst_leaf(summed, {k: want[k] for k in layers})[0] < 2e-5
    assert _worst_leaf(of_rest, {k: want[k] for k in rest})[0] < 2e-5
    # The copies' gradients differ: the sum is of four different things.
    first, last = by_copy[0]["layer0"]["mlp"]["up"]["kernel"], \
        by_copy[-1]["layer0"]["mlp"]["up"]["kernel"]
    assert float(jnp.linalg.norm(first - last)) > 0.1 * float(
        jnp.linalg.norm(last))


# The jaxpr of the loss of the plain stack at bb2dbfc, the commit before
# ``loops`` (``str(jax.make_jaxpr(lm.make_loss_fn(cfg))(shapes, batch))``,
# sha256[:16]); a PR that changes the default block's program records them
# anew.
PARENTS_JAXPR = {"gpt2 block": "e9aaa5d7703e5040",
                 "rmsnorm, rope, swiglu, pre-norm": "81506c2ed4a87605"}


@pytest.mark.parametrize("block", list(PARENTS_JAXPR))
def test_one_loop_is_the_parents_program(block):
    given = {} if block == "gpt2 block" else dict(
        norm="rmsnorm", positions="rope", rope_theta=THETA, bias=False,
        tied_head=False, ffn="swiglu", num_layers=LAYERS, mlp_dim=48,
        max_len=16, dim=32, vocab=97)
    base = dict(vocab=256, dim=64, num_heads=4, num_layers=2, max_len=64,
                causal=True, dtype=jnp.float32)

    def jaxpr(**more):
        cfg = T.TransformerConfig(**{**base, **given, **more})
        shapes = jax.eval_shape(lambda k: lm.init(k, cfg),
                                jax.random.PRNGKey(0))
        batch = (jax.ShapeDtypeStruct((2, 17), jnp.int32),)
        return str(jax.make_jaxpr(lm.make_loss_fn(cfg))(shapes, batch))

    plain = jaxpr()
    assert jaxpr(loops=1, norm_position="pre") == plain
    assert hashlib.sha256(plain.encode()).hexdigest()[:16] == \
        PARENTS_JAXPR[block]
    assert "pass0" not in plain and "exit_gate" not in plain


@pytest.mark.parametrize("plant", reference_ouro.PLANTS)
def test_a_planted_fault_is_told_from_the_program(plant, toy, program):
    """Each fault of the benchmark configuration's list (``check.why``),
    planted in the reference: the program stands off it by more than the
    check's limit on the loss or by a large part of some leaf's gradient."""
    cfg, params, tokens = toy
    loss, _, grads = program
    with jax.default_matmul_precision("highest"):
        wrong_loss, wrong_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_ouro.loss(p, tokens, plant=plant,
                                          **REFERENCE)))(params)
    off = abs(loss - float(wrong_loss)) / abs(loss)
    leaf, name = _worst_leaf(grads, wrong_grads)
    assert off > 2e-4 and leaf > 0.1, (plant, off, leaf, name)


@pytest.mark.parametrize("given, said", [
    (dict(loops=0), "at least 1"),
    (dict(scan_layers=True), "scan over the layers inside the loop"),
    (dict(ffn="moe", num_experts=4, experts_per_token=2), "expert layers"),
    (dict(mtp_depth=1), "prediction module"),
    (dict(layer_types=[T.LINEAR] * LAYERS, linear_heads=2, linear_key_dim=8,
          linear_value_dim=8), "linear layers"),
    (dict(norm_position="both"), "norm_position must be one of"),
    (dict(recompute="norms"), "recompute must be one of"),
])
def test_what_a_loop_cannot_carry_is_refused_by_name(given, said):
    with pytest.raises((NotImplementedError, ValueError), match=said):
        _config(**given)


def test_decoding_refuses_a_loop_by_name():
    looped = T.TransformerConfig(vocab=64, dim=32, num_heads=4, num_layers=2,
                                 max_len=16, causal=True, loops=2)
    for call in (lambda: T.init_cache(looped, 1, 8),
                 lambda: lm.make_decode_fn(looped)(None, None, None, None)):
        with pytest.raises(NotImplementedError, match="a cache entry a pass"):
            call()


def test_the_preset_is_the_published_model():
    cfg = lm.ouro_2_6b()
    assert (cfg.dim, cfg.num_heads, cfg.head_dim, cfg.mlp_dim, cfg.vocab,
            cfg.num_layers, cfg.loops) == (2048, 16, 128, 5632, 49152, 48, 4)
    assert (cfg.norm, cfg.norm_position, cfg.positions, cfg.rope_theta,
            cfg.ffn, cfg.tied_head, cfg.bias, cfg.norm_eps) == (
        "rmsnorm", "sandwich", "rope", 1e6, "swiglu", False, False, 1e-6)
    shapes = jax.eval_shape(lambda k: lm.init(k, lm.ouro_2_6b(
        num_layers=8, vocab=8192)), jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    # 8 x 51,388,416 a layer, embedding and head, the final norm, the gate.
    assert count == 8 * 51_388_416 + 2 * 8192 * 2048 + 2048 + 2049 \
        == 444_665_857
    whole = jax.eval_shape(lambda k: lm.init(k, lm.ouro_2_6b()),
                           jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        whole)) == 48 * 51_388_416 + 201_326_592 + 2048 + 2049


def test_the_explicit_step_on_four_devices_is_the_one_device_step(tmp_path):
    """``PartitionedPS`` on four virtual devices, every matrix through
    ``layer_boundary``'s gather and its asynchronous scatter (the threshold
    at zero: a test's argument): the boundary op runs inside the scan over
    the passes, so a variable is gathered, and its gradient scattered, once
    a pass, and the shards' sums meet in the backward scan's carry.  Losses
    and every parameter after three steps against plain JAX on one
    device."""
    cfg = _config(num_layers=2, vocab=64)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(1)
    batches = [(rng.randint(0, 64, (8, 17)).astype(np.int32),)
               for _ in range(3)]
    spec = tmp_path / "spec.yml"
    spec.write_text("nodes:\n  - address: 127.0.0.1\n    chief: true\n"
                    "    cpus: [0, 1, 2, 3]\n")
    ad = AutoDist(str(spec), PartitionedPS(), devices=jax.devices()[:4])
    loss_fn = lm.make_loss_fn(cfg)
    item = ad.capture(loss_fn, params, optax.sgd(0.1),
                      example_batch=batches[0])
    runner = ad.create_distributed_session(item)
    assert runner.program.use_explicit_path
    state, step, got = runner.create_state(), None, []
    for batch in batches:
        sharded = runner.remapper.shard_batch(batch)
        if step is None:
            specs = runner.program.batch_specs(sharded)
            step = jax.jit(
                runner._explicit_step_fn(specs, async_min_bytes=0),
                in_shardings=(runner.state_shardings, None),
                out_shardings=(runner.state_shardings, None))
            text = step.lower(runner.state_struct, sharded).as_text()
            # 7 matrices a layer, 3 permutes each, once in the program:
            # the scan's body, which runs once a pass.
            assert text.count("collective_permute") == 2 * 7 * 3
        state, metrics = step(state, sharded)
        got.append(float(metrics["loss"]))
    assert metrics["aux"]["loop.xent"].shape == (PASSES,)

    want, plain = [], params
    grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
    for batch in batches:
        (loss, _), grads = grad_fn(plain, batch)
        plain = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, plain, grads)
        want.append(float(loss))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    flat = jax.tree_util.tree_leaves_with_path(runner.logical_params(state))
    for (path, leaf), wanted in zip(flat, jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(leaf, wanted, rtol=1e-5, atol=1e-5,
                                   err_msg=str(path))
