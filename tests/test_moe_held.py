"""``moe.dropless_apply`` told its share of the experts beside a shared
expert, in its two families: sigmoid-routed with a selection bias, 4 of 16 a
token (``chipbench/reference_mla_moe.py``'s layer), and softmax-routed, 10 of
64 a token, weights times 2.5 (``chipbench/reference_swa_moe.py``'s); each
against its plain reference under even routing, under routing that sends
every token's choices to held experts (the ``T x k`` worst case: nothing
dropped) and under routing that sends none; at held counts on, beside and
between the rungs of the buffers' ladder (``moe.held_rungs``), from no
chunk to every one; and the shares (4 of the one, 32 of the other) add up to
the uncut layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.parallel import moe
from chipbench import reference_mla_moe as ref
from chipbench import reference_swa_moe

E, K, D, H, RATE, SCALE = 16, 4, 32, 24, 0.001, 2.5
# The softmax family (Laguna's layer): 10 of 64 a token, no bias, a share of
# 2 in the sum over 32 shares and of 12 where every choice has to fit it.
SOFTMAX = "softmax"
FAMILIES = ["sigmoid", SOFTMAX]


def _cfg(held, family="sigmoid", **kw):
    if family == SOFTMAX:
        return moe.MoEConfig(num_experts=64, top_k=10, d_model=D, d_hidden=H,
                             dtype=jnp.float32, expert="swiglu",
                             norm_topk=True, scoring="softmax",
                             route_scale=SCALE, shared=1, held=held, **kw)
    return moe.MoEConfig(num_experts=E, top_k=K, d_model=D, d_hidden=H,
                         dtype=jnp.float32, expert="swiglu", norm_topk=True,
                         scoring="sigmoid", route_scale=SCALE, shared=1,
                         select_bias=True, bias_update_rate=RATE, held=held,
                         **kw)


def _layer(held, bias=None, seed=0, rows=2, seq=64, family="sigmoid",
           towards=None, held_count=None):
    """Parameters of the WHOLE layer cut to ``held``'s matrices, so that
    every share of one seed routes alike.  ``towards`` sends every token's
    choices to those experts: by the selection bias where the family has
    one, else by a constant input lane that the router's matrix weighs.
    ``held_count`` sends exactly that many of the assignments to held
    experts: three input lanes, each one for some tokens and nought for the
    others, that the router's matrix weighs towards ``top_k`` held experts,
    towards one held expert and ``top_k - 1`` others, and towards none."""
    whole = moe.init(jax.random.PRNGKey(seed), _cfg(None, family))
    first, count = held or (0, _cfg(None, family).num_experts)
    p = dict(whole, **{name: {"kernel":
                              whole[name]["kernel"][first:first + count]}
                       for name in ("up", "down", "glu")})
    if bias is not None:
        p["bias"] = jnp.asarray(bias, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (rows, seq, D))
    if towards is not None and family == SOFTMAX:
        x = x.at[..., -1].set(1.0)
        p["gate"] = {"kernel": p["gate"]["kernel"].at[
            -1, jnp.asarray(towards)].add(30.0)}
    elif towards is not None:
        p["bias"] = _towards(towards)
    if held_count is not None:
        _, on_held, off_held = _STEERED[family]
        top_k = len(on_held)
        every, one = divmod(held_count, top_k)
        token = jnp.arange(rows * seq).reshape(rows, seq)
        lanes = jnp.stack([token >= every + one,
                           (token >= every) & (token < every + one),
                           token < every], axis=-1)
        x = x.at[..., -3:].set(lanes.astype(x.dtype))
        gate = p["gate"]["kernel"]
        for lane, experts in ((-3, off_held), (-2, on_held[:1] + off_held[1:]),
                              (-1, on_held)):
            gate = gate.at[lane, jnp.asarray(experts)].add(30.0)
        p["gate"] = {"kernel": gate}
    return p, x


def _reference(p, x, held, family="sigmoid"):
    if family == SOFTMAX:
        return reference_swa_moe.experts_layer(
            p, x, top_k=10, route_scale=SCALE, held=held)
    return ref.experts_layer(p, x, top_k=K, route_scale=SCALE, held=held)


def _towards(experts):
    """A bias that sends every token's K choices to ``experts``."""
    return jnp.zeros((E,)).at[jnp.asarray(experts)].set(10.0)


# ``(held, the experts an all-on-held routing chooses, those a none-on-held
# routing chooses)`` of each family: every one of a token's choices fits.
_STEERED = {"sigmoid": ((4, 4), [4, 5, 6, 7], [0, 1, 8, 9]),
            SOFTMAX: ((20, 12), list(range(21, 31)), list(range(40, 50)))}


# A held count of each case that steers by ``held_count``, from the ladder's
# rungs and the assignments (the grouped product's row tile cut to 16, so
# that a chunk is 32 or 80 rows and 512 or 1,280 assignments take sixteen):
# no chunk, one, two, three and all sixteen.
_COUNTS = {"no_row": lambda rungs, every: 0,
           "one_under_a_rung": lambda rungs, every: rungs[1] - 1,
           "a_rung": lambda rungs, every: rungs[2],
           "one_over_a_rung": lambda rungs, every: rungs[2] + 1,
           "every_row": lambda rungs, every: every}


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("routing, held_share", [
    ("even", None), ("all_held", 1.0), ("none_held", 0.0)]
    + [(case, None) for case in _COUNTS])
def test_the_held_part_matches_the_reference(routing, held_share, family,
                                             monkeypatch):
    held, on_held, off_held = _STEERED[family]
    cfg = _cfg(held, family)
    assignments = 2 * 64 * cfg.top_k
    if routing in _COUNTS:
        monkeypatch.setattr(moe, "GMM_TILING", (16,) + moe.GMM_TILING[1:])
        rungs = moe.held_rungs(assignments)
        assert len(rungs) == 17 and rungs[-1] == assignments
        count = _COUNTS[routing](rungs, assignments)
        held_share = count / assignments
        p, x = _layer(held, family=family, held_count=count)
    else:
        rungs = moe.held_rungs(assignments)
        towards = {"even": None, "all_held": on_held,
                   "none_held": off_held}[routing]
        p, x = _layer(held, family=family, towards=towards)
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    with jax.default_matmul_precision("highest"):
        got, stats = moe.dropless_apply(p, cfg, x)
        want, balance, counts, routed_rms = _reference(p, x, held, family)
        np.testing.assert_allclose(got, want, atol=2e-5)
        grads = [jax.grad(lambda p, x: jnp.sum(f(p, x) * w), (0, 1))(p, x)
                 for f in (lambda p, x: moe.dropless_apply(p, cfg, x)[0],
                           lambda p, x: _reference(p, x, held, family)[0])]
    for a, b in zip(*(jax.tree_util.tree_leaves(g) for g in grads)):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max())
                                   + 1e-6)
    tokens = x.shape[0] * x.shape[1]
    assert float(stats["dropped"]) == 0.0
    assert float(stats["held_assignments"]) == float(
        counts[held[0]:held[0] + held[1]].sum())
    # The smallest rung that holds the step's held rows.
    assert float(stats["held_buffer_rows"]) == min(
        r for r in rungs if r >= float(stats["held_assignments"]))
    np.testing.assert_allclose(stats["held_output_rms"], routed_rms,
                               rtol=1e-4, atol=1e-7)
    assert (float(routed_rms) == 0.0) == (held_share == 0.0)
    if held_share is not None:
        assert float(stats["held_assignments"]) \
            == held_share * tokens * cfg.top_k
    np.testing.assert_allclose(stats["load_balance"], balance, rtol=1e-5)
    assert float(stats["load_max_over_mean"]) == pytest.approx(
        float(counts.max()) * cfg.num_experts / (tokens * cfg.top_k))
    if family == "sigmoid":     # the bias has no gradient: it only chooses
        assert float(jnp.abs(grads[0][0]["bias"]).max()) == 0.0
    else:
        assert "bias" not in p and "z_loss" in stats


@pytest.mark.parametrize("family, count", [("sigmoid", 4), (SOFTMAX, 2)],
                         ids=["sigmoid-4-shares", "softmax-32-shares"])
def test_the_shares_add_up_to_the_uncut_layer(family, count):
    """The routed parts of the shares (four of 4 experts; thirty-two of 2,
    10 a token, weights times 2.5) plus the shared expert, counted once, are
    the layer that holds every expert."""
    whole_p, x = _layer(None, family=family)
    cfg = _cfg(None, family)
    with jax.default_matmul_precision("highest"):
        whole, whole_stats = moe.dropless_apply(whole_p, cfg, x)
        shared = moe._shared_expert(whole_p["shared"], cfg,
                                    x.reshape(-1, D)).reshape(x.shape)
        routed, held = 0.0, 0.0
        for first in range(0, cfg.num_experts, count):
            p, _ = _layer((first, count), family=family)
            part, stats = moe.dropless_apply(
                p, _cfg((first, count), family), x)
            routed = routed + (part - shared)
            held += float(stats["held_assignments"])
            assert float(stats["dropped"]) == 0.0
    np.testing.assert_allclose(routed + shared, whole, atol=3e-5)
    assert held == x.shape[0] * x.shape[1] * cfg.top_k
    assert "held_assignments" not in whole_stats
    # And the uncut layer is the reference's with every expert held.
    with jax.default_matmul_precision("highest"):
        want, _, _, _ = _reference(whole_p, x, (0, cfg.num_experts), family)
    np.testing.assert_allclose(whole, want, atol=3e-5)


def test_the_bias_moves_towards_an_even_load_and_is_not_in_the_weights():
    held = (0, 4)
    p, x = _layer(held, 0.05 * jnp.arange(E) / E)
    _, stats = moe.dropless_apply(p, _cfg(held), x)
    _, _, counts, _ = _reference(p, x, held)
    moved = stats["state_updates"]["bias"] - p["bias"]
    mean = x.shape[0] * x.shape[1] * K / E
    np.testing.assert_allclose(
        moved, RATE * np.sign(mean - np.asarray(counts)), atol=1e-8)
    assert set(np.round(np.asarray(moved) / RATE).astype(int)) <= {-1, 0, 1}
    assert float(stats["bias_absmax"]) == pytest.approx(
        float(jnp.abs(stats["state_updates"]["bias"]).max()))
    # Weights: route_scale times the chosen scores over their sum, whatever
    # the bias; planted faults move the output.
    want, _, _, _ = _reference(p, x, held)
    for fault in ("bias_in_weights", "normalised_over_held", "no_shared"):
        broken_p, cfg = dict(p), _cfg(held)
        if fault == "no_shared":
            broken_p["shared"] = jax.tree_util.tree_map(jnp.zeros_like,
                                                        p["shared"])
            got, _ = moe.dropless_apply(broken_p, cfg, x)
        elif fault == "bias_in_weights":
            weights, chosen, scores = ref.route(p, x, top_k=K,
                                                route_scale=SCALE)
            biased = jnp.where(weights > 0, scores + p["bias"], 0.0)
            biased = SCALE * biased / biased.sum(-1, keepdims=True)
            got = want + jnp.einsum(
                "bse,ebsd->bsd", (biased - weights)[..., :4],
                jnp.stack([_one_expert(p, x, e) for e in range(4)]))
        else:
            weights, _, _ = ref.route(p, x, top_k=K, route_scale=SCALE)
            over_held = weights[..., :4]
            over_held = SCALE * over_held / jnp.maximum(
                over_held.sum(-1, keepdims=True), 1e-9)
            got = want + jnp.einsum(
                "bse,ebsd->bsd", over_held - weights[..., :4],
                jnp.stack([_one_expert(p, x, e) for e in range(4)]))
        assert float(jnp.abs(got - want).max()) > 1e-3, fault


def _one_expert(p, x, e):
    return (jax.nn.silu(x @ p["glu"]["kernel"][e]) * (x @ p["up"]["kernel"][e])
            ) @ p["down"]["kernel"][e]


def test_the_softmax_layer_that_holds_every_expert_is_as_it_was():
    """OLMoE's call: no new statistic, no shared expert, no bias."""
    cfg = moe.MoEConfig(num_experts=8, top_k=2, d_model=D, d_hidden=H,
                        dtype=jnp.float32, expert="swiglu", norm_topk=False)
    p = moe.init(jax.random.PRNGKey(0), cfg)
    assert set(p) == {"gate", "up", "down", "glu"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, D))
    out, stats = moe.dropless_apply(p, cfg, x)
    assert set(stats) == {"load_balance", "z_loss", "load_max_over_mean",
                          "dropped"}
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(moe.dropless_apply(p, cfg, x)[0],
                                   moe.dense_apply(p, cfg, x)[0], atol=2e-5)


@pytest.mark.parametrize("held", [(0, 0), (14, 4), (-1, 2)])
def test_a_share_outside_the_experts_is_refused(held):
    with pytest.raises(ValueError, match="consecutive experts"):
        _cfg(held)


def test_the_event_names_the_share():
    from autodist_tpu import observability
    p, x = _layer((8, 4))
    moe.dropless_apply(p, _cfg((8, 4)), x)
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["moe.experts_held"] == 4 and gauges["moe.experts"] == E
    assert gauges["moe.held_buffer_rungs"] == len(moe.held_rungs(512)) == 2
    assert any("experts 8-11 held" in str(e)
               for e in observability.tracing.events()
               if e.get("name") == "moe")
    # The flight recorder keeps the whole line: the ladder is in it.
    assert any("held (4 of 16: held rows in chunks of 512 rows, as many as "
               "the step's count takes, 1 at most), 1 shared, selection "
               "bias" in e["detail"]
               for e in observability.recorder.events() if e["kind"] == "moe")


@pytest.mark.parametrize("assignments, chunk, rungs", [
    (40960, 2560, 17), (32768, 2048, 17), (1280, 512, 4), (512, 512, 2),
    (10, 10, 2)])
def test_the_ladder_is_whole_chunks_up_to_every_assignment(assignments, chunk,
                                                          rungs):
    """Laguna's and JoyAI's layers, and sizes under a tile: a chunk is a
    sixteenth of the assignments in whole tiles of ``GMM_TILING[0]`` rows,
    the rungs its multiples from none to the first that holds them all."""
    assert moe.held_chunk_rows(assignments) == chunk
    ladder = moe.held_rungs(assignments)
    assert ladder == tuple(chunk * i for i in range(rungs))
    assert ladder[-2] < assignments <= ladder[-1]


@pytest.mark.parametrize("kwargs, message", [
    (dict(scoring="tanh", route_scale=2.5), "scoring must be one of"),
    (dict(expert="gelu", shared=1), "shared experts are SwiGLU")])
def test_a_configuration_no_path_computes_is_refused(kwargs, message):
    with pytest.raises(ValueError, match=message):
        moe.MoEConfig(num_experts=E, top_k=K, **kwargs)
