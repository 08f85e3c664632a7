"""HLO-assertion tier for expert parallelism (MoE dispatch).

The numerics tests (tests/test_moe.py) prove the capacity dispatch is
expert-CORRECT; these prove it is expert-PARALLEL: in the compiled dp x ep
program the per-device expert-FFN operands must be E/ep-expert buffers (the
FLOPs split that makes EP worth having), tokens must cross the expert axis
through real collectives, and the capacity path must cost measurably fewer
FLOPs than dense all-experts compute.  A dispatch that degenerated to
replicated gathers (every device computing all E experts) passes every
numeric test and fails here.

Claim under test: ``autodist_tpu/parallel/moe.py`` apply()/_constrain_
expert_sharded.  Reference has no EP at all (SURVEY.md §2.3); the structure
asserted is the GShard/Switch SPMD form.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.strategy import AllReduce, ModelParallel
from autodist_tpu.parallel import moe as moe_mod

EP = 4
E = 8
D_MODEL = 32
D_HIDDEN = 128
TOKENS = 256


def _build(apply_fn):
    from autodist_tpu.autodist import _reset_default
    _reset_default()  # two programs built inside one module-scoped fixture
    cfg = moe_mod.MoEConfig(num_experts=E, top_k=2, d_model=D_MODEL,
                            d_hidden=D_HIDDEN)
    k = jax.random.PRNGKey(1)
    params = {"moe": moe_mod.init(k, cfg),
              "head": {"kernel": jax.random.normal(k, (D_MODEL, 4)) * 0.1}}

    def loss(p, b):
        x, labels = b
        h, aux = apply_fn(p["moe"], cfg, x)
        logits = h @ p["head"]["kernel"]
        ce = -jnp.mean(jax.nn.log_softmax(logits)[
            jnp.arange(labels.shape[0]), labels])
        return ce + 0.01 * aux

    rng = np.random.RandomState(0)
    batch = (rng.randn(TOKENS, D_MODEL).astype(np.float32),
             rng.randint(0, 4, (TOKENS,)).astype(np.int32))
    ad = AutoDist(strategy_builder=ModelParallel(
        AllReduce(), model_axis=EP, rules=moe_mod.EXPERT_RULES,
        mesh_axis="expert"))
    item = ad.capture(loss, params, optax.adam(1e-3), example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    sharded = runner.remapper.shard_batch(batch)
    state, metrics = runner.step(state, sharded, shard_inputs=False)
    assert np.isfinite(float(metrics["loss"]))
    state_shapes = jax.eval_shape(lambda: runner.create_state())
    compiled = runner._compiled.lower(state_shapes, sharded).compile()
    return compiled


def _ffn_dot_lead_dims(text):
    """Leading (expert-batch) dims of every compiled expert-FFN op —
    shared matcher with the v5e-compiler case in test_topology_aot.py
    (``report.einsum_result_lead_dims``)."""
    from autodist_tpu.report import einsum_result_lead_dims
    return einsum_result_lead_dims(text, ("ecd,edh->ech", "ech,ehd->ecd"))


@pytest.fixture(scope="module")
def compiled_pair():
    capacity = _build(moe_mod.apply)
    dense = _build(moe_mod.dense_apply)
    return capacity, dense


def test_expert_ffn_operands_are_ep_sharded(compiled_pair):
    """Every expert-FFN dot runs on an E/ep buffer, none on all E experts."""
    text = compiled_pair[0].as_text()
    lead = _ffn_dot_lead_dims(text)
    assert lead, "no expert-FFN dots found in HLO (metadata format changed?)"
    assert all(d == E // EP for d in lead), (
        f"expert-FFN dots with per-device expert dims {sorted(set(lead))}; "
        f"expected all {E // EP} (= E/ep) — dispatch degenerated to "
        f"replicated expert compute")


def test_tokens_cross_expert_axis_via_collectives(compiled_pair):
    """Dispatch/combine must exchange over the expert axis: at least one
    collective whose replica groups have expert-axis size (groups of ep
    devices), not only data-axis (groups of 8/ep) collectives."""
    text = compiled_pair[0].as_text()
    ops = re.findall(
        r"(all-to-all|collective-permute|all-gather|reduce-scatter)"
        r"(?:-start)?(?:\.\d+)?\([^\n]*", text)
    assert ops, "no collectives at all in a dp x ep program"
    # replica_groups=[G,S]<=... : S = group size.  Expert-axis exchange has
    # S == EP (all-to-all/all-gather over 'expert').
    from autodist_tpu.report import replica_group_sizes
    group_sizes = replica_group_sizes(text)
    assert EP in group_sizes, (
        f"no collective spans the expert axis (group sizes seen: "
        f"{sorted(group_sizes)}; expected one of size {EP})")


def test_capacity_dispatch_saves_flops_vs_dense(compiled_pair):
    """FLOPs contract: capacity dispatch computes ~T*k*cf tokens of FFN
    instead of T*E (E/(k*cf) = 3.2x less expert compute at E=8,k=2,cf=1.25).
    Whole-program FLOPs include gate/head/optimizer, so assert a
    conservative margin rather than the pure-FFN ratio."""
    def flops(compiled):
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return float(ca.get("flops", 0))

    f_cap, f_dense = flops(compiled_pair[0]), flops(compiled_pair[1])
    if not f_cap or not f_dense:
        pytest.skip("backend reports no cost analysis")
    assert f_cap < 0.7 * f_dense, (
        f"capacity dispatch flops {f_cap:.3g} not materially below dense "
        f"{f_dense:.3g} (ratio {f_cap / f_dense:.2f})")
