"""The plain reference against the program's own loss, at a toy size on
the CPU (the chip's check at the published widths is the benchmark's own,
in every run)."""
import json
import pathlib

import jax
import numpy as np
import pytest

from chipbench import reference
from chipbench.catalog import Catalog

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
TOYS = {"causal_lm": ("tiny-lm", "tiny-lm-s32"),
        "masked_lm": ("tiny-mlm", "tiny-mlm-s32")}


def _toy(kind_name, compute_dtype):
    config, traffic = TOYS[kind_name]
    sizes = json.loads((DATA / f"{config}.json").read_text())
    sizes["deployment"]["compute_dtype"] = compute_dtype
    mix = json.loads((DATA / f"{traffic}.json").read_text())
    kind = Catalog(str(ROOT)).module("kinds", kind_name)
    init, loss_fn = kind.program(sizes)
    params = init(jax.random.PRNGKey(5))
    batch = kind.host_batch(sizes, mix, 4, np.random.RandomState(5))
    return kind, sizes, params, batch, loss_fn


@pytest.mark.parametrize("kind_name", sorted(TOYS))
@pytest.mark.parametrize("compute_dtype, rtol", [
    # The same arithmetic in the same precision: rounding order only.
    ("float32", 1e-5),
    # The precision the cells run in, inside the tolerance their
    # configurations state.
    ("bfloat16", 1e-3),
])
def test_reference_loss_equals_the_programs(kind_name, compute_dtype, rtol):
    kind, sizes, params, batch, loss_fn = _toy(kind_name, compute_dtype)
    got = float(jax.jit(loss_fn)(params, batch))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(kind.reference_loss(sizes))(params, batch))
    assert got == pytest.approx(want, rel=rtol)
    # Random weights: the loss starts near ln V.
    assert want == pytest.approx(np.log(sizes["vocab_size"]), rel=0.05)


@pytest.mark.parametrize("kind_name", sorted(TOYS))
def test_reference_gradients_equal_the_programs(kind_name):
    kind, sizes, params, batch, loss_fn = _toy(kind_name, "float32")
    got = jax.jit(jax.grad(loss_fn))(params, batch)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(kind.reference_loss(sizes)))(params, batch)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_tolerance_tells_a_wrong_mask():
    """What the check is for: at a toy size and from random weights, the
    causal model without its mask is already further from the program than
    any cell's tolerance allows."""
    kind, sizes, params, batch, loss_fn = _toy("causal_lm", "float32")
    got = float(jax.jit(loss_fn)(params, batch))
    (tokens,) = batch

    def unmasked(params, batch):
        hidden = reference.hidden_states(
            params, tokens[:, :-1], layers=sizes["n_layer"],
            heads=sizes["n_head"], causal=False,
            eps=sizes["block"]["layernorm_eps"])
        return reference.tied_head_xent(params, hidden, tokens[:, 1:])

    wrong = float(jax.jit(unmasked)(params, batch))
    loosest = max(
        json.loads(path.read_text())["check"]["rtol"]
        for path in (ROOT / "chipbench" / "configs").glob("*.json"))
    assert loosest <= 0.0002
    assert abs(wrong - got) / got > 2 * loosest


def test_train_losses_is_three_plain_adam_steps():
    kind, sizes, params, batch, _ = _toy("causal_lm", "float32")
    ref_loss = kind.reference_loss(sizes)
    first = float(jax.jit(ref_loss)(params, batch))
    losses = reference.train_losses(ref_loss, params, [batch] * 3, 1e-2,
                                     chunk_rows=2)
    assert len(losses) == 3 and losses[0] == pytest.approx(first, rel=1e-6)
    # The same batch three times: Adam at 1e-2 must bring the loss down.
    assert losses[2] < losses[1] < losses[0]
