"""Structure of the held expert layer's program (``moe.dropless_apply`` with
``MoEConfig.held``), on the CPU from the jaxpr of ``jax.grad`` through one
layer.

The numerics tests (tests/test_moe_held.py) prove the held layer exact at
every held count; these prove that it MOVES only the held rows: the forward
and the backward pass are one loop each over chunks of the held rows, and
nowhere in the program, inside the loops or outside them, saved for the
backward pass or filled with zeros, is there an array of all ``T x k``
assignments' rows at the model's or the experts' width (a form that sizes
its buffers for the worst case, or a conditional differentiated by JAX,
which returns every branch's residuals from every branch, passes every
numeric test and has them).  And the layer that holds every expert
(OLMoE's) has no loop and no conditional of its own and lowers to the text
it lowered to before.
"""
import hashlib
import math

import jax
import jax.numpy as jnp
import pytest

from autodist_tpu.models import transformer as T
from autodist_tpu.parallel import moe

from test_moe_held import D, FAMILIES, H, _cfg, _layer, _STEERED


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs it calls (loops' and
    conditionals' bodies too), but not of a kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for x in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _shapes(eqns):
    return {v.aval.shape for e in eqns
            for v in list(e.invars) + list(e.outvars)
            if hasattr(v.aval, "shape")}


@pytest.mark.parametrize("family", FAMILIES)
def test_no_array_of_all_the_assignments_rows_is_in_the_program(
        family, monkeypatch):
    monkeypatch.setattr(moe, "GMM_TILING", (16,) + moe.GMM_TILING[1:])
    held = _STEERED[family][0]
    cfg = _cfg(held, family)
    p, x = _layer(held, family=family)
    tokens = x.shape[0] * x.shape[1]
    assignments = tokens * cfg.top_k
    chunk = moe.held_chunk_rows(assignments)
    assert chunk * 16 == assignments

    def loss(p, x):
        out, stats = moe.dropless_apply(p, cfg, x)
        return jnp.sum(out * out) + stats["load_balance"]
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(p, x).jaxpr
    eqns = list(_eqns(jaxpr))
    # Forward and backward are one loop each, and no conditional.
    names = [e.primitive.name for e in eqns]
    assert names.count("while") == 2 and "cond" not in names
    wide = [(e.primitive.name, shape) for e in eqns for shape in _shapes([e])
            if len(shape) >= 2 and shape[-1] in (D, H)
            and math.prod(shape[:-1]) >= assignments]
    assert not wide, wide
    # The rows are inside the loops, a chunk's at a time.
    for loop in (e for e in eqns if e.primitive.name == "while"):
        inside = _shapes(_eqns(loop.params["body_jaxpr"].jaxpr))
        assert {(chunk, D), (chunk, H), (tokens, D)} <= inside


# ``jax.jit(jax.grad(...)).lower(...).as_text()`` of the layer that holds
# every expert at tests/test_olmoe.py's shape, hashed on the parent commit
# (738504c): the held layers' loop changes no instruction of it.
_UNHELD_ON_THE_PARENT = "4fd0e9de7018951b"


def test_the_layer_that_holds_every_expert_has_no_loop_and_is_as_it_was():
    import test_olmoe
    cfg = T.TransformerConfig(**test_olmoe.TOY).moe
    assert cfg.held is None
    p = jax.eval_shape(lambda: moe.init(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((4, test_olmoe.TOY["max_len"], cfg.d_model),
                             jnp.float32)

    def loss(p, x):
        out, stats = moe.dropless_apply(p, cfg, x)
        return jnp.sum(out) + stats["load_balance"] + stats["z_loss"]
    grad = jax.grad(loss, (0, 1))
    assert not {"cond", "while"} & {
        e.primitive.name for e in _eqns(jax.make_jaxpr(grad)(p, x).jaxpr)}
    text = jax.jit(grad).lower(p, x).as_text()
    assert hashlib.sha1(text.encode()).hexdigest()[:16] \
        == _UNHELD_ON_THE_PARENT
