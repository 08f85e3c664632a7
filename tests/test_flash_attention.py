"""Flash attention kernel vs the dense reference (interpret mode on CPU)."""
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex_core

from autodist_tpu.graph_item import _sub_jaxprs
from autodist_tpu.models import layers as L
from autodist_tpu.ops.flash_attention import flash_attention, _dense_reference

# ``autodist_tpu.ops`` exports the function under the module's name.
fa = importlib.import_module("autodist_tpu.ops.flash_attention")


def _qkv(b=2, h=2, s=64, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d), jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal, 16, 16, 0, True)  # interpret
    expect = _dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_mha_reference():
    q, k, v = _qkv(s=32)
    got = flash_attention(q, k, v, True, 8, 8, 0, True)
    expect = L.dot_product_attention(q, k, v, L.causal_mask(q.shape[2]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(s=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, 8, 8, 0, True) ** 2).sum()

    def loss_dense(q, k, v):
        return (_dense_reference(q, k, v, causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_q_offset_matches_shifted_global_positions():
    """q_offset masks as if q were a shard of a longer sequence."""
    q, k, v = _qkv(s=32)
    qs = q[:, :, 16:, :]
    got = flash_attention(qs, k, v, True, 8, 8, 16, True)
    full = _dense_reference(q, k, v, True)[:, :, 16:, :]
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_kernel_goes_under_a_full_manual_region_on_a_mesh():
    """On a mesh of several devices the kernel call is wrapped in a
    shard_map over every free axis (batch over data, heads over model) —
    jax will not partition a Mosaic kernel itself — and an axis that can
    split neither raises instead of replicating the work."""
    from jax.sharding import Mesh
    from autodist_tpu.parallel import context as parallel_ctx

    q, k, v = _qkv(b=4, h=2, s=32)

    def kernel(ql, kl, vl):
        # Local views: batch 4 / data 4, heads 2 / model 2.
        assert ql.shape == (1, 1, 32, 16)
        return flash_attention(ql, kl, vl, True, 8, 8, 0, True)

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    with parallel_ctx.use(parallel_ctx.ParallelContext(mesh)):
        got = jax.jit(lambda q, k, v: fa._under_full_manual(
            kernel, q, k, v))(q, k, v)
        grads = jax.jit(jax.grad(lambda q, k, v: (fa._under_full_manual(
            kernel, q, k, v) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_reference(q, k, v, True)),
                               rtol=2e-5, atol=2e-5)
    want = jax.grad(lambda q, k, v: (_dense_reference(q, k, v, True) ** 2)
                    .sum(), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)

    seq_mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "seq"))
    with parallel_ctx.use(parallel_ctx.ParallelContext(seq_mesh)):
        with pytest.raises(NotImplementedError, match="'seq'"):
            fa._under_full_manual(kernel, q, k, v)


# ---------------------------------------------------------------------------
# dtype discipline: f32 lives in the accumulators and the softmax statistics;
# what enters the MXU and what leaves a kernel has the inputs' dtype.


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones (jit and custom_vjp bodies,
    a kernel's ``pl.when`` branches) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _force_rows(monkeypatch, rows):
    """Steer the one rule that picks the rows a program: the kernels take
    no argument for it."""
    rule = fa._rows_per_program

    def forced(*args):
        g, vmem = rule(*args)
        return rows, vmem // g * rows
    monkeypatch.setattr(fa, "_rows_per_program", forced)
    return fa


def _grad_jaxpr(dtype, causal=True, b=2):
    q, k, v = (x.astype(dtype) for x in _qkv(b=b, s=32))

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal, 8, 8, 0, True)
        return (o.astype(jnp.float32) ** 2).sum()

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr


def _kernels(jaxpr):
    return {e.params["name"]: e for e in _eqns(jaxpr)
            if e.primitive.name == "pallas_call"}


def test_bf16_gradients_leave_the_kernels_as_bf16():
    """The backward kernels store their f32 accumulators in the inputs'
    dtype themselves: no f32 gradient array, and no cast of one, outside."""
    jaxpr = _grad_jaxpr(jnp.bfloat16)
    kernels = _kernels(jaxpr)
    assert set(kernels) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    results = set()
    for name in ("flash_bwd_dq", "flash_bwd_dkv"):
        for out in kernels[name].outvars:
            assert out.aval.dtype == jnp.bfloat16, (name, out.aval)
            results.add(out)
    assert len(results) == 3
    # Whatever consumes a result reshapes it (to b, h, s, d): none is cast.
    consumers = 0
    for eqn in _eqns(jaxpr):
        if any(v in results for v in eqn.invars
               if not isinstance(v, jex_core.Literal)):
            assert eqn.primitive.name == "reshape", eqn
            results.update(eqn.outvars)
            consumers += 1
    assert consumers == 3


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_bf16_flash_matches_f32_dense(causal):
    """bf16 inputs, several blocks a side: forward and the three gradients
    against the dense reference computed in f32 from the same values."""
    qf, kf, vf = (x.astype(jnp.bfloat16).astype(jnp.float32)
                  for x in _qkv(s=64, d=32, seed=3))
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))
    w = jax.random.normal(jax.random.PRNGKey(9), qf.shape, jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, 16, 32, 0, True)
        return (o.astype(jnp.float32) * w).sum()

    def loss_dense(q, k, v):
        return (_dense_reference(q, k, v, causal) * w).sum()

    got = flash_attention(qb, kb, vb, causal, 16, 32, 0, True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_dense_reference(qf, kf, vf, causal)),
        rtol=2e-2, atol=2e-2)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(qf, kf, vf)
    for a, b in zip(gf, gd):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


def test_block_attn_bwd_keeps_f32_partials_for_bf16_inputs():
    """Ring attention sums per-hop partials in f32, so its per-block
    kernels are asked for f32 results whatever the inputs are."""
    from autodist_tpu.ops.flash_attention import (block_attn_bwd,
                                                  block_attn_fwd)
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(s=32))
    o, lse = block_attn_fwd(q, k, v, True, 0, 0, 8, 8, True)
    assert o.dtype == jnp.float32 and lse.dtype == jnp.float32
    do = jnp.ones_like(q)
    delta = (do.astype(jnp.float32) * o).sum(-1, keepdims=True)
    grads = block_attn_bwd(q, k, v, do, lse, delta, True, 0, 0, 8, 8, True)
    assert [g.dtype for g in grads] == [jnp.float32] * 3
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]


@pytest.mark.parametrize("rows", [1, 6], ids=["one-row", "six-rows"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kernel,products", [("flash_fwd", 2),
                                             ("flash_bwd_dq", 3),
                                             ("flash_bwd_dkv", 4)])
def test_mxu_operands_follow_the_inputs(kernel, products, dtype, rows,
                                        monkeypatch):
    """All nine products take operands of the inputs' dtype and accumulate
    in f32: with bf16 inputs none runs multi-pass on the MXU, with f32
    inputs the casts are the identity.  A program of six rows loops over
    them on the device: its code holds one row's products, not six."""
    _force_rows(monkeypatch, rows)
    body = _kernels(_grad_jaxpr(dtype, b=3))[kernel].params["jaxpr"]
    dots = [e for e in _eqns(body) if e.primitive.name == "dot_general"]
    assert len(dots) == products
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype], eqn
        assert eqn.outvars[0].aval.dtype == jnp.float32, eqn


# ---------------------------------------------------------------------------
# several (batch, head) rows a program: the rule, and what it may not change


def _fwd_and_bwd(fa, q, k, v, do, causal, block_q, block_k):
    """(o, lse, dq, dk, dv) of the interpreted kernels, compiled without
    LLVM's optimisations: with them XLA's CPU backend gives a program of
    several rows other code than a program of one, and one row's ``lse``
    then differs in its last bit (bf16, causal, one k block)."""
    def run(q, k, v, do):
        o, lse = fa._flash_fwd(q, k, v, causal, block_q, block_k, 0, 0, True)
        delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
            .sum(-1, keepdims=True)
        return (o, lse) + tuple(fa._flash_bwd(
            q, k, v, do, lse, delta, causal, block_q, block_k, 0, 0, True))
    return jax.jit(run).lower(q, k, v, do).compile(
        compiler_options={"xla_backend_optimization_level": 0})(q, k, v, do)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("block_k", [32, 8], ids=["one-k-block",
                                                  "four-k-blocks"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("rows", [2, 3, 6])
def test_rows_a_program_change_no_bit(rows, causal, block_k, dtype,
                                      monkeypatch):
    """A row's arithmetic does not depend on how many rows share its
    program: o, lse, dq, dk, dv of the interpreted kernels at G = 2 (one
    step of two rows), 3 (a loop of three) and 6 (a loop of three steps of
    two rows) are those of G = 1, with the accumulators carried across k
    blocks too."""
    q, k, v, do = (x.astype(dtype) for x in
                   _qkv(b=3, s=32, seed=5) + (_qkv(b=3, s=32, seed=6)[0],))
    fa = _force_rows(monkeypatch, 1)
    want = _fwd_and_bwd(fa, q, k, v, do, causal, 16, block_k)
    monkeypatch.undo()
    fa = _force_rows(monkeypatch, rows)
    # Two of these small tiles fill a step of the loop.
    monkeypatch.setattr(fa, "_STEP_TILE", 2 * 16 * block_k)
    got = _fwd_and_bwd(fa, q, k, v, do, causal, 16, block_k)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)
    assert np.isfinite(np.asarray(got[0], np.float32)).all()


# (batch x heads, seq, head width) of a kernel call, a chip: the five cells
# of the benchmark, then counts of rows that are prime.
_CELL_SHAPES = {
    "gpt2-medium.train-s1024": ((128, 1024, 64), (1, 1)),
    "gpt2-xl.train-s1024-x4": ((50, 1024, 64), (1, 1)),
    "olmoe-1b-7b.train-s4096": ((32, 4096, 128), (1, 1)),
    "bert-base.mlm-s512": ((768, 512, 64), (2, 2)),
    "bert-base.mlm-s128": ((3072, 128, 64), (8, 32)),
    "seven-rows-s128": ((7, 128, 64), (7, 7)),
    "thirty-seven-rows-s128": ((37, 128, 64), (1, 1)),
}
_plans = {}


def _plan(cell):
    """Trace forward and backward at the cell's shape (nothing runs):
    ``(kernels of the jaxpr, [(G, vmem bytes)] as the rule returned them)``."""
    if cell not in _plans:
        (bh, s, d), _ = _CELL_SHAPES[cell]
        x = jax.ShapeDtypeStruct((1, bh, s, d), jnp.bfloat16)
        rule, picked = fa._rows_per_program, []

        def recording(*args):
            picked.append(rule(*args))
            return picked[-1]

        def loss(q, k, v):
            o = flash_attention(q, k, v, False, 512, 1024, 0, True)
            return (o.astype(jnp.float32) ** 2).sum()
        fa._rows_per_program = recording
        try:
            jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
        finally:
            fa._rows_per_program = rule
        _plans[cell] = _kernels(jaxpr.jaxpr), picked
    return _plans[cell]


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_rows_a_program_follow_the_shape(cell):
    """The rule alone: one row a program where a row's tile is as large as
    a long-sequence program's (those cells' kernels are the programs they
    were), two at s = 512, 8 to 32 at s = 128; always a divisor of
    batch x heads, and the padded VMEM estimate within the budget."""
    (bh, s, _), (least, most) = _CELL_SHAPES[cell]
    _, picked = _plan(cell)
    assert len(picked) == 3                      # fwd, dq, dkv
    for g, vmem in picked:
        assert least <= g <= most, picked
        assert bh % g == 0, picked
        assert g * min(s, 512) * min(s, 1024) <= fa._MAX_TILE or g == 1
        assert 0 < vmem <= fa._VMEM_BUDGET, picked


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_grid_starts_with_programs_not_rows(cell):
    """Each layer still makes three ``pallas_call``s under their three
    names (the benchmark's trace reader finds them by name and counts
    calls); the grid's first dimension is batch x heads / G."""
    (bh, s, d), _ = _CELL_SHAPES[cell]
    kernels, picked = _plan(cell)
    assert list(kernels) == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    for (name, eqn), (g, _) in zip(kernels.items(), picked):
        grid = eqn.params["grid_mapping"].grid
        assert grid[0] == bh // g, (name, grid, g)
        assert grid[1:] == (s // min(s, 512), s // min(s, 1024)) or \
            name == "flash_bwd_dkv" and \
            grid[1:] == (s // min(s, 1024), s // min(s, 512)), (name, grid)
        for out in eqn.outvars[:1]:
            assert out.aval.shape == (bh, s, d)


@pytest.mark.parametrize("rows,tile,steps,a_step", [
    (1, 128 * 128, 1, 1), (16, 128 * 128, 4, 4), (6, 128 * 128, 2, 3),
    (7, 128 * 128, 7, 1), (2, 512 * 512, 2, 1), (4, 64 * 64, 1, 4)])
def test_rows_a_step_follow_the_tile(rows, tile, steps, a_step):
    """A step of a program's loop takes the rows whose score tiles fit the
    vector registers, four at 128 x 128 and one at 512 x 512: one row as an
    index (the two-dimensional arithmetic of a one-row program), several as
    a slice of the block."""
    from jax.experimental import pallas as pl
    seen = []
    jaxpr = jax.make_jaxpr(lambda: fa._for_rows(rows, tile, seen.append))()
    loops = [e.params["length"] for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "scan"]
    assert loops == ([steps] if steps > 1 else [])
    # The body is traced once, whatever the rows.
    (at,) = seen
    if rows == 1:
        assert at == 0
    elif a_step == 1:
        assert at.shape == () and at.dtype == jnp.int32
    elif steps == 1:
        assert at == slice(None)
    else:
        assert isinstance(at, pl.Slice) and at.size == a_step


def test_padded_bytes_count_whole_lanes_and_sublanes():
    # A width of 64 or of 1 occupies 128 lanes; bf16 packs 16 rows a tile.
    assert fa._padded_bytes((128, 64), jnp.bfloat16) == 128 * 128 * 2
    assert fa._padded_bytes((128, 1), jnp.float32) == 128 * 128 * 4
    assert fa._padded_bytes((8, 128), jnp.bfloat16) == 16 * 128 * 2
    assert fa._padded_bytes((3, 512, 128), jnp.float32) == 3 * 512 * 128 * 4


def test_flash_event_and_gauge_carry_the_rows_at_trace_time():
    """Telemetry says which program the rule made of a call: the gauge
    ``flash.rows_per_program`` and one ``flash`` event a kernel and shape,
    written while tracing (nothing runs)."""
    from autodist_tpu import observability
    from autodist_tpu.observability import recorder
    observability.reset()
    x = jax.ShapeDtypeStruct((3, 4, 128, 64), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, False, 512, 1024, 0, True)
        return (o.astype(jnp.float32) ** 2).sum()
    for _ in range(2):
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["flash.rows_per_program"] == 12
    events = [e["detail"] for e in recorder.events() if e["kind"] == "flash"]
    assert len(events) == len(set(events)) == 3
    for kernel, detail in zip(("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
                              events):
        assert detail.startswith(f"{kernel} bfloat16[12,128,64]"), detail
        assert "blocks 128 x 128, G = 12 " in detail, detail
        assert " 1 programs a call" in detail and "VMEM" in detail, detail
