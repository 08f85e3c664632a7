"""Grouped key-value heads and a window in the flash kernels (interpreted) and
on the dense path, against the plain reference's attention
(``chipbench/reference_swa_moe.py``: keys and values indexed by ``h // group``
under an explicit ``t - window < s <= t`` mask); the window's walk by brute
force; YaRN's tables against their equations; and, with no window and one
query head a key-value head, the kernels' traced bodies are the parent's."""
import hashlib
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import layers as L
from chipbench import reference_swa_moe as ref

fa = importlib.import_module("autodist_tpu.ops.flash_attention")

# 16-key sub-tiles: the row of 64 below is four of them, as the cell's 4,096
# is eight of 512.  ``window``: none, the sub-tile's own length (the cell's
# 512 of 512), shorter than a sub-tile, longer than the row.
SUB = 16
WINDOWS = [None, 16, 5, 200]
GROUPS = [1, 6, 9]


def _operands(group, s=64, d=16, kv=2, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed + group), 4)
    q, do = (jax.random.normal(k, (1, kv * group, s, d)).astype(dtype)
             for k in ks[:2])
    k, v = (jax.random.normal(k, (1, kv, s, d)).astype(dtype)
            for k in ks[2:])
    return q, k, v, do


def _reference(q, k, v, window):
    with jax.default_matmul_precision("highest"):
        return ref.attention_core(q, k, v, window)


def _grads(fn, q, k, v, do):
    return jax.grad(lambda *x: (fn(*x).astype(jnp.float32)
                                * do.astype(jnp.float32)).sum(),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("window", WINDOWS, ids=lambda w: f"window-{w}")
@pytest.mark.parametrize("group", GROUPS, ids=lambda g: f"group-{g}")
@pytest.mark.parametrize("path", ["kernels", "dense"])
def test_forward_and_three_gradients_match_the_reference(path, group, window,
                                                         monkeypatch):
    """o, dq and the key-value-head-wide dk and dv, which sum a group's
    query heads: the interpreted kernels (blocks 16 x 32 in two sub-tiles)
    and the dense path, each against the reference."""
    monkeypatch.setattr(fa, "_SUB_TILE", SUB)
    q, k, v, do = _operands(group)
    interpret = True if path == "kernels" else None

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, True, 16, 32, 0, interpret,
                                  window)
    got = attend(q, k, v)
    want = _reference(q, k, v, window)
    np.testing.assert_allclose(got, want, atol=3e-6)
    for g, e, x in zip(_grads(attend, q, k, v, do),
                       _grads(lambda *x: _reference(*x, window), q, k, v, do),
                       (q, k, v)):
        assert g.shape == x.shape
        np.testing.assert_allclose(g, e, atol=1e-4)


@pytest.mark.parametrize("q_offset,k_offset", [(0, 0), (32, 0), (0, 32),
                                               (64, 16)],
                         ids=["diagonal", "behind", "ahead", "shifted"])
@pytest.mark.parametrize("window", [None, 16, 40])
def test_traced_offsets_as_ring_attention_passes_them(window, q_offset,
                                                      k_offset, monkeypatch):
    """One hop's block: q and k shards at traced global offsets, grouped
    heads, a window; rows that see no key give o = 0 and the sentinel lse,
    on the kernels and on the dense path alike, and the backward pass takes
    the global statistics."""
    monkeypatch.setattr(fa, "_SUB_TILE", SUB)
    q, k, v, do = _operands(3, s=32)
    t = (q_offset + jnp.arange(32))[:, None]
    s = (k_offset + jnp.arange(32))[None, :]
    seen = (s <= t) if window is None else (s <= t) & (t - window < s)

    def dense(q, k, v):
        keys = k[:, jnp.arange(6) // 3]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, keys) / 4.0
        scores = jnp.where(seen, scores, -jnp.inf)
        top = jnp.max(scores, -1, keepdims=True)
        top = jnp.where(jnp.isfinite(top), top, 0.0)
        p = jnp.exp(scores - top)
        l = p.sum(-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v[:, jnp.arange(6) // 3]) \
            / jnp.maximum(l, 1e-30)
        return o, jnp.where(l > 0, top + jnp.log(jnp.maximum(l, 1e-30)),
                            fa._NEG_INF)

    with jax.default_matmul_precision("highest"):
        want_o, want_lse = dense(q, k, v)
        want = jax.grad(lambda *x: (dense(*x)[0] * do).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    for interpret in (True, False):     # the kernels, then the dense path
        run = jax.jit(lambda qo, ko: fa.block_attn_fwd(
            q, k, v, True, qo, ko, 16, 32, interpret=interpret,
            window=window))
        if not interpret:
            # Off the TPU ``block_attn_fwd`` takes the dense path.
            assert fa._use_pallas(q, k, 16, 32, False) is False
        o, lse = run(jnp.int32(q_offset), jnp.int32(k_offset))
        np.testing.assert_allclose(o, want_o, atol=3e-6)
        np.testing.assert_allclose(lse, want_lse, rtol=1e-5)
        if not interpret and not bool(seen.any(-1).all()):
            # The dense backward takes the GLOBAL statistics (ring attention
            # passes the whole ring's): a row whose own lse is the sentinel
            # is the kernels' to mask.
            continue
        delta = (do * o).sum(-1, keepdims=True)
        got = jax.jit(lambda qo, ko: fa.block_attn_bwd(
            q, k, v, do, lse, delta, True, qo, ko, 16, 32,
            interpret=interpret, window=window))(
                jnp.int32(q_offset), jnp.int32(k_offset))
        for g, e in zip(got, want):
            np.testing.assert_allclose(g, e, atol=1e-4)


def _whole_rectangle(monkeypatch):
    """The parent's program: every call on the whole rectangle of blocks
    with the plain index maps, which is what a call with no ``_Sweep`` (one
    that is not causal, to ``_grid``) builds."""
    grid = fa._grid
    monkeypatch.setattr(fa, "_grid",
                        lambda causal, *a, **k: grid(False, *a, **k))


def _level_0(fn, *args):
    """``fn(*args)`` compiled without LLVM's optimisations: two programs of
    different grids then run the same arithmetic a tile
    (``test_flash_attention._fwd_and_bwd``)."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})(*args)


@pytest.mark.parametrize("block_k", [32, 16], ids=["16x32", "16x16"])
@pytest.mark.parametrize("window", [16, 5, 40], ids=lambda w: f"window-{w}")
@pytest.mark.parametrize("group", [6, 9], ids=lambda g: f"group-{g}")
def test_a_windows_own_grid_changes_no_bit(group, window, block_k,
                                           monkeypatch):
    """Under a window the grid's inner dimension is the window's extent and
    a step's block ``first + j``: o, lse, dq and the key-value-head-wide dk
    and dv are, bit for bit, those of the whole rectangle with the plain
    maps (the parent's program: a call with no ``_Sweep`` builds it), with
    the dk/dv kernel's grid fanned over groups of 6 and 9 query heads."""
    monkeypatch.setattr(fa, "_SUB_TILE", SUB)
    q, k, v, do = _operands(group)

    def both(q, k, v, do):
        o, lse = fa._flash_fwd(q, k, v, True, 16, block_k, 0, 0, True,
                               window=window)
        delta = (do * o).sum(-1, keepdims=True)
        return (o, lse) + tuple(fa._flash_bwd(
            q, k, v, do, lse, delta, True, 16, block_k, 0, 0, True,
            window=window))
    narrow = fa._Sweep.of(False, 64, 64, 16, block_k, window, (0, 0))
    fanned = fa._Sweep.of(True, 64, 64, 16, block_k, window, (0, 0))
    # A q block's 16 rows see both k blocks of 32 whatever the window, and 2
    # of the 4 of 16 (all 4 under the window of 40); a k block of 32 keys is
    # seen from 3 of the 4 q blocks, one of 16 from 2 (4 under the 40).
    assert (narrow.extent, narrow.blocks) == \
        ((2, 2) if block_k == 32 else (4 if window == 40 else 2, 4))
    assert (fanned.extent, fanned.blocks) == \
        (4 if window == 40 else 3 if block_k == 32 else 2, 4)
    kernels = {e.params["name"]: e.params["grid_mapping"].grid
               for e in jax.make_jaxpr(both)(q, k, v, do).jaxpr.eqns
               if e.primitive.name == "pallas_call"}
    assert kernels == {
        "flash_fwd": (2 * group, 4, narrow.extent),
        "flash_bwd_dq": (2 * group, 4, narrow.extent),
        "flash_bwd_dkv": (2, 64 // block_k, group * fanned.extent)}
    got = _level_0(both, q, k, v, do)
    _whole_rectangle(monkeypatch)
    want = _level_0(both, q, k, v, do)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("q_offset,k_offset", [(0, 0), (32, 0), (0, 32),
                                               (64, 16), (16, 48)],
                         ids=["diagonal", "behind", "ahead", "shifted",
                              "half-seen"])
@pytest.mark.parametrize("window", [None, 16, 40])
def test_traced_offsets_clamp_on_the_device_and_change_no_bit(
        window, q_offset, k_offset, monkeypatch):
    """Ring attention's hop: the offsets are traced, so the maps read them
    from the scalar-prefetch operand where they run, and a block wholly
    masked by them clamps to an empty range and is skipped as it was; the
    results are the whole rectangle's, bit for bit."""
    monkeypatch.setattr(fa, "_SUB_TILE", SUB)
    q, k, v, do = _operands(3, s=64)

    def hop(qo, ko):
        o, lse = fa.block_attn_fwd(q, k, v, True, qo, ko, 16, 32,
                                   interpret=True, window=window)
        delta = (do * o).sum(-1, keepdims=True)
        return (o, lse) + tuple(fa.block_attn_bwd(
            q, k, v, do, lse, delta, True, qo, ko, 16, 32, interpret=True,
            window=window))
    offsets = jnp.int32(q_offset), jnp.int32(k_offset)
    got = _level_0(hop, *offsets)
    _whole_rectangle(monkeypatch)
    want = _level_0(hop, *offsets)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(a, b, err_msg=name)


def _brute_force(sq, sk, block_q, block_k, sub, q_offset, k_offset, window):
    """Sub-tiles of ``block_q x sub`` that hold a seen score."""
    t = (q_offset + np.arange(sq))[:, None]
    s = (k_offset + np.arange(sk))[None, :]
    seen = (s <= t) & ((t - window < s) if window else True)
    tiles = seen.reshape(sq // block_q, block_q, sk // sub, sub)
    return int(tiles.any(axis=(1, 3)).sum())


@pytest.mark.parametrize("window", [512, 100, 513, 1024, 5000])
@pytest.mark.parametrize("q_offset,k_offset", [(0, 0), (1024, 0), (0, 512)])
def test_the_windows_walk_visits_what_holds_a_seen_score(window, q_offset,
                                                         k_offset):
    """``_causal_plan`` (the integers' side of ``_walk``, whose traced side
    the device runs): at the cell's blocks every sub-tile that holds a seen
    score is visited and no other; the cell's own 15 of 64."""
    total, visited, masked = fa._causal_plan(4096, 4096, 512, 1024, 512,
                                             q_offset, k_offset, window)
    assert total == 64
    assert visited == _brute_force(4096, 4096, 512, 1024, 512, q_offset,
                                   k_offset, window)
    assert masked <= visited
    if (window, q_offset, k_offset) == (512, 0, 0):
        assert (visited, masked) == (15, 15)
        assert fa._causal_plan(4096, 4096, 512, 1024, 512)[1] == 36


@pytest.mark.parametrize("window", [16, 5, 40, 200])
def test_the_device_walks_as_the_plan_says(window):
    """``_walk`` on traced values gives the integers' counts: the rule the
    kernels run on the device is the rule the plan and the gauges report."""
    for q_start in range(0, 64, 16):
        for k_start in range(0, 64, 32):
            want = fa._walk(q_start, k_start, 16, 32, 8, window)
            got = jax.jit(lambda q, k: fa._walk(q, k, 16, 32, 8, window))(
                jnp.int32(q_start), jnp.int32(k_start))
            assert tuple(int(x) for x in got) == want


# The three kernels' traced bodies (the form the chip runs) at two of the
# guarded cells' shapes, hashed with no window and one query head a key-value
# head: neither argument, left out, changes an instruction of them.  Hashed
# anew by PR 45, which changed the bodies themselves (the forward's running
# statistics are lane-replicated, the dk/dv kernel's scores transposed); PR 36 pinned
# its parent's (a117e0a) the same way.
_ON_THE_PARENT = {
    ((1, 16, 4096, 128), True): {"flash_fwd": "22d9226e0fc740d5", "flash_bwd_dq": "75c24f8330df407a",
                                 "flash_bwd_dkv": "ff784f0762ceb353"},
    ((8, 12, 128, 64), False): {"flash_fwd": "503d8914d9b56097", "flash_bwd_dq": "ba321e9e5e50f812",
                                "flash_bwd_dkv": "f73e20d5deb976eb"},
}


def _bodies(shape, causal, kv_heads=None, window=None):
    b, h, s, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, kv_heads or h, s, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal, 512, 1024, 0, False, window)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, k)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                found[name] = hashlib.sha1(
                    str(eqn.params["jaxpr"]).encode()).hexdigest()[:16]
            for param in eqn.params.values():
                for x in param if isinstance(param, (list, tuple)) \
                        else (param,):
                    inner = getattr(x, "jaxpr", x)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("shape,causal", list(_ON_THE_PARENT))
def test_with_no_window_and_one_head_a_group_the_bodies_are_the_parents(
        shape, causal):
    assert _bodies(shape, causal) == _ON_THE_PARENT[(shape, causal)]
    if causal:      # and a group or a window does change them
        grouped = _bodies(shape, causal, kv_heads=2)
        windowed = _bodies(shape, causal, window=512)
        assert grouped["flash_bwd_dkv"] != \
            _ON_THE_PARENT[(shape, causal)]["flash_bwd_dkv"]
        assert all(windowed[k] != v for k, v in
                   _ON_THE_PARENT[(shape, causal)].items())


def test_results_with_no_window_and_one_head_a_group_equal_the_dense_paths():
    """Bit for bit what the call gave before it took a window: ``window``
    None and as many key-value heads is the same trace, so the same bits,
    interpreted and dense."""
    q, k, v, _ = _operands(1, kv=4)
    for interpret in (True, None):
        a = fa.flash_attention(q, k, v, True, 16, 32, 0, interpret)
        b = fa.flash_attention(q, k, v, True, 16, 32, 0, interpret, None)
        assert np.array_equal(a, b)
        # A window longer than the row hides nothing: the same values (not
        # the same instructions).
        c = fa.flash_attention(q, k, v, True, 16, 32, 0, interpret, 4096)
        np.testing.assert_allclose(a, c, atol=1e-6)


@pytest.mark.parametrize("mask_heads", [1, 4])
def test_the_plain_paths_mask_by_batch_row_stands_beside_the_group(mask_heads):
    """``dot_product_attention`` with grouped heads keeps the group as a
    dimension of its own: a mask (batch, 1 or heads, sq, sk) has to land on
    batch rows and heads, not on key-value heads and the group."""
    rows, kv, group, s, d = 2, 2, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (rows, kv * group, s, d))
    k, v = (jax.random.normal(key, (rows, kv, s, d)) for key in ks[1:3])
    mask = jax.random.bernoulli(ks[3], 0.6, (rows, mask_heads, s, s)) \
        | jnp.eye(s, dtype=bool)
    of_head = jnp.arange(kv * group) // group
    np.testing.assert_allclose(
        L.dot_product_attention(q, k, v, mask),
        L.dot_product_attention(q, k[:, of_head], v[:, of_head], mask),
        atol=1e-6)


def test_what_the_kernels_do_not_serve_is_refused_by_name():
    q, k, v, _ = _operands(2)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, False, 16, 32, 0, True, 8)
    with pytest.raises(NotImplementedError, match="split layout"):
        fa._flash_fwd(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), True,
                      16, 32, 0, 0, True, packed=True)
    with pytest.raises(AssertionError, match="do not group"):
        fa.flash_attention(q[:, :3], k, v, True, 16, 32, 0, True)


def test_the_flash_line_names_the_group_and_the_windows_walk(monkeypatch):
    from autodist_tpu import observability
    monkeypatch.setattr(fa, "_SUB_TILE", SUB)
    monkeypatch.setattr(fa, "_logged_paths", set())
    monkeypatch.setattr(fa, "_announced", set())
    q, k, v, _ = _operands(3)
    fa.flash_attention(q, k, v, True, 16, 32, 0, True, 16)
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["flash.group"] == 3
    assert gauges["flash.window_subtiles_total"] == 16
    assert gauges["flash.window_subtiles_visited"] == 7
    assert gauges["flash.causal_subtiles_visited"] == 10
    from autodist_tpu.observability import recorder
    said = [e["detail"] for e in recorder.events() if e["kind"] == "flash"]
    assert any("a window of 16 keys: 7 of 16 sub-tiles" in e
               and "3 query heads read one key-value head" in e
               for e in said)
    fa.flash_attention(q, q, q, True, 16, 32, 0, True)
    gauges = observability.registry().snapshot()["gauges"]
    assert gauges["flash.group"] == 1
    assert gauges["flash.window_subtiles_total"] == 0


def _yarn_by_hand(seq, lanes, theta, factor, original, fast, slow, scale):
    def correction(beta):
        return lanes * math.log(original / (2 * math.pi * beta)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), lanes - 1)
    inv = []
    for i in range(lanes // 2):
        r = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        base = theta ** (2.0 * i / lanes)
        inv.append((1.0 - r) / (factor * base) + r / base)
    angles = np.arange(seq)[:, None] * np.asarray(inv)[None, :]
    return (low, high, np.cos(angles) * scale, np.sin(angles) * scale)


def test_yarn_tables_follow_their_equations():
    """Laguna's full layers: 64 lanes, theta 500,000, factor 128 over 8,192,
    beta 32 / 1: the ramp runs from pair 9 to pair 18, so the first nine
    pairs keep their frequency and those from 18 on turn 128 times slower."""
    args = (64, 500000.0, 128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    low, high, cos, sin = _yarn_by_hand(256, *args)
    assert (low, high) == (9, 18)
    got_cos, got_sin = L.yarn_rope_tables(256, *args)
    assert got_cos.shape == (256, 64)
    np.testing.assert_allclose(got_cos[:, :32], cos, atol=1e-4)
    np.testing.assert_allclose(got_sin[:, 32:], sin, atol=1e-4)
    ref_cos, ref_sin = ref.rotary_tables(256, 64, 500000.0, dict(
        factor=128.0, original_len=8192, beta_fast=32.0, beta_slow=1.0,
        attention_factor=args[-1]))
    np.testing.assert_allclose(ref_cos, cos, atol=1e-4)
    np.testing.assert_allclose(ref_sin, sin, atol=1e-4)
    plain = L.rope_tables(256, 64, 500000.0)
    np.testing.assert_allclose(got_cos[:, :9], plain[0][:, :9] * args[-1],
                               atol=1e-4)
    slow = np.cos(np.arange(256)[:, None] / (128.0 * 500000.0 ** (
        2.0 * np.arange(18, 32) / 64))) * args[-1]
    np.testing.assert_allclose(got_cos[:, 18:32], slow, atol=1e-4)
    # Static: a longer table begins with the shorter one.
    np.testing.assert_array_equal(
        L.yarn_rope_tables(64, *args)[0], got_cos[:64])


def test_partial_rotary_passes_the_other_lanes():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
    tables = L.rope_tables(8, 8, 100.0)
    turned = L.apply_rope(x, tables)
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])
    np.testing.assert_allclose(turned[..., :8],
                               L.apply_rope(x[..., :8], tables), atol=0)
    half = tuple(t[:, :4] for t in tables)
    np.testing.assert_allclose(turned, ref.rotate(x, half), atol=1e-6)


# -- 256 lanes, eight query heads a key-value head ------------------------------

def test_256_lanes_at_a_group_of_eight_match_the_reference(monkeypatch):
    """The Qwen3-Next cell's full layer in small: 16 query heads of 256 lanes
    over 2 key-value heads, no window; o, dq and the key-value-head-wide dk
    and dv through the interpreted kernels (blocks 16 x 32 in two
    sub-tiles: the dk/dv program accumulates over 8 x 4 query blocks)."""
    monkeypatch.setattr(fa, "_SUB_TILE", SUB)
    q, k, v, do = _operands(8, d=256)
    assert q.shape == (1, 16, 64, 256) and k.shape == (1, 2, 64, 256)

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, True, 16, 32, 0, True)
    np.testing.assert_allclose(attend(q, k, v), _reference(q, k, v, None),
                               atol=3e-6)
    for g, e, x in zip(_grads(attend, q, k, v, do),
                       _grads(lambda *x: _reference(*x, None), q, k, v, do),
                       (q, k, v)):
        assert g.shape == x.shape
        np.testing.assert_allclose(g, e, atol=1e-4)


def test_the_256_lane_programs_of_8192_keys_fit_the_vmem_reckoning(
        monkeypatch):
    """The three kernels as the chip runs them at the cell's own shape (16
    heads of 256 over 2 key-value heads, 8,192 keys, bf16: traced, not run):
    one row a program on the split layout at the default blocks, 512 x 1,024,
    and the padded estimate of each under the budget (the dk/dv program's two
    (1,024 x 256) f32 accumulators with it); Mosaic's own verdict is
    ``tests/test_topology_aot.py``'s."""
    from autodist_tpu.observability import recorder
    monkeypatch.setattr(fa, "_pallas_interpret", lambda *_: False)
    q = jax.ShapeDtypeStruct((1, 16, 8192, 256), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, 8192, 256), jnp.bfloat16)
    jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, kv, kv)
    said = {e["detail"].split()[0]: e["detail"] for e in recorder.events()
            if e["kind"] == "flash" and "bfloat16[16,8192,256]" in e["detail"]}
    assert set(said) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    for kernel, detail in said.items():
        assert "split layout, 1 heads a block of 256 lanes, blocks 512 x " \
            "1024, G = 1 (batch, head) rows a program" in detail, detail
        assert "8 query heads read one key-value head, 2 key-value heads " \
            "in HBM" in detail
        vmem = int(detail.split(" bytes of VMEM")[0].split()[-1])
        assert 2 * 2 ** 20 < vmem <= fa._VMEM_BUDGET, (kernel, vmem)
    # 36 of a row's 16 x 8 = 128 sub-tiles hold a seen score... of 136.
    assert "136 of 256 sub-tiles of 512 x 512 visited" in said["flash_fwd"]
