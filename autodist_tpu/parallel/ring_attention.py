"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

NEW capability vs the reference (no SP anywhere, SURVEY.md §5 long-context):
attention over sequences sharded across the ``seq`` mesh axis.

* :func:`ring_attention` — blockwise attention with the K/V shards rotating
  around the ring via ``lax.ppermute`` (the Ring Attention recipe: each hop
  overlaps with the block computation). Per-hop compute is the fused Pallas
  flash kernel on TPU (dense jnp elsewhere), hops merge through a
  logsumexp combine, and a custom VJP **re-rotates K/V during the backward**
  with the fused FlashAttention-2 block kernels against the saved global
  logsumexp — memory stays O(seq/P) per device in BOTH passes (reverse-mode
  through the naive loop would checkpoint every hop's K/V block and score
  transient, i.e. dense-backward memory).
* :func:`ulysses_attention` — DeepSpeed-Ulysses style: ``all_to_all`` swaps
  the sequence sharding for a head sharding, runs fused local attention, and
  swaps back. Fewer, larger collectives; needs heads % P == 0.

Both are designed to be called INSIDE an SPMD context (shard_map over the
``seq`` axis); :func:`make_ring_attn_fn` / :func:`make_ulysses_attn_fn`
wrap them in their own ``shard_map`` so a model's ``attn_fn`` hook can use
them directly under the GSPMD jit path.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.ops.flash_attention import (_dense_reference, _use_pallas,
                                              block_attn_bwd, block_attn_fwd,
                                              combine_blocks)
from autodist_tpu.ops.flash_attention import flash_attention as _flash_attn

_NEG_INF = -1e30


def _ring_fwd_impl(q, k, v, my_idx, axis_name, causal, p_size, interpret):
    """Forward ring: rotate K/V, merge finalized (o, lse) partials.

    Returns (o q.dtype, lse f32 (..., sq, 1)).
    """
    sq, sk = q.shape[-2], k.shape[-2]
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    # Accumulators are derived from q (zeroed) so their varying-manner type
    # matches the loop body's outputs whatever axes enclose this call
    # (shard_map VMA typing: a fori_loop carry must keep one type).
    qz = q.astype(jnp.float32) * 0.0
    o = qz
    lse = qz[..., :1] + _NEG_INF

    def step(t, carry):
        o, lse, kt, vt = carry
        # After t hops this device holds the K/V block of device my_idx - t;
        # global positions decide causal visibility.
        src = (my_idx - t) % p_size
        ob, lb = block_attn_fwd(q, kt, vt, causal, my_idx * sq, src * sk,
                                interpret=interpret)
        o, lse = combine_blocks(o, lse, ob, lb)
        kt, vt = jax.tree_util.tree_map(
            lambda x: lax.ppermute(x, axis_name, perm), (kt, vt))
        return o, lse, kt, vt

    o, lse, _, _ = lax.fori_loop(0, p_size, step, (o, lse, k, v))
    return o.astype(q.dtype), lse


def _ring_bwd_impl(q, k, v, o, lse, my_idx, do, axis_name, causal, p_size,
                   interpret):
    """Backward ring: K/V make one more full rotation, each hop running the
    fused block backward against the global lse; dk/dv accumulators travel
    WITH their block so after p_size hops they arrive back home."""
    sq, sk = q.shape[-2], k.shape[-2]
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
        .sum(-1, keepdims=True)
    dq = q.astype(jnp.float32) * 0.0
    dk0 = k.astype(jnp.float32) * 0.0
    dv0 = v.astype(jnp.float32) * 0.0

    def step(t, carry):
        dq, kt, vt, dkt, dvt = carry
        src = (my_idx - t) % p_size
        dqb, dkb, dvb = block_attn_bwd(q, kt, vt, do, lse, delta, causal,
                                       my_idx * sq, src * sk,
                                       interpret=interpret)
        dq = dq + dqb
        dkt = dkt + dkb
        dvt = dvt + dvb
        kt, vt, dkt, dvt = jax.tree_util.tree_map(
            lambda x: lax.ppermute(x, axis_name, perm), (kt, vt, dkt, dvt))
        return dq, kt, vt, dkt, dvt

    dq, _, _, dk, dv = lax.fori_loop(0, p_size, step, (dq, k, v, dk0, dv0))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.lru_cache(maxsize=None)
def _ring_vjp(axis_name, causal, p_size, interpret):
    """The custom-VJP ring core for one (axis, causal, size) config.

    ``my_idx`` is a traced int argument (axis_index / seq-sharded iota) —
    its cotangent is None."""

    @jax.custom_vjp
    def ring(q, k, v, my_idx):
        o, _ = _ring_fwd_impl(q, k, v, my_idx, axis_name, causal, p_size,
                              interpret)
        return o

    def fwd(q, k, v, my_idx):
        o, lse = _ring_fwd_impl(q, k, v, my_idx, axis_name, causal, p_size,
                                interpret)
        return o, (q, k, v, o, lse, my_idx)

    def bwd(res, do):
        q, k, v, o, lse, my_idx = res
        dq, dk, dv = _ring_bwd_impl(q, k, v, o, lse, my_idx, do, axis_name,
                                    causal, p_size, interpret)
        return dq, dk, dv, None

    ring.defvjp(fwd, bwd)
    return ring


def ring_attention(q, k, v, axis_name=const.MESH_AXIS_SEQ, causal=False,
                   p_size=None, my_idx=None, interpret=False):
    """Ring attention inside an SPMD context.

    q/k/v: (batch, heads, seq_local, head_dim), sequence sharded over
    ``axis_name``. Returns (batch, heads, seq_local, head_dim) in q.dtype.
    ``p_size``/``my_idx`` may be supplied by the caller (the shard_map
    wrapper does: ``lax.axis_index`` cannot lower inside *nested*
    partial-manual regions, so the index rides in as a seq-sharded iota).
    """
    if p_size is None:
        p_size = lax.axis_size(axis_name)
    if my_idx is None:
        my_idx = lax.axis_index(axis_name)
    return _ring_vjp(axis_name, bool(causal), int(p_size),
                     bool(interpret))(q, k, v, jnp.asarray(my_idx, jnp.int32))


def ulysses_attention(q, k, v, axis_name=const.MESH_AXIS_SEQ, causal=False,
                      inner_attn=None, p_size=None, my_idx=None):
    """Ulysses SP: all_to_all heads<->sequence, fused local attention, swap back.

    q/k/v: (batch, heads, seq_local, head_dim) with heads % axis_size == 0.
    """
    if p_size is None:
        p_size = lax.axis_size(axis_name)
    if q.shape[1] % p_size != 0:
        raise ValueError(f"ulysses needs heads ({q.shape[1]}) divisible by "
                         f"seq-axis size ({p_size})")

    def a2a_fwd(x):  # (b, h, s_local, d) -> (b, h/P, s_global, d)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def a2a_bwd(x):  # inverse
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    q, k, v = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
    if inner_attn is not None:
        o = inner_attn(q, k, v, causal)
    else:
        # Local attention over the full gathered sequence: the fused Pallas
        # kernels on TPU (custom-VJP flash path), dense softmax elsewhere.
        s = q.shape[-2]
        bq, bk = min(512, s), min(1024, s)
        if _use_pallas(q, k, bq, bk, False):
            o = _flash_attn(q, k, v, causal, bq, bk)
        else:
            o = _dense_reference(q, k, v, causal)
    return a2a_bwd(o)


def _wrap_sharded(inner, mesh, causal, data_axis, seq_axis):
    """shard_map wrapper: q/k/v (b, h, s, d) sequence-sharded over ``seq``;
    runs ``inner`` per shard.

    Manual over ``seq`` ONLY (partial-auto): the batch dimension stays under
    GSPMD, so the same attention hook works at top level (pure-jit path,
    where GSPMD splits the batch over ``data``) and nested inside the
    runner's explicit manual-over-data region (where the batch arrives
    pre-split).  When nested, the *context* abstract mesh must be passed
    instead of the concrete one (jax requires the meshes to match)."""
    spec = P(None, None, seq_axis, None)
    size = dict(mesh.shape)[seq_axis]
    iota = jnp.arange(size, dtype=jnp.int32)  # P(seq) -> local (1,) = my index

    def sharded(q, k, v):
        am = jax.sharding.get_abstract_mesh()
        if am is not None and seq_axis in getattr(am, "manual_axes", ()):
            # Already inside a manual-over-seq region (e.g. the pipeline's
            # shard_map went manual over {pipe, seq} so SP composes without
            # nesting — Shardy requires manual axes before free axes in AD
            # residual shardings, which nested seq-inside-pipe violates).
            # q/k/v arrive sequence-local; run the collective body directly.
            return inner(q, k, v, axis_name=seq_axis, causal=causal,
                         p_size=size, my_idx=lax.axis_index(seq_axis))
        use = am if (am is not None and am.shape and
                     dict(am.shape) == dict(mesh.shape)) else mesh
        f = jax.shard_map(
            lambda ql, kl, vl, il: inner(ql, kl, vl, axis_name=seq_axis,
                                         causal=causal, p_size=size,
                                         my_idx=il[0]),
            mesh=use, in_specs=(spec, spec, spec, P(seq_axis)),
            out_specs=spec, axis_names={seq_axis})
        return f(q, k, v, iota)

    return sharded


def make_ring_attn_fn(mesh, causal=False, data_axis=const.MESH_AXIS_DATA,
                      seq_axis=const.MESH_AXIS_SEQ):
    """An ``attn_fn(q, k, v, mask)`` hook (models.layers.mha) running ring
    attention over the mesh's seq axis. ``mask`` is ignored — causality is
    positional (set ``causal=``)."""
    sharded = _wrap_sharded(ring_attention, mesh, causal, data_axis, seq_axis)
    return lambda q, k, v, mask=None: sharded(q, k, v)


def make_ulysses_attn_fn(mesh, causal=False, data_axis=const.MESH_AXIS_DATA,
                         seq_axis=const.MESH_AXIS_SEQ):
    sharded = _wrap_sharded(ulysses_attention, mesh, causal, data_axis, seq_axis)
    return lambda q, k, v, mask=None: sharded(q, k, v)
