"""Flash attention: fused blockwise attention as Pallas TPU kernels.

The per-chip hot op for every transformer in the zoo, and the per-block
compute of ring attention (``parallel/ring_attention.py``). K/V stream
through VMEM one block per grid step (3-D grid; online-softmax accumulators
live in VMEM scratch), so neither the (seq x seq) score matrix nor the full
K/V sequence is VMEM-resident — the long-context regime stays within the
~16MB/core budget. Fully-masked causal blocks skip their MXU work.

Forward emits per-row logsumexp next to the output; backward is the fused
FlashAttention-2 pair (a dq kernel accumulating over K blocks and a dk/dv
kernel accumulating over Q blocks) recomputing p = exp(s - lse) blockwise —
the O(s^2) score transient of the old dense-recompute VJP never
materializes. Block position offsets ride in as scalar-prefetch operands,
so they may be traced values (ring attention's rotating K/V offsets).

float32 lives in the VMEM accumulators (o, dq, dk, dv) and in the softmax
statistics (scores, running max and sum, lse, delta, p, ds) and nowhere else.
All nine MXU products take operands of the inputs' dtype — the f32 ``p`` and
``ds`` are cast down to it, not ``q``/``k``/``v``/``do`` up — and accumulate
in f32; o and the three gradients leave the kernels in the inputs' dtype,
rounded once from the accumulator. Ring attention's per-hop partials are
summed across hops, so ``block_attn_fwd``/``block_attn_bwd`` ask for f32
results. With f32 inputs every one of these casts is the identity. At the
default precision Mosaic rounds an f32 MXU operand to bf16 itself, so with
bf16 inputs the cast changes no bit of the result (v5e, PERF.md PR 24); it
makes the nine products single-pass whatever ``jax_default_matmul_precision``
the caller traces under, where f32 operands would follow it (1.4-2.5x the
kernel time at ``highest``).

Off TPU the dense jnp path runs instead (CPU tests use ``interpret=True``
to exercise the kernels in the Pallas interpreter); every trace logs once,
at info, which path it took and why.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.utils import logging

_NEG_INF = -1e30
_logged_paths = set()


def _log_path(path, why):
    """One info line per distinct (path, reason): a run that meant to
    compile the kernels and got the dense reference says so."""
    if (path, why) not in _logged_paths:
        _logged_paths.add((path, why))
        logging.info("flash_attention: %s path (%s)", path, why)


def _pallas_interpret(interpret, dtype):
    """Resolve the ``interpret`` argument at trace time: the flag to hand
    ``pallas_call``, or None when this trace takes the dense reference
    (``interpret=None`` off TPU). ``dtype`` is the inputs': the kernels'
    log line names it, since it is what all nine MXU products multiply."""
    operands = f"{jnp.dtype(dtype).name} MXU operands, f32 accumulators"
    if interpret is not None:
        _log_path("interpreted pallas" if interpret else "pallas",
                  f"interpret={bool(interpret)} requested; {operands}")
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        _log_path("pallas", f"backend is tpu; {operands}")
        return False
    _log_path("dense", f"backend is {backend}; the kernels compile for tpu")
    return None


def _sds(shape, dtype, *arrays):
    """ShapeDtypeStruct whose varying-manner matches the inputs' union.

    Inside a shard_map manual region (ring attention's per-hop kernels)
    pallas_call outputs must declare their vma explicitly."""
    vma = frozenset()
    for a in arrays:
        vma |= getattr(jax.typeof(a), "vma", frozenset()) or frozenset()
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def causal_bias(sq, sk, q_offset=0, k_offset=0):
    """Additive causal bias (0 where visible, -inf where masked) for a
    (sq, sk) score block whose rows/cols sit at the given global offsets
    (offsets may be traced scalars). The single definition of causal
    masking shared by the dense reference, the Pallas kernels, and the
    ring/Ulysses SP paths."""
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return jnp.where(q_pos >= k_pos, 0.0, _NEG_INF)


# ---------------------------------------------------------------------------
# dense reference (CPU fallback and numerics oracle)


def _dense_fwd(q, k, v, causal, q_offset=0, k_offset=0):
    """Returns (o f32, lse f32 (..., sq, 1))."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = s + causal_bias(q.shape[2], k.shape[2], q_offset, k_offset)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    lse = m + jnp.log(l)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) / l
    return o, lse


def _dense_reference(q, k, v, causal, q_offset=0):
    o, _ = _dense_fwd(q, k, v, causal, q_offset)
    return o.astype(q.dtype)


def _dense_bwd(q, k, v, do, lse, delta, causal, q_offset=0, k_offset=0):
    """FA2-style dense backward from the saved lse: p = exp(s - lse).

    delta = rowsum(do * o); returns (dq, dk, dv) in f32.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = s + causal_bias(q.shape[2], k.shape[2], q_offset, k_offset)
    p = jnp.exp(s - lse)                       # (..., sq, sk); masked -> 0
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v.astype(jnp.float32))
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# forward kernel


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l, *,
                block_q, block_k, causal, skip_blocks):
    """Grid (batch*heads, q-blocks, k-blocks): k innermost, accumulators in
    VMEM scratch carried across the k dimension."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    num_kb = pl.num_programs(2)
    d = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(d)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, _NEG_INF)
        l[:] = jnp.zeros_like(l)

    q_start = offs_ref[0] + iq * block_q
    k_start = offs_ref[1] + ik * block_k
    # A causal block is fully masked iff its largest q position is still
    # left of its smallest k position — skip the MXU work entirely.
    # ``skip_blocks`` is off in interpret mode (the Pallas interpreter's
    # state discharge loses multi-scratch writes under a skipped
    # runtime-conditional); the p-masking below keeps skipped-block
    # contributions exactly zero either way.
    visible = jnp.logical_or(not (causal and skip_blocks),
                             q_start + block_q - 1 >= k_start)

    @pl.when(visible)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = s + causal_bias(block_q, block_k, q_start, k_start)
        m_prev = m[:]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # Masked entries contribute EXACTLY zero (not exp(-1e30 - m)): in a
        # fully-masked block m_new stays at the sentinel and s - m_new = 0.
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        l[:] = l[:] * alpha + p.sum(-1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m[:] = m_new

    @pl.when(ik == num_kb - 1)
    def _finalize():
        # 1e-30, NOT 1e-38: f32 subnormals flush to zero on TPU (and in the
        # interpret pipeline), and max(0, ftz(1e-38)) / 0 is how a guard
        # epsilon turns into NaN for rows that saw no visible block.
        o_ref[0] = (acc[:] / jnp.maximum(l[:], 1e-30)).astype(o_ref.dtype)
        # Rows that saw no visible block keep the finite sentinel (not -inf:
        # downstream combines subtract lse values and -inf - -inf = nan).
        lse_ref[0] = jnp.where(l[:] > 0, m[:] + jnp.log(jnp.maximum(l[:], 1e-30)),
                               _NEG_INF).astype(lse_ref.dtype)


def _flash_fwd(q, k, v, causal, block_q, block_k, q_offset, k_offset,
               interpret, out_dtype=None):
    """Fused forward. Returns (o (b,h,sq,d) out_dtype, lse f32 (b,h,sq,1)).

    ``q_offset``/``k_offset`` may be traced scalars (scalar-prefetch)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, \
        f"seq ({sq},{sk}) must divide blocks ({block_q},{block_k})"
    if isinstance(q_offset, int) and causal:
        assert q_offset % block_q == 0, \
            f"q_offset {q_offset} must be a multiple of block_q {block_q}"
    out_dtype = out_dtype or q.dtype
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)
    grid = (b * h, sq // block_q, sk // block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda ibh, iq, ik, offs: (ibh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda ibh, iq, ik, offs: (ibh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda ibh, iq, ik, offs: (ibh, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda ibh, iq, ik, offs: (ibh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda ibh, iq, ik, offs: (ibh, iq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, skip_blocks=not interpret),
        grid_spec=grid_spec,
        out_shape=[_sds((b * h, sq, d), out_dtype, qr, kr, vr, offs),
                   _sds((b * h, sq, 1), jnp.float32, qr, kr, vr, offs)],
        # batch/q-block programs are independent; only the k dimension
        # carries the accumulator.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(offs, qr, kr, vr)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq, 1)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2: dq over K blocks, dk/dv over Q blocks)


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, block_q, block_k, causal, skip_blocks):
    ik = pl.program_id(2)
    num_kb = pl.num_programs(2)
    d = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(d)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = offs_ref[0] + pl.program_id(1) * block_q
    k_start = offs_ref[1] + ik * block_k
    visible = jnp.logical_or(not (causal and skip_blocks),
                             q_start + block_q - 1 >= k_start)

    @pl.when(visible)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = s + causal_bias(block_q, block_k, q_start, k_start)
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - lse_ref[0]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                    causal, skip_blocks):
    iq = pl.program_id(2)
    num_qb = pl.num_programs(2)
    d = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(d)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = offs_ref[0] + iq * block_q
    k_start = offs_ref[1] + pl.program_id(1) * block_k
    visible = jnp.logical_or(not (causal and skip_blocks),
                             q_start + block_q - 1 >= k_start)

    @pl.when(visible)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = s + causal_bias(block_q, block_k, q_start, k_start)
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - lse_ref[0]), 0.0)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # p^T do
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # ds^T q

    @pl.when(iq == num_qb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, do, lse, delta, causal, block_q, block_k, q_offset,
               k_offset, interpret, out_dtype=None):
    """Fused backward. Returns (dq, dk, dv) in out_dtype: the f32
    accumulators are rounded once, by the kernels' last step."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    out_dtype = out_dtype or q.dtype
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    dor = do.reshape(b * h, sq, d)
    lser = lse.reshape(b * h, sq, 1)
    deltar = delta.reshape(b * h, sq, 1)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)

    qspec = pl.BlockSpec((1, block_q, d), lambda ibh, i, j, offs: (ibh, i, 0))
    qspec_inner = pl.BlockSpec((1, block_q, d),
                               lambda ibh, i, j, offs: (ibh, j, 0))
    rowspec = pl.BlockSpec((1, block_q, 1), lambda ibh, i, j, offs: (ibh, i, 0))
    rowspec_inner = pl.BlockSpec((1, block_q, 1),
                                 lambda ibh, i, j, offs: (ibh, j, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda ibh, i, j, offs: (ibh, j, 0))
    kspec_outer = pl.BlockSpec((1, block_k, d),
                               lambda ibh, i, j, offs: (ibh, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, skip_blocks=not interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, sq // block_q, sk // block_k),
            in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=_sds((b * h, sq, d), out_dtype, qr, kr, vr, dor, offs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(offs, qr, kr, vr, dor, lser, deltar)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, skip_blocks=not interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, sk // block_k, sq // block_q),
            in_specs=[qspec_inner, kspec_outer, kspec_outer, qspec_inner,
                      rowspec_inner, rowspec_inner],
            out_specs=[kspec_outer, kspec_outer],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
        ),
        out_shape=[_sds((b * h, sk, d), out_dtype, qr, kr, vr, dor, offs),
                   _sds((b * h, sk, d), out_dtype, qr, kr, vr, dor, offs)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(offs, qr, kr, vr, dor, lser, deltar)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


# ---------------------------------------------------------------------------
# block-attention helpers (ring attention's per-hop compute)


def _use_pallas(q, k, block_q, block_k, interpret):
    if interpret:
        return True
    sq, sk = q.shape[2], k.shape[2]
    if sq % min(block_q, sq) or sk % min(block_k, sk):
        _log_path("dense", f"block attention: seq ({sq}, {sk}) does not "
                           f"divide blocks ({block_q}, {block_k})")
        return False
    return _pallas_interpret(None, q.dtype) is not None


def block_attn_fwd(q, k, v, causal, q_offset, k_offset, block_q=512,
                   block_k=1024, interpret=False):
    """One attention block: (o f32, lse f32 (..., sq, 1)).

    Offsets may be traced scalars (ring hop positions). Rows with no
    visible key get o = 0 and lse = -1e30 (finite sentinel), which the
    logsumexp-combine treats as an empty partial."""
    if _use_pallas(q, k, block_q, block_k, interpret):
        return _flash_fwd(q, k, v, causal, block_q, block_k, q_offset,
                          k_offset, interpret, out_dtype=jnp.float32)
    o, lse = _dense_fwd(q, k, v, causal, q_offset, k_offset)
    if causal:
        # Match the kernel's fully-masked-row convention: the dense softmax
        # spreads weight uniformly over masked keys instead; zero it.
        empty = lse <= _NEG_INF / 2
        o = jnp.where(empty, 0.0, o)
        lse = jnp.where(empty, _NEG_INF, lse)
    return o, lse


def block_attn_bwd(q, k, v, do, lse, delta, causal, q_offset, k_offset,
                   block_q=512, block_k=1024, interpret=False):
    """Fused per-block backward vs the GLOBAL lse (FA2 cross-block form):
    p = exp(s - lse) are the true softmax probabilities even when this block
    is one hop of a longer ring. Returns (dq, dk, dv) f32."""
    if _use_pallas(q, k, block_q, block_k, interpret):
        return _flash_bwd(q, k, v, do, lse, delta, causal, block_q, block_k,
                          q_offset, k_offset, interpret,
                          out_dtype=jnp.float32)
    return _dense_bwd(q, k, v, do, lse, delta, causal, q_offset, k_offset)


def combine_blocks(o_a, lse_a, o_b, lse_b):
    """Merge two finalized attention partials (o, lse) -> (o, lse).

    Standard logsumexp reweighting; empty partials (lse = -1e30) get weight
    ~0 without any nan path (sentinels are finite)."""
    lse = jnp.logaddexp(lse_a, lse_b)
    return (o_a * jnp.exp(lse_a - lse) + o_b * jnp.exp(lse_b - lse)), lse


# ---------------------------------------------------------------------------
# public fused attention


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, block_q=512, block_k=1024,
                    q_offset=0, interpret=None):
    """softmax(qk^T/sqrt(d) [+ causal mask]) v, fused fwd AND bwd.

    q/k/v: (batch, heads, seq, head_dim). ``q_offset`` shifts q's global
    positions for causal masking (used when q is a shard of a longer
    sequence); it must be a multiple of ``block_q``. ``interpret=None``
    picks the Pallas kernels on TPU and the dense path elsewhere.
    """
    interpret = _pallas_interpret(interpret, q.dtype)
    if interpret is None:
        return _dense_reference(q, k, v, causal, q_offset)
    o, _ = _flash_fwd(q, k, v, causal, block_q, block_k, q_offset, 0,
                      interpret)
    return o


def _fwd_rule(q, k, v, causal, block_q, block_k, q_offset, interpret):
    interpret = _pallas_interpret(interpret, q.dtype)
    if interpret is None:
        o, lse = _dense_fwd(q, k, v, causal, q_offset)
        return o.astype(q.dtype), (q, k, v, o.astype(q.dtype), lse)
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k, q_offset, 0,
                        interpret)
    return o, (q, k, v, o, lse)


def _bwd_rule(causal, block_q, block_k, q_offset, interpret, res, do):
    q, k, v, o, lse = res
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
        .sum(-1, keepdims=True)
    # interpret semantics match the forward: None = auto (Pallas on TPU,
    # dense elsewhere); False = native Pallas kernels; True = interpreted
    # Pallas. An explicit False must NOT mean "dense" — that would hand the
    # default TPU transformer path the O(s^2) dense backward.
    interpret = _pallas_interpret(interpret, q.dtype)
    if interpret is None:
        dq, dk, dv = _dense_bwd(q, k, v, do, lse, delta, causal, q_offset)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
    return _flash_bwd(q, k, v, do, lse, delta, causal, block_q, block_k,
                      q_offset, 0, interpret)


flash_attention.defvjp(_fwd_rule, _bwd_rule)


def _under_full_manual(fn, q, k, v):
    """``fn(q, k, v)`` where a Mosaic kernel can lower on the active mesh.

    jax refuses to partition a ``pallas_call`` automatically ("Mosaic
    kernels cannot be automatically partitioned"): on a mesh of several
    devices the kernel must sit in a region that is manual over every mesh
    axis.  The Runner's explicit path over ``{data}`` alone already is one;
    on the GSPMD path, or with further axes left automatic, the call goes
    under a ``shard_map`` over the axes still free — batch split over
    ``data``, heads over ``model``.  Any other axis of size > 1 would run
    the whole kernel on each of its devices, so it raises instead.
    """
    from autodist_tpu.parallel import context as parallel_ctx
    ctx = parallel_ctx.current()
    mesh = ctx.mesh if ctx is not None else None
    if mesh is None or mesh.size == 1:
        return fn(q, k, v)
    am = jax.sharding.get_abstract_mesh()
    free = [a for a in mesh.axis_names if a not in am.manual_axes]
    if not free:
        return fn(q, k, v)
    sizes = dict(mesh.shape)
    dim_of = {const.MESH_AXIS_DATA: 0, const.MESH_AXIS_MODEL: 1}
    spec = [None] * q.ndim
    for a in free:
        if sizes[a] == 1:
            continue
        dim = dim_of.get(a)
        if dim is None or q.shape[dim] % sizes[a]:
            raise NotImplementedError(
                f"flash attention on mesh {sizes}: axis {a!r} cannot split "
                f"q {q.shape} (batch over 'data', heads over 'model'), and "
                f"leaving it automatic would run the whole kernel on each "
                f"of its {sizes[a]} devices; pass attn_fn= to the model or "
                f"pick a strategy without that axis")
        spec[dim] = a
    spec = P(*spec)
    _log_path("pallas", f"under shard_map over {free} of mesh {sizes}")
    # Nested in a manual region, jax wants the context's own mesh.
    return jax.shard_map(fn, mesh=am if dict(am.shape) == sizes else mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         axis_names=set(free), check_vma=False)(q, k, v)


def make_flash_attn_fn(causal=False, block_q=512, block_k=1024):
    """An ``attn_fn(q, k, v, mask)`` hook (models.layers.mha signature).

    Uses the Pallas kernels on TPU when the sequence divides the block
    size; anything else — including an explicit boolean ``mask``, which the
    fused kernel does not consume — takes the dense reference so masking
    semantics are never dropped, and logs that it did.
    """
    from autodist_tpu.models import layers as L

    def attn_fn(q, k, v, mask=None):
        if mask is not None:
            _log_path("dense", "an explicit mask was passed")
            return L.dot_product_attention(q, k, v, mask)
        s = q.shape[2]
        bq, bk = min(block_q, s), min(block_k, s)
        if s % bq != 0 or s % bk != 0:
            _log_path("dense", f"seq {s} does not divide blocks "
                               f"({block_q}, {block_k})")
            return _dense_reference(q, k, v, causal)
        if _pallas_interpret(None, q.dtype) is None:
            return _dense_reference(q, k, v, causal)
        return _under_full_manual(
            lambda ql, kl, vl: flash_attention(ql, kl, vl, causal, bq, bk,
                                               0, False), q, k, v)
    return attn_fn
