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

A program of the grid handles ``G`` (batch, head) rows: where a row's score
tile is small (128 x 128 at s = 128) most of a one-row program is what a
program costs whatever it computes, so rows share a program until its tiles
are as large as a long-sequence program's or VMEM is full
(``_rows_per_program``; the operands' shape alone decides, and from s = 1,024
``G`` is 1 and the program is the one-row program unchanged). Inside, a loop
on the device takes as many rows a step as fit the vector registers, through
the same arithmetic with a leading rows axis (``_for_rows``). A row's results
do not depend on ``G``: they are bit for bit those of one row a program.

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


# The score tile of a long-sequence program (the default blocks): a program
# of short rows takes rows until its tiles add up to this.
_MAX_TILE = 512 * 1024
# Mosaic's scoped VMEM limit is 16 MiB a kernel on the v5e, and the padded
# estimate below leaves out what the compiler keeps for a row's own values
# (the s x s tile and its copies).
_VMEM_BUDGET = 12 * 2 ** 20
# The score tiles one step of a program's loop over its rows may hold: the
# vector registers (64 of 1,024 f32). A row is a chain (product, softmax,
# product) in which each link waits for the one before; where several rows'
# tiles fit the registers, a step takes them together and the compiler fills
# one row's waits with its neighbours. On the v5e at (3072, 128, 64), G = 16:
# 1.71 / 1.15 / 1.46 ms a call (fwd / dq / dkv) one row a step, 0.94 / 1.11 /
# 1.24 at four; at (768, 512, 64), where one row's tile is four times the
# registers, two rows a step are no faster than one (PERF.md, PR 26).
_STEP_TILE = 64 * 1024
_announced = set()


def _padded_bytes(shape, dtype):
    """VMEM bytes of a block of ``shape``: the last dimension occupies whole
    128-lane rows (a width of 64 or of 1 as much as one of 128), the one
    before it whole tiles of 8 sublanes of 32 bits."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    *lead, rows, lanes = shape
    return (math.prod(lead) * -(-rows // sublanes) * sublanes
            * -(-lanes // 128) * 128 * itemsize)


def _rows_per_program(rows, block_q, block_k, blocks, scratch):
    """``(G, vmem_bytes)``: how many of the ``rows`` (batch x heads) one
    program handles, and the VMEM those ``G`` rows occupy.

    ``blocks`` are one row's pipelined in/out blocks (double-buffered),
    ``scratch`` its accumulators and statistics, each ``(shape, dtype)``.
    ``G`` is the largest divisor of ``rows`` whose score tiles stay within
    ``_MAX_TILE`` and whose padded VMEM stays within ``_VMEM_BUDGET``; 1
    where not even one row does (the blocks are the caller's choice)."""
    vmem_a_row = (2 * sum(_padded_bytes(*b) for b in blocks)
                  + sum(_padded_bytes(*b) for b in scratch))
    most = min(_MAX_TILE // (block_q * block_k), _VMEM_BUDGET // vmem_a_row)
    g = max((g for g in range(1, most + 1) if rows % g == 0), default=1)
    return g, g * vmem_a_row


def _announce(kernel, operand, sk, block_q, block_k, rows, vmem_bytes):
    """One info line a distinct kernel and shape, and with telemetry on the
    gauge ``flash.rows_per_program`` and a ``flash`` event: which program
    the rule above made of this call, read at trace time."""
    bh, sq, d = operand.shape
    programs = bh // rows * (sq // block_q) * (sk // block_k)
    detail = (f"{kernel} {jnp.dtype(operand.dtype).name}[{bh},{sq},{d}] "
              f"over {sk} keys: blocks {block_q} x {block_k}, G = {rows} "
              f"(batch, head) rows a program, {programs} programs a call, "
              f"{vmem_bytes} bytes of VMEM by the padded estimate")
    _log_path("pallas", detail)
    from autodist_tpu import observability
    if not observability.enabled():
        return
    observability.registry().gauge("flash.rows_per_program").set(rows)
    if detail not in _announced:
        _announced.add(detail)
        observability.record_event("flash", detail)


def _for_rows(rows, tile, body):
    """``body(at)`` over a program's rows, ``tile`` a row's score tile and
    ``at`` what indexes the rows of one step in a block: the one row of a
    one-row program, which has no loop and two-dimensional arithmetic; else
    a loop on the device whose step takes as many rows as ``_STEP_TILE``
    allows, one as an index, several as a slice, so that a kernel's code does
    not grow with ``rows``."""
    if rows == 1:
        body(0)
        return
    a_step = max((n for n in range(1, rows + 1)
                  if rows % n == 0 and n * tile <= _STEP_TILE), default=1)
    if a_step == rows:
        body(slice(None))
        return

    def step(i, carry):
        body(i if a_step == 1
             else pl.ds(pl.multiple_of(i * a_step, a_step), a_step))
        return carry
    jax.lax.fori_loop(0, rows // a_step, step, 0)


def _dot(a, b, contract_a, contract_b):
    """``a . b`` in f32 over the given axes, counted from the end; the
    leading axis of rank-3 operands is the rows of a loop step."""
    rows = tuple(range(a.ndim - 2))
    return jax.lax.dot_general(
        a, b, (((a.ndim + contract_a,), (b.ndim + contract_b,)),
               (rows, rows)), preferred_element_type=jnp.float32)


def _row_of(scratch, at):
    """``at`` for a program's scratch, which has no rows dimension in a
    program of one row (``_scratch``): the long-sequence cells' kernels
    compile to the same Mosaic module whether or not short rows group."""
    return slice(None) if len(scratch.shape) == 2 else at


def _all_rows(rows):
    """The index of all of a program's rows in a block, for a value read from
    its scratch: the one row of a one-row program, whose scratch has no rows
    dimension."""
    return 0 if rows == 1 else slice(None)


def _scratch(g, shape):
    return pltpu.VMEM(shape if g == 1 else (g,) + shape, jnp.float32)


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
    """Grid (batch*heads / G, q-blocks, k-blocks): k innermost, accumulators
    in VMEM scratch carried across the k dimension, each of a program's G
    rows with its own."""
    rows = q_ref.shape[0]
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
        def _rows(at):
            row = _row_of(acc, at)
            q = q_ref[at]
            k = k_ref[at]
            v = v_ref[at]
            s = _dot(q, k, -1, -1) * scale
            if causal:
                s = s + causal_bias(block_q, block_k, q_start, k_start)
            m_prev = m[row]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # Masked entries contribute EXACTLY zero (not exp(-1e30 - m)): in
            # a fully-masked block m_new stays at the sentinel and
            # s - m_new = 0.
            p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - m_new), 0.0)
            l[row] = l[row] * alpha + p.sum(-1, keepdims=True)
            acc[row] = acc[row] * alpha + _dot(p.astype(v.dtype), v, -1, -2)
            m[row] = m_new
        _for_rows(rows, block_q * block_k, _rows)

    @pl.when(ik == num_kb - 1)
    def _finalize():
        every = _all_rows(rows)
        # 1e-30, NOT 1e-38: f32 subnormals flush to zero on TPU (and in the
        # interpret pipeline), and max(0, ftz(1e-38)) / 0 is how a guard
        # epsilon turns into NaN for rows that saw no visible block.
        o_ref[every] = (acc[:] / jnp.maximum(l[:], 1e-30)).astype(o_ref.dtype)
        # Rows that saw no visible block keep the finite sentinel (not -inf:
        # downstream combines subtract lse values and -inf - -inf = nan).
        lse_ref[every] = jnp.where(
            l[:] > 0, m[:] + jnp.log(jnp.maximum(l[:], 1e-30)),
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
    f32 = jnp.float32
    scratch = [((block_q, d), f32), ((block_q, 1), f32), ((block_q, 1), f32)]
    g, vmem = _rows_per_program(
        b * h, block_q, block_k,
        [((block_q, d), q.dtype), ((block_k, d), k.dtype),
         ((block_k, d), v.dtype), ((block_q, d), out_dtype),
         ((block_q, 1), f32)], scratch)
    _announce("flash_fwd", qr, sk, block_q, block_k, g, vmem)
    grid = (b * h // g, sq // block_q, sk // block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((g, block_q, d), lambda ibh, iq, ik, offs: (ibh, iq, 0)),
            pl.BlockSpec((g, block_k, d), lambda ibh, iq, ik, offs: (ibh, ik, 0)),
            pl.BlockSpec((g, block_k, d), lambda ibh, iq, ik, offs: (ibh, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, block_q, d), lambda ibh, iq, ik, offs: (ibh, iq, 0)),
            pl.BlockSpec((g, block_q, 1), lambda ibh, iq, ik, offs: (ibh, iq, 0)),
        ],
        scratch_shapes=[_scratch(g, shape) for shape, _ in scratch],
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
    rows = q_ref.shape[0]
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    num_kb = pl.num_programs(2)
    d = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(d)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = offs_ref[0] + iq * block_q
    k_start = offs_ref[1] + ik * block_k
    visible = jnp.logical_or(not (causal and skip_blocks),
                             q_start + block_q - 1 >= k_start)

    @pl.when(visible)
    def _block():
        def _rows(at):
            q = q_ref[at]
            k = k_ref[at]
            v = v_ref[at]
            do = do_ref[at]
            s = _dot(q, k, -1, -1) * scale
            if causal:
                s = s + causal_bias(block_q, block_k, q_start, k_start)
            p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - lse_ref[at]), 0.0)
            dp = _dot(do, v, -1, -1)
            ds = p * (dp - delta_ref[at]) * scale
            dq_acc[_row_of(dq_acc, at)] += _dot(ds.astype(k.dtype), k, -1, -2)
        _for_rows(rows, block_q * block_k, _rows)

    @pl.when(ik == num_kb - 1)
    def _finalize():
        dq_ref[_all_rows(rows)] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                    causal, skip_blocks):
    rows = q_ref.shape[0]
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    num_qb = pl.num_programs(2)
    d = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(d)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = offs_ref[0] + iq * block_q
    k_start = offs_ref[1] + ik * block_k
    visible = jnp.logical_or(not (causal and skip_blocks),
                             q_start + block_q - 1 >= k_start)

    @pl.when(visible)
    def _block():
        def _rows(at):
            row = _row_of(dk_acc, at)
            q = q_ref[at]
            k = k_ref[at]
            v = v_ref[at]
            do = do_ref[at]
            s = _dot(q, k, -1, -1) * scale
            if causal:
                s = s + causal_bias(block_q, block_k, q_start, k_start)
            p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - lse_ref[at]), 0.0)
            dv_acc[row] += _dot(p.astype(do.dtype), do, -2, -2)    # p^T do
            dp = _dot(do, v, -1, -1)
            ds = p * (dp - delta_ref[at]) * scale
            dk_acc[row] += _dot(ds.astype(q.dtype), q, -2, -2)     # ds^T q
        _for_rows(rows, block_q * block_k, _rows)

    @pl.when(iq == num_qb - 1)
    def _finalize():
        every = _all_rows(rows)
        dk_ref[every] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[every] = dv_acc[:].astype(dv_ref.dtype)


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

    f32 = jnp.float32
    q_block, k_block, row_block = (block_q, d), (block_k, d), (block_q, 1)
    ins = [(q_block, q.dtype), (k_block, k.dtype), (k_block, v.dtype),
           (q_block, do.dtype), (row_block, f32), (row_block, f32)]

    def outer(ibh, i, j, offs):
        return ibh, i, 0

    def inner(ibh, i, j, offs):
        return ibh, j, 0

    def call(name, body, grid_tail, at_q, at_k, out_block, out_len, n_out):
        """One backward kernel: ``n_out`` results of ``out_block`` a row at
        the grid's second index, each with its f32 accumulator in scratch."""
        g, vmem = _rows_per_program(
            b * h, block_q, block_k, ins + [(out_block, out_dtype)] * n_out,
            [(out_block, f32)] * n_out)
        _announce(name, qr, sk, block_q, block_k, g, vmem)

        def spec(shape, at):
            return pl.BlockSpec((g,) + shape, at)
        return pl.pallas_call(
            functools.partial(body, block_q=block_q, block_k=block_k,
                              causal=causal, skip_blocks=not interpret),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b * h // g,) + grid_tail,
                in_specs=[spec(q_block, at_q), spec(k_block, at_k),
                          spec(k_block, at_k), spec(q_block, at_q),
                          spec(row_block, at_q), spec(row_block, at_q)],
                out_specs=[spec(out_block, outer)] * n_out,
                scratch_shapes=[_scratch(g, out_block)] * n_out,
            ),
            out_shape=[_sds((b * h, out_len, d), out_dtype, qr, kr, vr, dor,
                            offs)] * n_out,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name=name,
        )(offs, qr, kr, vr, dor, lser, deltar)

    # dq: q blocks outside, accumulated over the k blocks inside; dk and dv:
    # k blocks outside, accumulated over the q blocks inside.
    dq, = call("flash_bwd_dq", _bwd_dq_kernel,
               (sq // block_q, sk // block_k), outer, inner, q_block, sq, 1)
    dk, dv = call("flash_bwd_dkv", _bwd_dkv_kernel,
                  (sk // block_k, sq // block_q), inner, outer, k_block, sk, 2)
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
