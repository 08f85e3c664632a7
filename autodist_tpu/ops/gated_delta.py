"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464), chunked
(arXiv:2406.06484), with its backward pass.

Per head, a state ``S`` of (d_k, d_v) starts at zero at a row's first
position and is rewritten once a position::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t            alpha_t = exp(g_t) in (0, 1], beta_t in [0, 2]

(the transpose of the (d_v, d_k) state ``alpha S (I - beta k k^T) + beta v
k^T`` of the papers; the numbers are the same).  A position at a time that
is ``s`` sequential steps; here a row is cut into chunks of ``chunk``
positions.  With ``gamma_i`` the sum of ``g`` from the chunk's start to
position i, ``u_i = beta_i (v_i - alpha_i S_(i-1)^T k_i)`` what position i
really writes and ``S_0`` the state the chunk starts from::

    (I + A) U = diag(beta) (V - diag(exp gamma) K S_0)
    A_ij = beta_i exp(gamma_i - gamma_j) (k_i . k_j)      for j < i, else 0
    O    = diag(exp gamma) Q S_0 + (M * Q K^T) U          M_ij = exp(gamma_i
    S_C  = exp(gamma_C) S_0 + (exp(gamma_C - gamma) K)^T U      - gamma_j), j <= i

so ``T = (I + A)^-1`` (the WY / UT form: the inverse of a unit
lower-triangular matrix of ``chunk`` rows, once a chunk and head, by block
matrix products where ``chunk`` is a power of two: ``unit_lower_inverse``),
``W = T diag(beta exp gamma) K`` and ``U_0 = T diag(beta) V`` are computed
for every chunk at once, and one walk over the ``s / chunk`` chunks carries
``S`` in float32 through four matrix products a step (``U = U_0 - W S``,
``O``'s two, ``S_C``).
Decays are differences of logarithms, never quotients of decays, so a
decay near 0 underflows to an exact 0 and nothing overflows.  Every product
takes operands in the inputs' dtype (bfloat16 on the train path) and
accumulates in float32, but the two that ``T`` multiplies, which are float32
throughout; the decays, the inverse and the carried state are float32.

**Fewer key heads than value heads** (``q``, ``k`` of ``H_k`` heads, ``v``,
``g``, ``beta`` of ``H_v = r H_k``): value head ``h`` reads the queries and
keys of key head ``h // r``.  A chunk's ``K K^T`` and ``Q K^T`` belong to a
key head and are computed once for it; the decays, ``beta``, ``A``, the
inverse, ``W``, ``U_0`` and the state belong to a value head, so the two
products meet their value heads' decays as a broadcast over the ``r`` heads of
a group (``of_value_heads`` in ``_chunk_terms``), and so do ``q`` and ``k``
where a value head's decay scales them (``diag(exp gamma) Q``,
``diag(exp(gamma_C - gamma)) K``, ``diag(beta exp gamma) K``): elementwise,
fused with the multiply.  With ``r`` = 1 nothing is broadcast and the
equations are those of equal heads.

**The walk over the chunks** is two Pallas kernels on a TPU (``_walk``, one
``jax.custom_vjp``; ``walk_form`` decides from the backend, the mesh and the
shapes, and the ``lax.scan`` they replace runs anywhere else: off TPU, with
a mesh axis left to the partitioner, a chunk of no whole 8-row tiles, a head
too wide for VMEM).  Both run a grid (batch, heads / ``Hb``, chunks), the
last dimension in order.  Forward, the state of ``Hb`` heads, ``(Hb, d_k,
d_v)`` float32, stays in a VMEM scratch from a row's first chunk to its
last; a program reads its chunk's ``W``, ``U_0``, ``M * Q K^T``, ``diag(exp
gamma) Q``, ``diag(exp(gamma_C - gamma)) K`` and ``exp(gamma_C)`` through
its block's index on the stacked ``(chunks, batch, heads, chunk, ...)``
arrays ``_chunk_terms`` makes (the pipeline fetches the next chunk's under
this chunk's products; nothing is sliced or stacked by an instruction), does
the scan step's arithmetic (operands in the inputs' dtype, float32 sums, ``U``
rounded to the inputs' dtype) and writes ``O`` and, where a backward pass
will read it, the state the chunk starts from (not in a forward pass under
``jax.checkpoint``, which makes the walk again before it transposes it); the
final state leaves with the last chunk.  Transposed, the same grid counts a row's chunks from its
end with the state's cotangent in the scratch, started from the final
state's own (``layers.gdn`` returns the state); a program makes its chunk's
``U`` again from the saved state and writes the cotangents of the five terms
and of the decay: ``dQ~ = dO S^T``, ``d(M * Q K^T) = dO U^T``, ``dU = (M * Q
K^T)^T dO + K~ dS``, ``dK~ = U dS^T``, ``dU_0 = dU``, ``dW = -dU S^T``,
``d exp(gamma_C) = <dS, S>`` and ``dS <- exp(gamma_C) dS + Q~^T dO - W^T
dU``, summed in float32.  ``Hb`` is the largest divisor of the heads whose
blocks, double-buffered, fit the VMEM budget (``_head_block``: 16 of 32 heads
at 64 x 128 / 128 in bfloat16, 10 of 30 at 64 x 96 / 192).

The backward pass is autodiff through this chunked form (the inverse
brings its own cotangent, ``dA = -tril(T^T dT T^T, -1)``: two products a
system; the walk its transposed kernel, or the scan's transpose under
``jax.checkpoint``) with a ``jax.checkpoint`` around the terms: the walk
saves each chunk's incoming state (``s / chunk`` x heads x d_k x d_v float32
a row: 141 MB at 4,096 positions, 30 heads of 96 x 192; 268 MB at 8,192
positions, 32 heads of 128 x 128) and the five terms it was given, in the
inputs' dtype, and its transpose is again one walk over the chunks,
backwards, that makes a chunk's ``U`` again; the terms' own intermediates
(the decay matrices, ``A``, ``T``: float32, chunk x chunk a chunk and head)
are made again from q, k, v, g and beta and not kept.  Without the two, one
layer of 30 heads keeps 0.7 GB at 4,096 positions.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops.flash_attention import (_dot, _free_axes,
                                               _padded_bytes, _sds)
from autodist_tpu.utils import logging

#: Positions a chunk: the rows of the matrix inverted and the walk's stride.
CHUNK = 64
BACKWARD = ("autodiff through the chunked form around the inverse's own "
            "cotangent and the walk's transpose (its kernel; off TPU the "
            "scan's); each chunk's incoming state and the walk's terms "
            "saved, the terms' intermediates (the inverse among them) and a "
            "chunk's U recomputed (jax.checkpoint)")

_announced = set()


def _announce(rows, s, heads, key_heads, d_k, d_v, chunk, head_block, why):
    """Gauges and a ``gdn`` event for the rule being traced; the event and
    the log line are written once a process for each shape and form
    traced."""
    from autodist_tpu import observability
    chunks = -(-s // chunk)
    grouped = "" if key_heads == heads else (
        f", {heads // key_heads} value heads a key head ({key_heads} key "
        f"heads: K K^T and Q K^T once a key head)")
    walk = (f"Pallas kernels, {head_block} heads a program, the state in VMEM"
            if head_block else "lax.scan")
    detail = (f"gated delta rule, chunked: ({rows}, {s}, {heads}, {d_k} / "
              f"{d_v}){grouped}, {chunks} chunks of {chunk} a row, state "
              f"{heads} x {d_k} x {d_v} float32; walk over the chunks: "
              f"{walk} ({why}); inverse: {inverse_form(chunk)}; backward: "
              f"{BACKWARD}")
    new = detail not in _announced
    _announced.add(detail)
    if new:
        logging.info("gated_delta_rule: %s", detail)
    if observability.enabled():
        registry = observability.registry()
        registry.gauge("gdn.heads").set(heads)
        registry.gauge("gdn.key_heads").set(key_heads)
        registry.gauge("gdn.chunk").set(chunk)
        registry.gauge("gdn.chunks_per_row").set(chunks)
        registry.gauge("gdn.state_bytes_per_row").set(heads * d_k * d_v * 4)
        registry.gauge("gdn.scan_kernel").set(int(bool(head_block)))
        registry.gauge("gdn.scan_head_block").set(head_block)
        if new:
            observability.record_event("gdn", detail)


def _mm(spec, a, b, dtype):
    """``einsum`` with both operands in ``dtype``, accumulated and returned
    in float32."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def gated_delta_rule(q, k, v, g, beta, chunk=CHUNK, interpret=None):
    """``(o, state)`` of the gated delta rule: ``o`` (batch, s, heads, d_v)
    and each row's final state (batch, heads, d_k, d_v) in float32, over
    ``q``, ``k`` (batch, s, key heads, d_k), ``v`` (batch, s, heads, d_v),
    the log decays ``g`` <= 0 and the write strengths ``beta`` (batch, s,
    heads); one document a row, the state zero at its start.  The key heads
    are ``heads`` or a divisor of them: value head ``h`` reads key head ``h
    // (heads / key heads)`` (the module docstring).  ``q`` and ``k`` come
    as the rule takes them (normalised and scaled by the caller).  A length
    ``chunk`` does not divide is padded inside with positions that decay
    nothing and write nothing.  ``interpret=None`` walks the chunks in the
    Pallas kernels on a TPU and in the ``lax.scan`` anywhere else
    (``walk_form``); True runs the kernels in the Pallas interpreter (the
    CPU tests)."""
    b, s, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    if heads % key_heads or k.shape != q.shape \
            or g.shape != v.shape[:3] or beta.shape != g.shape:
        raise ValueError(
            f"q and k {q.shape} / {k.shape} must hold key heads that divide "
            f"the {heads} heads of v {v.shape}, g {g.shape} and beta "
            f"{beta.shape}")
    interpret, head_block, why = walk_form(interpret, heads, chunk, d_k, d_v,
                                           q.dtype)
    _announce(b, s, heads, key_heads, d_k, d_v, chunk, head_block, why)
    return _chunked_rule(q, k, v, g, beta, chunk=chunk, interpret=interpret,
                         head_block=head_block)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "interpret", "head_block"))
def _chunked_rule(q, k, v, g, beta, chunk, interpret=None, head_block=0):
    """An inlined ``jit``: a model's linear layers make the same call, and
    every one after the first takes the first's equations from the cache
    (the benchmark's process spends 5-10 times a clean process's time on
    tracing, PERF.md section 7, and the inverse's levels are operations more
    to trace than the solve they replace).  ``head_block`` heads a program
    of the kernels; 0: the scan."""
    b, s, _, d_k = q.shape
    h, d_v = v.shape[2:]
    dtype = q.dtype
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk

    def chunked(t):     # (b, s, h, ...) -> (n, b, h, chunk, ...)
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 1), 2, 0)

    terms = jax.checkpoint(lambda *a: _chunk_terms(*a, dtype))(
        *(chunked(t) for t in (q, k, v)),
        *(chunked(t.astype(jnp.float32)) for t in (g, beta)))

    def step(state, chunk_in):
        w, u0, qk, q_in, k_out, carry = chunk_in
        s0 = state.astype(dtype)
        u = (u0 - _mm("bhik,bhkd->bhid", w, s0, dtype)).astype(dtype)
        o = _mm("bhik,bhkd->bhid", q_in, s0, dtype) \
            + _mm("bhij,bhjd->bhid", qk, u, dtype)
        state = carry * state + _mm("bhik,bhid->bhkd", k_out, u, dtype)
        return state, o.astype(dtype)

    if head_block:
        state, o = _walk(*terms, head_block, interpret)
    else:
        state, o = lax.scan(jax.checkpoint(step),
                            jnp.zeros((b, h, d_k, d_v), jnp.float32), terms)
    # (n, b, h, chunk, d_v) -> (b, s, h, d_v)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(b, n * chunk, h,
                                                          d_v)
    return o[:, :s], state


# -- the walk over the chunks as two Pallas kernels ---------------------------

# Mosaic's scoped VMEM limit is 16 MiB a kernel on the v5e; the padded
# estimate leaves out a program's own values (``ops/flash_attention.py``).
_VMEM_BUDGET = 12 * 2 ** 20


def _head_block(heads, chunk, d_k, d_v, dtype):
    """Heads a program: the largest divisor of ``heads`` whose blocks,
    double-buffered, and state scratch stay within ``_VMEM_BUDGET`` in the
    transposed kernel, which holds the most: the five terms and the decay
    in and their cotangents out, the saved state, ``o``'s cotangent and the
    final state's (at 64 x 128 / 128 in bfloat16 0.58 MB a head: 16 of 32
    heads; at 64 x 96 / 192 0.84 MB: 10 of 30)."""
    terms = [((chunk, d_k), dtype), ((chunk, d_v), dtype),
             ((chunk, chunk), dtype), ((chunk, d_k), dtype),
             ((chunk, d_k), dtype), ((1, 1), jnp.float32)]
    state = ((d_k, d_v), jnp.float32)
    blocks = 2 * terms + [state, ((chunk, d_v), dtype), state]
    a_head = (2 * sum(_padded_bytes(*b) for b in blocks)
              + _padded_bytes(*state))
    return max((n for n in range(1, heads + 1)
                if heads % n == 0 and n * a_head <= _VMEM_BUDGET), default=0)


def walk_form(interpret, heads, chunk, d_k, d_v, dtype):
    """``(interpret, heads a program, why)``: how a trace walks a row's
    chunks.  The kernels (``interpret`` False, or True where a test asked
    for the Pallas interpreter) on a TPU backend with no mesh axis left to
    the partitioner (Mosaic kernels cannot be partitioned automatically),
    for a chunk of whole 8-row tiles whose blocks of one head fit the
    budget; ``(None, 0, why)``, the ``lax.scan``, anywhere else."""
    if interpret is None:
        backend = jax.default_backend()
        if backend != "tpu":
            return None, 0, f"backend is {backend}; the kernels compile for tpu"
        if _free_axes() is not None:
            return None, 0, "a mesh axis is left to the partitioner"
    if chunk % 8:
        return None, 0, f"a chunk of {chunk} is no multiple of 8"
    head_block = _head_block(heads, chunk, d_k, d_v, dtype)
    if not head_block:
        return None, 0, (f"one head's blocks at {chunk} x {d_k} / {d_v} pass "
                         f"{_VMEM_BUDGET} bytes of VMEM")
    return bool(interpret), head_block, (
        "interpret=True requested" if interpret else
        "backend is tpu" if interpret is None else "interpret=False requested")


def _walk_forward_body(w_ref, u0_ref, qk_ref, q_ref, k_ref, carry_ref, o_ref,
                       *rest, heads):
    """One chunk of ``heads`` heads: the scan's step, the state in the VMEM
    scratch (last of ``rest``) from the row's first chunk to its last, where
    it leaves as the row's final state; ``rest`` begins with the block the
    chunk's incoming state is saved to where a backward pass will read
    it."""
    *saved, final_ref, state_ref = rest
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    dtype = w_ref.dtype
    for h in range(heads):
        state = state_ref[h]
        if saved:
            saved[0][h] = state
        s0 = state.astype(dtype)
        u = (u0_ref[h] - _dot(w_ref[h], s0, -1, -2)).astype(dtype)
        o_ref[h] = (_dot(q_ref[h], s0, -1, -2)
                    + _dot(qk_ref[h], u, -1, -2)).astype(o_ref.dtype)
        state_ref[h] = carry_ref[h] * state + _dot(k_ref[h], u, -2, -2)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        final_ref[...] = state_ref[...]


def _walk_transposed_body(w_ref, u0_ref, qk_ref, q_ref, k_ref, carry_ref,
                          saved_ref, do_ref, dfinal_ref, dw_ref, du0_ref,
                          dqk_ref, dq_ref, dk_ref, dcarry_ref, dstate_ref, *,
                          heads):
    """The step's transpose for one chunk of ``heads`` heads, the grid's
    last index counting the chunks from a row's end: the cotangent of the
    state the chunk leaves is in the VMEM scratch, started from the final
    state's; the chunk's ``U`` is made again from its saved incoming
    state."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = dfinal_ref[...]

    dtype = w_ref.dtype
    for h in range(heads):
        state, dstate = saved_ref[h], dstate_ref[h]
        w, qk, q_in, k_out, do = (ref[h] for ref in (w_ref, qk_ref, q_ref,
                                                     k_ref, do_ref))
        s0, ds = state.astype(dtype), dstate.astype(dtype)
        u = (u0_ref[h] - _dot(w, s0, -1, -2)).astype(dtype)
        dq_ref[h] = _dot(do, s0, -1, -1).astype(dtype)          # do S^T
        dqk_ref[h] = _dot(do, u, -1, -1).astype(dtype)          # do U^T
        dk_ref[h] = _dot(u, ds, -1, -1).astype(dtype)           # U dS^T
        du = _dot(qk, do, -2, -2) + _dot(k_out, ds, -1, -2)
        du0_ref[h] = du.astype(dtype)
        du = du.astype(dtype)
        dw_ref[h] = (-_dot(du, s0, -1, -1)).astype(dtype)       # -dU S^T
        dcarry_ref[h] = jnp.sum(dstate * state, axis=(0, 1), keepdims=True)
        dstate_ref[h] = (carry_ref[h] * dstate + _dot(q_in, do, -2, -2)
                         - _dot(w, du, -2, -2))


def _walk_call(body, name, ins, outs, heads, reverse, interpret):
    """``pallas_call`` of a walk: grid (batch, head blocks, chunks), the
    chunks in order (from a row's end where ``reverse``), on ``ins`` and for
    ``outs`` (ShapeDtypeStructs).  Of a stacked array ``(n, b, h, ...)`` a
    block is the chunk's rows of ``heads`` heads; of a row's one state ``(b,
    h, d_k, d_v)`` it is those heads', the same for every chunk."""
    n, b, h = ins[0].shape[:3]

    def spec(x):
        if x.ndim == 4:
            return pl.BlockSpec((None, heads) + x.shape[2:],
                                lambda i, j, c: (i, j, 0, 0))
        return pl.BlockSpec(
            (None, None, heads) + x.shape[3:],
            lambda i, j, c: ((n - 1 - c if reverse else c), i, j, 0, 0))

    return pl.pallas_call(
        functools.partial(body, heads=heads),
        grid=(b, h // heads, n),
        in_specs=[spec(x) for x in ins], out_specs=[spec(x) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads,) + ins[0].shape[-1:]
                                   + ins[1].shape[-1:], jnp.float32)],
        # Only the walk over the chunks carries the scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)(*ins)


def _walk_forward(terms, head_block, interpret, save):
    """``(final state, o, each chunk's incoming state or None)``."""
    w, u0 = terms[:2]
    n, b, h, _, d_k = w.shape
    state = _sds((b, h, d_k, u0.shape[-1]), jnp.float32, *terms)
    outs = [_sds(u0.shape, u0.dtype, *terms)]
    if save:
        outs.append(_sds((n,) + state.shape, jnp.float32, *terms))
    o, *saved, final = _walk_call(
        _walk_forward_body, "gdn_walk_fwd", terms, outs + [state],
        head_block, False, interpret)
    return final, o, (saved[0] if save else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _walk(w, u0, qk, q_in, k_out, carry, head_block, interpret):
    """``(final state, o)`` of the scan over the chunks, as kernels: terms
    ``(n, b, h, chunk, ...)`` as ``_chunk_terms`` makes them."""
    return _walk_forward((w, u0, qk, q_in, k_out, carry), head_block,
                         interpret, save=False)[:2]


def _walk_fwd(w, u0, qk, q_in, k_out, carry, head_block, interpret):
    terms = (w, u0, qk, q_in, k_out, carry)
    final, o, saved = _walk_forward(terms, head_block, interpret, save=True)
    return (final, o), (terms, saved)


def _walk_bwd(head_block, interpret, res, cotangents):
    terms, saved = res
    dfinal, do = cotangents
    ins = terms + (saved, do, dfinal)
    return tuple(_walk_call(
        _walk_transposed_body, "gdn_walk_bwd", ins,
        [_sds(t.shape, t.dtype, *ins) for t in terms], head_block, True,
        interpret))


# optimize_remat: under ``jax.checkpoint`` the forward pass proper runs
# ``_walk`` itself, which saves no states (a kernel's unread output cannot be
# dropped: 268 MB a layer written for nothing otherwise); the forward rule
# runs where the backward pass makes the walk again.
_walk.defvjp(_walk_fwd, _walk_bwd, optimize_remat=True)


def _chunk_terms(q, k, v, g, beta, dtype):
    """What the scan over the chunks takes, for every chunk at once: ``W``,
    ``U_0``, ``M * Q K^T``, ``diag(exp gamma) Q`` and ``diag(exp(gamma_C -
    gamma)) K`` in ``dtype``, and ``exp(gamma_C)`` in float32.  ``v`` is (n,
    b, h, chunk, d_v), ``g`` and ``beta`` (n, b, h, chunk) in float32, ``q``
    and ``k`` (n, b, key heads, chunk, d_k): what is computed from them alone
    is computed a key head and met by its value heads as a broadcast."""
    chunk = g.shape[-1]
    group = v.shape[2] // k.shape[2]

    def of_value_heads(x):      # (n, b, key heads, ...) -> (n, b, h, ...)
        return x if group == 1 else jnp.repeat(x, group, axis=2)

    gamma = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :],
                              -jnp.inf))                  # M, zero above
    a = jnp.where(jnp.tril(lower, -1), beta[..., None] * decay
                  * of_value_heads(_mm("nbhid,nbhjd->nbhij", k, k, dtype)),
                  0.0)
    with jax.named_scope("inverse"):
        t = unit_lower_inverse(a)
    into = jnp.exp(gamma)[..., None]                      # decay since S_0
    # T's two products stay float32, exact: rounding the inverse to bf16 is
    # the rule's largest error (the reference check read 7.0e-5 with it and
    # 4.4e-5 without), and at chunk x chunk a chunk and head they are cheap.
    w, u0 = (jnp.einsum("nbhij,nbhjd->nbhid", t, rhs,
                        precision=lax.Precision.HIGHEST)
             for rhs in ((beta[..., None] * into) * of_value_heads(k),
                         beta[..., None] * v))
    qk = decay * of_value_heads(_mm("nbhid,nbhjd->nbhij", q, k, dtype))
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None]  # decay to S_C
    carry = jnp.exp(gamma[..., -1])[..., None, None]      # (n, b, h, 1, 1)
    return tuple(x.astype(dtype) for x in (
        w, u0, qk, into * of_value_heads(q),
        to_end * of_value_heads(k))) + (carry,)


def inverse_form(chunk):
    """How ``unit_lower_inverse`` computes a chunk's ``T``, for the ``gdn``
    event and the op's info line."""
    if chunk & (chunk - 1):
        return ("triangular solve against the identity, float32 (chunk no "
                "power of two); its cotangent the solve's own")
    return ("block products level by level from 1 x 1, float32, vector work "
            "with the systems along the lanes; its cotangent closed, "
            "-tril(T^T dT T^T, -1)")


def unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a`` (..., c, c) in
    float32; what stands on or above the diagonal is never read.  ``c`` a
    power of two: by halves, ``[[L11, 0], [A21, L22]]^-1 = [[T11, 0], [-T22
    A21 T11, T22]]``, level by level from the 1 x 1 blocks (inverse 1) up,
    each level two products batched over every pair of blocks of every
    system; no row substitution, which on the TPU is ``c`` dependent steps
    of vector work (a ``custom-call`` that was the fourth operation of the
    Olmo-Hybrid step).  Not ``(I - a)(I + a^2)(I + a^4)...``: exact on
    paper, but with a chunk's keys alike the powers of ``a`` grow before
    they cancel and float32 is off by factors of 1e15.  Any other ``c``:
    ``solve_triangular`` against the identity."""
    c = a.shape[-1]
    if c & (c - 1):
        eye = jnp.eye(c, dtype=a.dtype)
        return jax.scipy.linalg.solve_triangular(
            a + eye, jnp.broadcast_to(eye, a.shape), lower=True,
            unit_diagonal=True)
    return _block_inverse(a)


@jax.custom_vjp
def _block_inverse(a):
    """The levels run with the systems as the minor dimension, (blocks, m,
    m, systems): of a chunk of 64 a block has 32 rows at most, a quarter of
    a vector's 128 lanes, and as matrix products blocks that small fill a
    sliver of the MXU's tile; a layer's 1,920 systems fill the lanes at
    every level
    (v5e, the inverse alone at the cell's shape: 0.41 ms against 0.99 ms
    with the blocks' own rows along the lanes, 1.13 to 1.88 ms with the
    levels under 16 or 8 rows on the MXU, 3.00 ms for the solve)."""
    c = a.shape[-1]
    t = _inverse_of_blocks(jnp.moveaxis(a.reshape((1, -1, c, c)), 1, -1))
    return jnp.moveaxis(t, -1, 1).reshape(a.shape)


def _block_inverse_fwd(a):
    t = _block_inverse(a)
    return t, t


def _block_inverse_bwd(t, dt):
    """``dT = -T dA T``, so ``dA = -T^T dT T^T``, cut to what ``a`` is read
    from: two float32 products a system (``HIGHEST``, as T's other two: the
    inverse's rounding is the rule's largest error in the reference check),
    where autodiff through the levels runs two for each of the forward's
    and keeps every level's blocks (measured 2.3 ms a layer slower on the
    v5e, and 0.1 GB more at the cell's shape)."""
    t = jnp.swapaxes(t, -1, -2)
    da = jnp.matmul(jnp.matmul(t, dt, precision=lax.Precision.HIGHEST), t,
                    precision=lax.Precision.HIGHEST)
    return (jnp.tril(-da, -1),)


_block_inverse.defvjp(_block_inverse_fwd, _block_inverse_bwd)


def _inverse_of_blocks(d):
    """``(I + d)^-1`` of each diagonal block ``d`` (blocks, m, m, systems),
    m a power of two: one Python level a halving, every block of every
    system in one array (blocks x 2, m / 2, m / 2, systems), so a chunk of
    64 traces six levels and not a tree of 63 products."""
    blocks, m, _, systems = d.shape
    if m == 1:
        return jnp.ones_like(d)
    half = m // 2
    x = d.reshape(blocks, 2, half, 2, half, systems)
    t11, t22 = jnp.split(_inverse_of_blocks(jnp.concatenate(
        [x[:, 0, :, 0], x[:, 1, :, 1]])), 2)
    t21 = -_mm_lanes(_mm_lanes(t22, x[:, 1, :, 0]), t11)
    return jnp.concatenate(
        [jnp.concatenate([t11, jnp.zeros_like(t11)], axis=2),
         jnp.concatenate([t21, t22], axis=2)], axis=1)


def _mm_lanes(a, b):
    """``a @ b`` of (blocks, i, k, systems) and (blocks, k, j, systems):
    multiplies and a sum over k, each over whole vectors of systems."""
    return jnp.sum(a[:, :, :, None] * b[:, None], axis=2)
