"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464), chunked
(arXiv:2406.06484), with its backward pass; with one decay a head and
position, or one a CHANNEL of the key (Kimi Delta Attention,
arXiv:2510.26692: the last section), by the rank of ``g``.

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
matrix products where ``chunk`` is a power of two: ``unit_lower_inverse``)
and ``M * Q K^T``, the two ``chunk`` x ``chunk`` matrices a chunk and head,
are computed for every chunk at once (``_chunk_terms``), and one walk over
the ``s / chunk`` chunks carries ``S`` in float32 and makes everything of
``chunk`` x d a chunk at a time, from q, k, v and the gates as they are
(``_head_step``: the first equation as it stands, ``U = T diag(beta) (V -
diag(exp gamma) K S_0)``, then ``O`` and ``S_C``: five matrix products a
step, ``K S_0``, ``T``'s, ``Q S_0``, ``(M * Q K^T) U`` and ``K~^T U``).
``W = T diag(beta exp gamma) K`` and ``U_0 = T diag(beta) V`` of the papers'
``U = U_0 - W S_0`` are never formed: they, ``diag(exp gamma) Q`` and
``diag(exp(gamma_C - gamma)) K`` were four ``(chunks, batch, heads, chunk,
d)`` arrays written to HBM and read back three times a layer.
With one decay a head and position, decays are differences of logarithms,
never quotients of decays, so a decay near 0 underflows to an exact 0 and
nothing overflows (a decay a channel needs a quotient in two places, and a
bound: the last section).  Every product
takes operands in the inputs' dtype (bfloat16 on the train path) and
accumulates in float32, but those that ``T`` multiplies (one forward, and
with it ``dT``'s and ``T^T``'s transposed), which take float32 operands at
``HIGHEST``; the decays, the inverse and the carried state are float32, and
a decay scales a product's float32 result where it can (``diag(exp gamma) (Q
S_0)``), a rounded operand where it must (``K~ = diag(exp(gamma_C - gamma))
K`` in ``K~^T U``).

**Fewer key heads than value heads** (``q``, ``k`` of ``H_k`` heads, ``v``,
``g``, ``beta`` of ``H_v = r H_k``): value head ``h`` reads the queries and
keys of key head ``h // r``.  A chunk's ``K K^T`` and ``Q K^T`` belong to a
key head and are computed once for it; the decays, ``beta``, ``A``, the
inverse and the state belong to a value head, so the two products meet their
value heads' decays as a broadcast over the ``r`` heads of a group
(``of_value_heads`` in ``_chunk_terms``: ``chunk`` x ``chunk`` a head).  q
and k themselves are never repeated: the kernels read a key head's block
through the block index (a program's ``Hb`` value heads are whole groups, so
its ``Hb / r`` key heads are block ``j`` of the key-head axis where its
value heads are block ``j`` of theirs) and sum dq and dk over a group's
value heads before they write them, a key head; the scan's step maps over
(key heads, ``r``) with q and k unmapped along ``r``.  With ``r`` = 1 the
equations are those of equal heads.

**The walk over the chunks** is two Pallas kernels on a TPU (``_walk``, one
``jax.custom_vjp``; ``walk_form`` decides from the backend, the mesh and the
shapes, and the ``lax.scan`` they replace runs anywhere else: off TPU, with
a mesh axis left to the partitioner, a chunk of no whole 8-row tiles, a
group of heads too wide for VMEM; it takes the same terms and runs the same
``_head_step`` under ``vmap``).  Both run a grid (batch, heads / ``Hb``,
chunks), the last dimension in order.  Forward, the state of ``Hb`` heads,
``(Hb, d_k, d_v)`` float32, stays in a VMEM scratch from a row's first
chunk to its last; a program reads its chunk's ``T`` (float32) and ``M * Q
K^T``, ``chunk`` x ``chunk`` a value head, v a value head, q and k a KEY
head and the chunk's ``gamma`` and ``beta`` as ``(Hb, chunk)`` float32 rows
(a ``(chunk, 1)`` float32 block would be 128 lanes a value in HBM and in
VMEM; ``_column`` turns a row into the column that scales a chunk's rows,
exactly, and ``beta`` scales ``T``'s columns as the row it is) through its
block's index on the stacked ``(chunks, batch, heads, chunk, ...)`` arrays
(the pipeline fetches the next chunk's under this chunk's products; nothing
is sliced or stacked by an instruction), runs ``_head_step`` and writes ``O``
and, where a backward pass will read it, the state the chunk starts from
(not in a forward pass under ``jax.checkpoint``, which makes the walk again
before it transposes it); the final state leaves with the last chunk.  A
head's products wait on one another (``S_0`` -> ``K S_0`` -> ``U`` -> ``O``,
``S_C``; the float32 product is six passes of the MXU), so a program takes
its heads ``_TRIP_HEADS`` at most a trip of a ``fori_loop`` and runs each of
the step's three phases for all of a trip's heads before the next, what
passes between phases staged in VMEM (``_for_trips``): the scheduler fills
one head's waits with its neighbours'.  Transposed, the same grid counts a
row's chunks from its end with the state's cotangent in the scratch, started
from the final state's own (``layers.gdn`` returns the state); a program
makes its chunk's ``U`` again from the saved state and writes what the
rule's inputs need and nothing wider: ``dT`` (float32), ``d(M * Q K^T)``, dv
a value head, dq and dk a key head, and the cotangents of ``gamma`` and
``beta`` as rows (``_walk_transposed_body`` has the equations).  ``Hb`` is
the largest divisor of the heads in whole groups whose blocks,
double-buffered, fit the VMEM budget (``_head_block``: 16 of 32 heads at 64
x 128 / 128 in bfloat16, 10 of 30 at 64 x 96 / 192).

The backward pass is autodiff through this chunked form (the inverse
brings its own cotangent, ``dA = -tril(T^T dT T^T, -1)``: two products a
system; ``d(M * Q K^T)`` goes on into q, k and the decays; the walk its
transposed kernel, or the scan's transpose under ``jax.checkpoint``) with a
``jax.checkpoint`` around the terms: the walk saves each chunk's incoming
state (``s / chunk`` x heads x d_k x d_v float32 a row: 141 MB at 4,096
positions, 30 heads of 96 x 192; 268 MB at 8,192 positions, 32 heads of 128
x 128) and what it was given: ``T``, ``M * Q K^T`` and the gates' rows (103
MB at the second shape, 48 at the first, where the five ``chunk`` x d terms
it read before were 302 and 134) beside the chunked q, k and v, the rule's
own inputs; its transpose is again one walk over the chunks, backwards,
that makes a chunk's ``U`` again; the terms' own intermediates (the decay
matrices, ``A``: float32, chunk x chunk a chunk and head) are made again
from q, k, g and beta and not kept.  Without the two, one layer of 30 heads
keeps 0.7 GB at 4,096 positions.

**A decay a channel of the key** (``g`` of (batch, s, heads, d_k); the rule
is ``S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_(t-1) + beta_t k_t
v_t^T``, ``alpha_t = exp(g_t)`` a vector of d_k).  With ``gamma_i`` the sum
of ``g`` over the chunk's positions up to ``i``, now a vector, and ``G_i =
exp(gamma_i)``::

    (I + A) U = diag(beta) (V - (K * G) S_0)
    A_ij = beta_i sum_c k_ic k_jc exp(gamma_ic - gamma_jc)    for j < i
    O    = (Q * G) S_0 + P U     P_ij = sum_c q_ic k_jc exp(gamma_ic -
    S_C  = diag(G_C) S_0 + (K * exp(gamma_C - gamma))^T U     gamma_jc), j <= i

Where the scalar form scales a product's result (``diag(exp gamma) (Q
S_0)``) the decay rides on the operand (``(Q * G) S_0``, rounded to the
inputs' dtype after the decay: ``_state_read``), the state's carry-over is a
scaling of its rows, and ``gamma``'s cotangent is a channel's
(``_walk_transposed_body``); the walk's kernels (``kda_walk_fwd``,
``kda_walk_bwd``) are the same bodies with ``by_channel``, their ``gamma``
block ``(Hb, chunk, d_k)`` float32 where the scalar form's is a row a head
(32 KB a head at 64 x 128, and as much for its cotangent: ``_walk_vmem``
counts it, 8 of 32 heads a program at 128 / 128 where the scalar form takes
16), and the ``lax.scan`` runs the same ``_head_step``.  **Only ``A`` and
``P`` hold a quotient of decays**, and they are no masked products: ``(K * G)
(K / G)^T`` over a chunk's 64 positions overflows (a gate of -5 a position
is ``exp(320)``).  The form taken (``_chunk_terms_by_channel``): the chunk's
positions in sub-blocks of ``SUB_BLOCK`` = 16, and for each pair of
sub-blocks ``J <= I`` ONE product of two scaled operands, both measured from
``r_I``, the summed log decay at sub-block ``I``'s first position: the rows
take ``exp(gamma_i - r_I)`` <= 1, the columns ``exp(r_I - gamma_j)``, which
is <= 1 for an earlier sub-block (``gamma_j >= r_I``) and at most
``exp(-GATE_LOWER_BOUND x 15)`` = e^75 inside ``I`` itself; float32 and
bfloat16 both hold that (their largest is e^88.7, and a sum of 128 channels
of it stays under), so the diagonal sub-blocks take the same product as the
others and none is computed a pair at a time on the vector unit.  That is
what the bound of -5 on a position's gate is for (``layers.kda``'s gate is
``-5 sigmoid(.)``; ``GATE_LOWER_BOUND``, the gauge
``kda.gate_lower_bound``, and ``sub_block_gate_min`` reads how far a step
came): a gate under it overflows, and the form is for bounded gates only.
The sub-block width is the widest the bound allows (-80 / 16) and the
narrowest that keeps a product's rows at a bf16 tile's 16.  The columns'
operand exists ``chunk / SUB_BLOCK`` = 4 times, once under each ``r_I``
(``(n, b, h, 4, chunk, d_k)`` in the inputs' dtype, of which the sub-blocks
above the diagonal are exact zeros: 4 copies of k a layer, 67 MB at 2,048
positions of 32 heads); no ``(chunk, chunk, d_k)`` array exists anywhere.
A decay of a whole sub-block or more away underflows to an exact 0, as in
the scalar form.
"""
import functools
import math

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
#: With a decay a channel, the positions of a chunk's sub-blocks: between two
#: of them and inside one, a chunk's two matrices are products of operands
#: that carry the decay measured from the later sub-block's first position.
SUB_BLOCK = 16
#: The lowest log decay a position and channel for which that form holds:
#: inside a sub-block an operand's decay reaches ``exp(-GATE_LOWER_BOUND x
#: (SUB_BLOCK - 1))`` = e^75, and a sum over a key's channels of such
#: products stays under float32's and bfloat16's largest, e^88.7.
GATE_LOWER_BOUND = -5.0
WALK_TERMS = ("the walk reads T, M * Q K^T, q and k a key head, v and the "
              "gates' rows and makes a chunk's U = T diag(beta) (V - "
              "diag(exp gamma) K S_0) itself")
BACKWARD = ("autodiff through the chunked form around the inverse's own "
            "cotangent and the walk's transpose (its kernel; off TPU the "
            "scan's); each chunk's incoming state and the walk's terms "
            "saved, the terms' intermediates (the inverse among them) and a "
            "chunk's U recomputed (jax.checkpoint)")

_announced = set()


def _announce(rows, s, heads, key_heads, d_k, d_v, chunk, head_block, why,
              dtype):
    """Gauges and a ``gdn`` event for the rule being traced; the event and
    the log line are written once a process for each shape and form
    traced."""
    from autodist_tpu import observability
    chunks = -(-s // chunk)
    item = jnp.dtype(dtype).itemsize
    # What a row's forward walk reads from HBM, either form: T float32 and
    # M * Q K^T a value head, q and k a key head, v and the two gates.
    term_bytes = chunks * chunk * (
        heads * chunk * (4 + item) + (2 * key_heads * d_k + heads * d_v) * item
        + 2 * heads * 4)
    grouped = "" if key_heads == heads else (
        f", {heads // key_heads} value heads a key head ({key_heads} key "
        f"heads: K K^T and Q K^T once a key head)")
    walk = (f"Pallas kernels, {head_block} heads a program, the state in VMEM"
            if head_block else "lax.scan")
    detail = (f"gated delta rule, chunked: ({rows}, {s}, {heads}, {d_k} / "
              f"{d_v}){grouped}, {chunks} chunks of {chunk} a row, state "
              f"{heads} x {d_k} x {d_v} float32; walk over the chunks: "
              f"{walk} ({why}); {WALK_TERMS}, {term_bytes} bytes a row; "
              f"inverse: {inverse_form(chunk)}; backward: {BACKWARD}")
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
        registry.gauge("gdn.walk_term_bytes_per_row").set(term_bytes)
        if new:
            observability.record_event("gdn", detail)


def _announce_by_channel(rows, s, heads, key_heads, d_k, d_v, chunk,
                         head_block, why, dtype):
    """Gauges ``kda.*`` and a ``kda`` event for the rule with a decay a
    channel being traced; the event and the log line are written once a
    process for each shape and form traced."""
    from autodist_tpu import observability
    chunks = -(-s // chunk)
    sub = _sub_block(chunk)
    group = heads // key_heads
    walk = "lax.scan"
    if head_block:
        trips = [_trip_heads(head_block, group, chunk, d_k, d_v, dtype, t,
                             True) for t in (False, True)]
        vmem = [_walk_vmem(head_block, trip, group, chunk, d_k, d_v, dtype, t,
                           True) for trip, t in zip(trips, (False, True))]
        walk = (f"Pallas kernels kda_walk_fwd / kda_walk_bwd, {head_block} "
                f"heads a program ({trips[0]} / {trips[1]} a trip), the state "
                f"in VMEM, {vmem[0]} / {vmem[1]} bytes of VMEM by the padded "
                f"estimate")
    detail = (f"delta rule with a decay a channel, chunked: ({rows}, {s}, "
              f"{heads}, {d_k} / {d_v}), {key_heads} key heads, {chunks} "
              f"chunks of {chunk} a row, state {heads} x {d_k} x {d_v} "
              f"float32; a chunk's two matrices from sub-blocks of {sub} "
              f"positions, both operands of a sub-block pair scaled by the "
              f"decay measured from the later one's first position (one "
              f"product a pair, nothing above exp({-GATE_LOWER_BOUND:g} x "
              f"{sub - 1}); a gate under {GATE_LOWER_BOUND:g} a position "
              f"would overflow); walk over the chunks: {walk} ({why}), gamma "
              f"a ({chunk}, {d_k}) float32 block a head; inverse: "
              f"{inverse_form(chunk)}; backward: {BACKWARD}")
    new = detail not in _announced
    _announced.add(detail)
    if new:
        logging.info("gated_delta_rule: %s", detail)
    if observability.enabled():
        registry = observability.registry()
        registry.gauge("kda.heads").set(heads)
        registry.gauge("kda.key_dim").set(d_k)
        registry.gauge("kda.sub_block").set(sub)
        registry.gauge("kda.gate_lower_bound").set(GATE_LOWER_BOUND)
        registry.gauge("kda.scan_kernel").set(int(bool(head_block)))
        registry.gauge("kda.scan_head_block").set(head_block)
        if new:
            observability.record_event("kda", detail)


def _sub_block(chunk):
    """The positions of a chunk's sub-blocks: ``SUB_BLOCK``, or the largest
    divisor of ``chunk`` under it."""
    return math.gcd(chunk, SUB_BLOCK)


def sub_block_gate_min(g, chunk=CHUNK):
    """The most negative log decay summed inside a sub-block, over ``g``
    (batch, s, heads, d_k): what stands between the sub-block form and an
    overflow (``GATE_LOWER_BOUND x (SUB_BLOCK - 1)`` is the least it
    holds)."""
    sub = _sub_block(chunk)
    b, s = g.shape[:2]
    g = jnp.pad(g, ((0, 0), (0, -s % sub), (0, 0), (0, 0)))
    return jnp.min(jnp.sum(
        g.reshape((b, -1, sub) + g.shape[2:])[:, :, 1:], axis=2))


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
    heads); one document a row, the state zero at its start.  ``g`` of
    (batch, s, heads, d_k) is a decay a CHANNEL of the key (the module
    docstring's last section: the rank of ``g`` decides the form, and a
    position's gate is then held to ``GATE_LOWER_BOUND``).  The key heads
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
    by_channel = g.ndim == 4
    if heads % key_heads or k.shape != q.shape \
            or g.shape != v.shape[:3] + ((d_k,) if by_channel else ()) \
            or beta.shape != v.shape[:3]:
        raise ValueError(
            f"q and k {q.shape} / {k.shape} must hold key heads that divide "
            f"the {heads} heads of v {v.shape}, g {g.shape} (a head, or a "
            f"head and channel of the key) and beta {beta.shape}")
    interpret, head_block, why = walk_form(
        interpret, heads, key_heads, chunk, d_k, d_v, q.dtype, by_channel)
    (_announce_by_channel if by_channel else _announce)(
        b, s, heads, key_heads, d_k, d_v, chunk, head_block, why, q.dtype)
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
    b, s, key_heads, d_k = q.shape
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

    q, k, v = (chunked(t) for t in (q, k, v))
    g, beta = (chunked(t.astype(jnp.float32)) for t in (g, beta))
    t, qk, gamma = jax.checkpoint(lambda *a: _chunk_terms(*a, dtype))(
        q, k, g, beta)
    terms = (t, qk, q, k, v, gamma, beta)
    if head_block:
        state, o = _walk(*terms, head_block, interpret)
    else:
        group = h // key_heads
        # One head's step over the group's heads (q and k their key head's),
        # the key heads and the rows.
        heads = jax.vmap(jax.vmap(jax.vmap(
            _head_step, in_axes=(0, 0, 0, None, None) + (0,) * 5)))

        def grouped(x):     # (b, h, ...) -> (b, key heads, group, ...)
            return x.reshape((b, key_heads, group) + x.shape[2:])

        by_channel = g.ndim == 5

        def column(x):      # a chunk's decays as ``_head_step`` takes them
            return x if by_channel else x[..., None]

        def step(state, chunk_in):
            t, qk, q, k, v, gamma, beta = chunk_in
            end = gamma[..., -1:, :] if by_channel else gamma[..., -1:]
            state, o = heads(
                grouped(state), grouped(t), grouped(qk), q, k, grouped(v),
                grouped(beta[..., None, :]), grouped(column(jnp.exp(gamma))),
                grouped(column(jnp.exp(end - gamma))),
                grouped(jnp.swapaxes(jnp.exp(end), -1, -2) if by_channel
                        else jnp.exp(end)[..., None]))
            return (state.reshape((b, h) + state.shape[3:]),
                    o.reshape((b, h) + o.shape[3:]))

        state, o = lax.scan(jax.checkpoint(step),
                            jnp.zeros((b, h, d_k, d_v), jnp.float32), terms)
    # (n, b, h, chunk, d_v) -> (b, s, h, d_v)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(b, n * chunk, h,
                                                          d_v)
    return o[:, :s], state


# -- one chunk of one head: the step both walks run, and what turns the
# -- kernels' rows of gates into its columns ----------------------------------

def _dot32(a, b, contract_a, contract_b):
    """``_dot`` of float32 operands as a float32 product (``HIGHEST``: the
    MXU's passes over the operands' bfloat16 parts; in a kernel Mosaic's
    ``contract_precision<fp32>``), for the products ``T`` is part of."""
    rows = tuple(range(a.ndim - 2))
    return lax.dot_general(
        a, b, (((a.ndim + contract_a,), (b.ndim + contract_b,)),
               (rows, rows)), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _masks(c):
    """``(the (c, c) identity's mask, the (1, c) mask of the last lane)``:
    what turns a row into a column and picks ``gamma_C``; made once a
    kernel, outside its loops."""
    lane = lax.broadcasted_iota(jnp.int32, (1, c), 1)
    return lax.broadcasted_iota(jnp.int32, (c, c), 0) == lane, lane == c - 1


def _column(row, diagonal):
    """A (1, c) row as a (c, 1) column, exactly: the row under the
    identity's mask, summed along the lanes.  The gates come to the kernels
    as 64-lane rows (a ``(c, 1)`` float32 array is 128 lanes a value in HBM
    and in VMEM) and scale a chunk's rows as a column."""
    return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)


def _row(column, diagonal):
    """A (c, 1) column as a (1, c) row, exactly (``_column``'s inverse)."""
    return jnp.sum(jnp.where(diagonal, column, 0.0), axis=0, keepdims=True)


def _into(gamma, masks):
    """``exp(gamma)`` as a (c, 1) column, of a chunk's (1, c) row of summed
    log decays: the decay since ``S_0``."""
    return jnp.exp(_column(gamma, masks[0]))


def _decays(gamma, masks):
    """``_into``, the decay to ``S_C`` ``exp(gamma_C - gamma)`` (c, 1) and
    ``exp(gamma_C)`` (1, 1)."""
    diagonal, last = masks
    column = _column(gamma, diagonal)
    end = jnp.sum(jnp.where(last, gamma, 0.0), axis=1, keepdims=True)
    return jnp.exp(column), jnp.exp(end - column), jnp.exp(end)


def _by_channel(into):
    """Whether a chunk's decays are a channel's: ``into`` is then ``exp
    gamma`` (c, d_k) and not a (c, 1) column.  A (c, 1) ``into`` of a d_k of
    one channel takes the column's form, which is then the same number."""
    return into.shape[-1] != 1


def _state_read(x, s0, into):
    """``diag(exp gamma) X S_0`` in float32, of ``x`` = ``Q`` or ``K`` (c,
    d_k): a decay a position scales the product's float32 result; a decay a
    channel rides on the operand, ``(X * exp gamma) S_0``, rounded to the
    inputs' dtype."""
    if _by_channel(into):
        return _dot((into * x).astype(x.dtype), s0, -1, -2)
    return into * _dot(x, s0, -1, -2)


def _channel_carry(gamma, key_masks):
    """``exp(gamma_C)`` as the (d_k, 1) column that scales the state's rows,
    of a chunk's (c, d_k) summed log decays a channel; ``key_masks`` =
    ``_masks(d_k)``."""
    return _column(jnp.exp(gamma[-1:, :]), key_masks[0])


def _channel_decays(gamma, key_masks):
    """``(exp(gamma_C - gamma) (c, d_k), _channel_carry)``."""
    return jnp.exp(gamma[-1:, :] - gamma), _channel_carry(gamma, key_masks)


def _chunk_reads(k, v, s0, into):
    """``(K S_0, V - diag(exp gamma) K S_0)`` in float32: what a chunk's
    positions read of the state it starts from, ``s0`` in the inputs'
    dtype.  With a decay a channel the first is ``(K * exp gamma) S_0``,
    the decay in it."""
    if _by_channel(into):
        ks = _state_read(k, s0, into)
        return ks, v - ks
    ks = _dot(k, s0, -1, -2)
    return ks, v - into * ks


def _chunk_writes(t, beta, rhs):
    """``U = T diag(beta) (V - diag(exp gamma) K S_0)`` in float32, what a
    chunk's positions really write: the one product with ``T``, float32
    operands (rounding the inverse to bf16 is the rule's largest error: the
    hybrid cell's reference check read 7.0e-5 with it and 4.4e-5 without);
    ``beta`` the (1, c) row that scales ``T``'s columns."""
    return _dot32(t * beta, rhs, -1, -2)


def _chunk_leaves(state, qk, k, u, o_s0, to_end, carry):
    """``(S_C, O)`` from a chunk's ``U`` (inputs' dtype) and ``o_s0 =
    diag(exp gamma) Q S_0``."""
    o = o_s0 + _dot(qk, u, -1, -2)
    state = carry * state + _dot((to_end * k).astype(u.dtype), u, -2, -2)
    return state, o.astype(u.dtype)


def _head_step(state, t, qk, q, k, v, beta, into, to_end, carry):
    """``(S_C, O)`` of one chunk of one head from the state ``S_0`` (d_k,
    d_v) float32 it starts from: ``t`` (c, c) float32, ``qk`` = ``M * Q
    K^T`` (c, c), ``q``, ``k`` (c, d_k) and ``v`` (c, d_v) in the inputs'
    dtype, ``beta`` a (1, c) float32 row, the decays since ``S_0`` and to
    ``S_C`` as (c, 1) float32 columns ``into`` = ``exp(gamma)`` and
    ``to_end`` = ``exp(gamma_C - gamma)``, ``carry`` = ``exp(gamma_C)`` (1,
    1); with a decay a channel ``into`` and ``to_end`` are (c, d_k) and
    ``carry`` the (d_k, 1) column that scales the state's rows.  The module
    docstring's equations as they stand, ``W`` and ``U_0``
    never formed: under ``vmap`` the scan's step, and the three functions
    it is made of are the three phases of the kernels' forward body."""
    dtype = v.dtype
    s0 = state.astype(dtype)
    u = _chunk_writes(t, beta, _chunk_reads(k, v, s0, into)[1]).astype(dtype)
    return _chunk_leaves(state, qk, k, u, _state_read(q, s0, into), to_end,
                         carry)


# -- the walk over the chunks as two Pallas kernels ---------------------------

# Mosaic's scoped VMEM limit is 16 MiB a kernel on the v5e; the padded
# estimate leaves out a program's own values (``ops/flash_attention.py``).
_VMEM_BUDGET = 12 * 2 ** 20
# Value heads a trip of a program's loop over its heads, at most: a head's
# products wait on one another (S_0 -> K S_0 -> U -> O, S_C, the float32
# product six passes of the MXU), so a trip runs each phase for all its
# heads, what passes from phase to phase staged in VMEM, and the scheduler
# fills one head's waits with its neighbours' products.  By the LLO dump at
# 64 x 128 / 128 the forward trip is 304 bundles a head at 2 heads, 220 at
# 4, 195 at 8, where the MXU's slots are 87% taken.
_TRIP_HEADS = 8


def _walk_vmem(heads, trip, group, chunk, d_k, d_v, dtype, transposed,
               by_channel=False):
    """The padded VMEM bytes of a walk's program of ``heads`` value heads:
    its blocks double-buffered (``T``, ``M * Q K^T``, v, the gates' rows and
    a key head's q and k in; forward ``o``, the saved state and the final
    state out; transposed the saved state, ``o``'s cotangent and the final
    state's in and the seven cotangents out), the state scratch and the
    staging of a trip of ``trip`` heads (forward ``R = V - diag(exp gamma) K
    S_0``, ``diag(exp gamma) Q S_0`` and ``U``; transposed ``R``, ``K
    S_0``, ``dU``, ``U``, ``d(K S_0)`` and the sums of dq and dk).
    ``by_channel``: ``gamma``'s block, and its cotangent's, is ``(heads,
    chunk, d_k)`` float32 and not a row a head."""
    state, wide = ((heads, d_k, d_v), jnp.float32), ((heads, chunk, d_v), dtype)
    gamma = (heads, chunk, d_k) if by_channel else (heads, chunk)
    terms = [((heads, chunk, chunk), jnp.float32),
             ((heads, chunk, chunk), dtype),
             ((heads // group, chunk, d_k), dtype),
             ((heads // group, chunk, d_k), dtype), wide,
             (gamma, jnp.float32), ((heads, chunk), jnp.float32)]
    blocks = terms + [wide, state, state] + (terms if transposed else [])
    return (2 * sum(_padded_bytes(*b) for b in blocks)
            + _padded_bytes(*state)
            + sum(_padded_bytes(*b) for b in _stages(
                trip, chunk, d_k, d_v, dtype, transposed, by_channel)))


def _stages(trip, chunk, d_k, d_v, dtype, transposed, by_channel=False):
    """The staging scratch of a trip's heads, ``(shape, dtype)``, in the
    order the bodies take it (transposed with a decay a channel ``K S_0``
    is not staged: ``gamma``'s cotangent is made of the operands')."""
    wide, key = (trip, chunk, d_v), (trip, chunk, d_k)
    if not transposed:
        return [(wide, jnp.float32), (wide, jnp.float32), (wide, dtype)]
    return [(wide, jnp.float32)] * (2 if by_channel else 3) \
        + [(wide, dtype)] * 2 + [(key, jnp.float32)] * 2


def _trip_heads(heads, group, chunk, d_k, d_v, dtype, transposed,
                by_channel=False):
    """Value heads a trip of a program's loop over its ``heads``: whole
    groups, a divisor of ``heads``, ``_TRIP_HEADS`` at most (one group where
    a group is more), as many as leave the program within ``_VMEM_BUDGET``
    (8 of 16 forward and 4 transposed at 64 x 128 / 128 in bfloat16, 5 of
    10 both ways at 64 x 96 / 192)."""
    return max(n for n in range(group, max(group, _TRIP_HEADS) + 1, group)
               if heads % n == 0 and (n == group or _walk_vmem(
                   heads, n, group, chunk, d_k, d_v, dtype, transposed,
                   by_channel) <= _VMEM_BUDGET))


def _head_block(heads, key_heads, chunk, d_k, d_v, dtype, by_channel=False):
    """Heads a program: the largest divisor of ``heads`` in whole groups of
    ``heads / key_heads`` (a program reads its heads' key heads whole) that
    ``_walk_vmem`` finds within ``_VMEM_BUDGET`` in the transposed kernel,
    which holds the most, at a trip of one group (at 64 x 128 / 128 in
    bfloat16, two heads a key head, 0.66 MB a head: 16 of 32 heads; at 64 x
    96 / 192 0.97 MB: 10 of 30; with a decay a channel, ``gamma`` and its
    cotangent (chunk, d_k) float32 a head, 0.79 MB at 64 x 128 / 128: 8 of
    32)."""
    group = heads // key_heads
    return max((n for n in range(group, heads + 1, group)
                if heads % n == 0 and _walk_vmem(
                    n, group, group, chunk, d_k, d_v, dtype, True, by_channel)
                <= _VMEM_BUDGET), default=0)


def walk_form(interpret, heads, key_heads, chunk, d_k, d_v, dtype,
              by_channel=False):
    """``(interpret, heads a program, why)``: how a trace walks a row's
    chunks.  The kernels (``interpret`` False, or True where a test asked
    for the Pallas interpreter) on a TPU backend with no mesh axis left to
    the partitioner (Mosaic kernels cannot be partitioned automatically),
    for a chunk of whole 8-row tiles where the blocks of one key head's
    group of value heads fit the budget; ``(None, 0, why)``, the
    ``lax.scan``, anywhere else."""
    if interpret is None:
        backend = jax.default_backend()
        if backend != "tpu":
            return None, 0, f"backend is {backend}; the kernels compile for tpu"
        if _free_axes() is not None:
            return None, 0, "a mesh axis is left to the partitioner"
    if chunk % 8:
        return None, 0, f"a chunk of {chunk} is no multiple of 8"
    head_block = _head_block(heads, key_heads, chunk, d_k, d_v, dtype,
                             by_channel)
    if not head_block:
        return None, 0, (f"the blocks of {heads // key_heads} head(s) a key "
                         f"head at {chunk} x {d_k} / {d_v} pass "
                         f"{_VMEM_BUDGET} bytes of VMEM")
    return bool(interpret), head_block, (
        "interpret=True requested" if interpret else
        "backend is tpu" if interpret is None else "interpret=False requested")


def _for_trips(heads, group, trip, phases):
    """``phases`` over a program's ``heads`` value heads, ``trip`` of them a
    trip of one ``fori_loop``: in a trip each phase runs for every one of
    its heads before the next begins, as a loop of its own that is unrolled
    where the kernel is lowered and not where it is traced (a body written
    out for 16 or 10 heads, or a phase for 8, is seconds of tracing in the
    benchmark's process).  A phase is ``(function of (key head, value head,
    staging slot), stride)``: a slot is the head's place in its trip, and a
    stride of ``group`` runs the function once a key head, on its first
    value head."""
    def run(i, carry):
        for phase, stride in phases:
            def head(n, carry, phase=phase, stride=stride):
                a = n * stride
                # The slot's key head (``//`` of a traced index is a dozen
                # operations to lower).
                j = n if stride == group else lax.div(a, group)
                phase(i * (trip // group) + j, i * trip + a, a)
                return carry
            lax.fori_loop(0, trip // stride, head, 0, unroll=True)
        return carry
    lax.fori_loop(0, heads // trip, run, 0)


def _gate(ref, h):
    """Value head ``h``'s (1, chunk) row of a program's block of gates."""
    return ref[0, pl.ds(h, 1), :]


def _walk_forward_body(t_ref, qk_ref, q_ref, k_ref, v_ref, gamma_ref,
                       beta_ref, o_ref, *rest, group, trip, by_channel=False):
    """One chunk of a program's heads: ``_head_step``, a phase at a time
    over a trip's heads, the state in the VMEM scratch ``state_ref`` from
    the row's first chunk to its last, where it leaves as the row's final
    state; ``rest`` begins with the block the chunk's incoming state is
    saved to where a backward pass will read it.  ``by_channel``: a head's
    ``gamma`` is its (chunk, d_k) block and not a row of the gates'."""
    *saved, final_ref, state_ref, rhs_ref, o_s0_ref, u_ref = rest
    c = pl.program_id(2)
    dtype = v_ref.dtype
    masks = _masks(t_ref.shape[-1])
    key_masks = _masks(k_ref.shape[-1]) if by_channel else None

    @pl.when(c == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def reads(j, h, a):         # what the chunk reads of S_0
        into = jnp.exp(gamma_ref[h]) if by_channel \
            else _into(_gate(gamma_ref, h), masks)
        state = state_ref[h]
        if saved:
            saved[0][h] = state
        s0 = state.astype(dtype)
        rhs_ref[a] = _chunk_reads(k_ref[j], v_ref[h], s0, into)[1]
        o_s0_ref[a] = _state_read(q_ref[j], s0, into)

    def writes(j, h, a):        # what its positions write
        u_ref[a] = _chunk_writes(t_ref[h], _gate(beta_ref, h),
                                 rhs_ref[a]).astype(dtype)

    def leaves(j, h, a):        # what it leaves
        if by_channel:
            to_end, carry = _channel_decays(gamma_ref[h], key_masks)
        else:
            _, to_end, carry = _decays(_gate(gamma_ref, h), masks)
        state_ref[h], o_ref[h] = _chunk_leaves(
            state_ref[h], qk_ref[h], k_ref[j], u_ref[a], o_s0_ref[a], to_end,
            carry)

    _for_trips(state_ref.shape[0], group, trip,
               ((reads, 1), (writes, 1), (leaves, 1)))

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        final_ref[...] = state_ref[...]


def _walk_transposed_body(t_ref, qk_ref, q_ref, k_ref, v_ref, gamma_ref,
                          beta_ref, saved_ref, do_ref, dfinal_ref, dt_ref,
                          dqk_ref, dq_ref, dk_ref, dv_ref, dgamma_ref,
                          dbeta_ref, dstate_ref, rhs_ref, *stages, group,
                          trip, by_channel=False):
    """``_head_step``'s transpose for one chunk of a program's heads, the
    grid's last index counting the chunks from a row's end: the cotangent
    ``dS`` of the state the chunk leaves is in the VMEM scratch, started
    from the final state's, and ``U`` is made again from the chunk's saved
    incoming state.  With ``R = V - diag(exp gamma) K S_0`` and ``K~ =
    diag(exp(gamma_C - gamma)) K``::

        d(M * Q K^T) = dO U^T        dU = (M * Q K^T)^T dO + K~ dS
        dq = diag(exp gamma) dO S_0^T        dK~ = U dS^T
        d(T diag(beta)) = dU R^T     dR = (T diag(beta))^T dU = dv
        d(K S_0) = -diag(exp gamma) dR
        dk = diag(exp(gamma_C - gamma)) dK~ + d(K S_0) S_0^T
        dS_0 = exp(gamma_C) dS + (diag(exp gamma) Q)^T dO + K^T d(K S_0)

    the two with ``T`` and ``dT``'s float32 operands, float32 sums
    everywhere; dq and dk summed over a key head's ``group`` value heads
    here, and the gates' cotangents as rows: ``dbeta`` the column sums of
    ``d(T diag(beta)) * T``, ``dgamma`` row sums of products made here
    anyway (of ``dO S_0^T * Q``, ``dR * K S_0``, ``dK~ * K~``), ``gamma_C``'s
    (with ``<dS, S_0>``) on ``gamma``'s last entry.

    ``by_channel`` (``gamma`` (chunk, d_k) a head; ``G = exp gamma``, ``E =
    exp(gamma_C - gamma)``, ``R = V - (K * G) S_0``, ``K~ = K * E``)::

        d(Q * G) = dO S_0^T          dq = G * d(Q * G)
        dK~ = U dS^T                 dk = E * dK~ + G * d(K * G)
        d((K * G) S_0) = -dR         d(K * G) = -dR S_0^T
        dgamma = d(Q * G) * Q * G + d(K * G) * K * G - dK~ * K~
        dS_0 = diag(exp gamma_C) dS + (Q * G)^T dO - (K * G)^T dR

    ``gamma_C``'s a channel (the column sums of ``dK~ * K~`` and the row
    sums of ``dS * S_0``, times ``exp gamma_C``) on ``gamma``'s last row;
    the rest as above, and ``K S_0`` is not staged."""
    dtype = v_ref.dtype
    masks = diagonal, last = _masks(t_ref.shape[-1])
    if by_channel:
        du_ref, u_ref, dks_ref, dq_sum, dk_sum = stages
        key_masks = _masks(k_ref.shape[-1])
        last_row = lax.broadcasted_iota(
            jnp.int32, (t_ref.shape[-1], 1), 0) == t_ref.shape[-1] - 1
    else:
        ks_ref, du_ref, u_ref, dks_ref, dq_sum, dk_sum = stages

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = dfinal_ref[...]

    def reads(j, h, a):         # U again: K S_0 and R
        into = _into(_gate(gamma_ref, h), masks)
        ks_ref[a], rhs_ref[a] = _chunk_reads(
            k_ref[j], v_ref[h], saved_ref[h].astype(dtype), into)

    def writes(j, h, a):
        u_ref[a] = _chunk_writes(t_ref[h], _gate(beta_ref, h),
                                 rhs_ref[a]).astype(dtype)

    def cotangents(j, h, a):    # what meets dO and dS
        into, to_end, carry = _decays(_gate(gamma_ref, h), masks)
        state, dstate = saved_ref[h], dstate_ref[h]
        s0, ds = state.astype(dtype), dstate.astype(dtype)
        q, k, u, do = q_ref[j], k_ref[j], u_ref[a], do_ref[h]
        dqk_ref[h] = _dot(do, u, -1, -1).astype(dqk_ref.dtype)
        du_ref[a] = _dot(qk_ref[h], do, -2, -2) \
            + _dot((to_end * k).astype(dtype), ds, -1, -2)
        dk_out = _dot(u, ds, -1, -1)
        dq = _dot(do, s0, -1, -1)
        dq_sum[a], dk_sum[a] = into * dq, to_end * dk_out
        dto_end = jnp.sum(dk_out * k, axis=1, keepdims=True) * to_end
        dend = jnp.sum(dto_end, axis=0, keepdims=True) \
            + carry * jnp.sum(dstate * state, axis=(0, 1), keepdims=True)
        dgamma_ref[0, pl.ds(h, 1), :] = _row(
            jnp.sum(dq * q, axis=1, keepdims=True) * into - dto_end,
            diagonal) + jnp.where(last, dend, 0.0)

    def through_writes(j, h, a):        # through U = T diag(beta) R
        into = None if by_channel else _into(_gate(gamma_ref, h), masks)
        t, beta, du = t_ref[h], _gate(beta_ref, h), du_ref[a]
        dtb = _dot32(du, rhs_ref[a], -1, -1)
        dt_ref[h] = dtb * beta
        dbeta_ref[0, pl.ds(h, 1), :] = jnp.sum(dtb * t, axis=0, keepdims=True)
        drhs = _dot32(t * beta, du, -2, -2)
        dv_ref[h] = drhs.astype(dv_ref.dtype)
        if by_channel:      # d((K * G) S_0) = -dR, the decay in the operand
            dks_ref[a] = (-drhs).astype(dtype)
            return
        dks_ref[a] = (-into * drhs).astype(dtype)
        dgamma_ref[0, pl.ds(h, 1), :] -= _row(
            jnp.sum(drhs * ks_ref[a], axis=1, keepdims=True) * into, diagonal)

    def through_reads(j, h, a):         # through K S_0, and on to dS_0
        into, _, carry = _decays(_gate(gamma_ref, h), masks)
        q, k, dks = q_ref[j], k_ref[j], dks_ref[a]
        dk_sum[a] += _dot(dks, saved_ref[h].astype(dtype), -1, -1)
        dstate_ref[h] = (carry * dstate_ref[h]
                         + _dot((into * q).astype(dtype), do_ref[h], -2, -2)
                         + _dot(k, dks, -2, -2))

    def sums(j, h, a):          # a key head's, from its first value head
        dq_ref[j] = sum(dq_sum[a + i] for i in range(group)) \
            .astype(dq_ref.dtype)
        dk_ref[j] = sum(dk_sum[a + i] for i in range(group)) \
            .astype(dk_ref.dtype)

    def channel_reads(j, h, a):
        rhs_ref[a] = _chunk_reads(
            k_ref[j], v_ref[h], saved_ref[h].astype(dtype),
            jnp.exp(gamma_ref[h]))[1]

    def channel_cotangents(j, h, a):
        gamma = gamma_ref[h]
        into = jnp.exp(gamma)
        to_end, carry = _channel_decays(gamma, key_masks)
        state, dstate = saved_ref[h], dstate_ref[h]
        s0, ds = state.astype(dtype), dstate.astype(dtype)
        q, k, u, do = q_ref[j], k_ref[j], u_ref[a], do_ref[h]
        dqk_ref[h] = _dot(do, u, -1, -1).astype(dqk_ref.dtype)
        du_ref[a] = _dot(qk_ref[h], do, -2, -2) \
            + _dot((to_end * k).astype(dtype), ds, -1, -2)
        dk_out = _dot(u, ds, -1, -1)
        dq = _dot(do, s0, -1, -1)
        dq_sum[a], dk_sum[a] = into * dq, to_end * dk_out
        dto_end = dk_out * k * to_end
        dend = jnp.sum(dto_end, axis=0, keepdims=True) + _row(
            carry * jnp.sum(dstate * state, axis=1, keepdims=True),
            key_masks[0])
        dgamma_ref[h] = dq * q * into - dto_end \
            + jnp.where(last_row, dend, 0.0)

    def channel_through_reads(j, h, a):
        gamma = gamma_ref[h]
        into, carry = jnp.exp(gamma), _channel_carry(gamma, key_masks)
        q, k, dks = q_ref[j], k_ref[j], dks_ref[a]
        dkg = _dot(dks, saved_ref[h].astype(dtype), -1, -1)
        dk_sum[a] += into * dkg
        dgamma_ref[h] += dkg * k * into
        dstate_ref[h] = (carry * dstate_ref[h]
                         + _dot((into * q).astype(dtype), do_ref[h], -2, -2)
                         + _dot((into * k).astype(dtype), dks, -2, -2))

    if by_channel:
        reads, cotangents, through_reads = (
            channel_reads, channel_cotangents, channel_through_reads)
    _for_trips(dstate_ref.shape[0], group, trip, (
        (reads, 1), (writes, 1), (cotangents, 1), (through_writes, 1),
        (through_reads, 1), (sums, group)))


def _walk_call(body, name, ins, outs, heads, reverse, interpret,
               by_channel=False):
    """``pallas_call`` of a walk: grid (batch, head blocks, chunks), the
    chunks in order (from a row's end where ``reverse``), on ``ins`` and for
    ``outs`` (ShapeDtypeStructs).  Of a stacked array ``(n, b, x, ...)`` a
    block is the chunk's rows of a program's share of ``x``: ``heads`` of
    the value heads, their key heads of q and k (the same block index on
    fewer heads: no repeat), the one block of ``heads`` rows of the gates
    ``(n, b, blocks, heads, chunk)``; of a row's one state ``(b, h, d_k,
    d_v)`` it is those heads', the same for every chunk.  ``by_channel``:
    ``gamma`` (and its cotangent) is ``(n, b, h, chunk, d_k)``, a block of
    ``heads`` of its heads as v's is."""
    t, _, q, _, v = ins[:5]
    n, b, h = t.shape[:3]
    blocks, group = h // heads, h // q.shape[2]
    chunk, d_k, d_v = t.shape[-1], q.shape[-1], v.shape[-1]
    trip = _trip_heads(heads, group, chunk, d_k, d_v, v.dtype, reverse,
                       by_channel)

    def spec(x):
        if x.ndim == 4:
            return pl.BlockSpec((None, heads) + x.shape[2:],
                                lambda i, j, c: (i, j, 0, 0))
        return pl.BlockSpec(
            (None, None, x.shape[2] // blocks) + x.shape[3:],
            lambda i, j, c: ((n - 1 - c if reverse else c), i, j, 0, 0))

    return pl.pallas_call(
        functools.partial(body, group=group, trip=trip,
                          by_channel=by_channel),
        grid=(b, blocks, n),
        in_specs=[spec(x) for x in ins], out_specs=[spec(x) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, d_k, d_v), jnp.float32)] + [
            pltpu.VMEM(*stage) for stage in _stages(
                trip, chunk, d_k, d_v, v.dtype, reverse, by_channel)],
        # Only the walk over the chunks carries the scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)(*ins)


def _gate_rows(x, heads):
    """(n, b, h, chunk) -> (n, b, h / heads, heads, chunk): a program's gates
    one whole block of 64-lane rows."""
    n, b, h, chunk = x.shape
    return x.reshape(n, b, h // heads, heads, chunk)


# Inlined ``jit``s, as ``_chunked_rule`` is and for its reason: a layer's
# backward pass traces the forward rule and the transposed walk apart from
# ``_chunked_rule``'s equations, and with these every layer after the first
# takes the first's kernel, traced once and lowered to Mosaic once.
@functools.partial(jax.jit, inline=True,
                   static_argnames=("head_block", "interpret", "save"))
def _walk_forward(terms, head_block, interpret, save):
    """``(final state, o, each chunk's incoming state or None)``."""
    t, _, q, _, v, gamma, beta = terms
    n, b, h = t.shape[:3]
    by_channel = gamma.ndim == 5
    ins = terms[:5] + (gamma if by_channel
                       else _gate_rows(gamma, head_block),
                       _gate_rows(beta, head_block))
    state = _sds((b, h, q.shape[-1], v.shape[-1]), jnp.float32, *terms)
    outs = [_sds(v.shape, v.dtype, *terms)]
    if save:
        outs.append(_sds((n,) + state.shape, jnp.float32, *terms))
    o, *saved, final = _walk_call(
        _walk_forward_body, "kda_walk_fwd" if by_channel else "gdn_walk_fwd",
        ins, outs + [state], head_block, False, interpret, by_channel)
    return final, o, (saved[0] if save else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _walk(t, qk, q, k, v, gamma, beta, head_block, interpret):
    """``(final state, o)`` of the scan over the chunks, as kernels: the
    stacked terms as ``_chunk_terms`` and ``chunked`` leave them, ``t`` and
    ``qk`` ``(n, b, h, chunk, chunk)``, q and k ``(n, b, key heads, chunk,
    d_k)``, v ``(n, b, h, chunk, d_v)``, ``gamma`` and ``beta`` ``(n, b, h,
    chunk)``."""
    return _walk_forward((t, qk, q, k, v, gamma, beta), head_block, interpret,
                         save=False)[:2]


def _walk_fwd(t, qk, q, k, v, gamma, beta, head_block, interpret):
    terms = (t, qk, q, k, v, gamma, beta)
    final, o, saved = _walk_forward(terms, head_block, interpret, save=True)
    return (final, o), (terms, saved)


def _walk_bwd(head_block, interpret, res, cotangents):
    return _walk_transposed(*res, *cotangents, head_block=head_block,
                            interpret=interpret)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("head_block", "interpret"))
def _walk_transposed(terms, saved, dfinal, do, head_block, interpret):
    """The cotangents of ``_walk``'s seven terms from those of its final
    state and ``o``."""
    by_channel = terms[5].ndim == 5
    rows = [terms[5] if by_channel else _gate_rows(terms[5], head_block),
            _gate_rows(terms[6], head_block)]
    ins = terms[:5] + tuple(rows) + (saved, do, dfinal)
    *dterms, dgamma, dbeta = _walk_call(
        _walk_transposed_body,
        "kda_walk_bwd" if by_channel else "gdn_walk_bwd", ins,
        [_sds(x.shape, x.dtype, *ins) for x in terms[:5] + tuple(rows)],
        head_block, True, interpret, by_channel)
    return (*dterms, dgamma.reshape(terms[5].shape),
            dbeta.reshape(terms[6].shape))


# optimize_remat: under ``jax.checkpoint`` the forward pass proper runs
# ``_walk`` itself, which saves no states (a kernel's unread output cannot be
# dropped: 268 MB a layer written for nothing otherwise); the forward rule
# runs where the backward pass makes the walk again.
_walk.defvjp(_walk_fwd, _walk_bwd, optimize_remat=True)


def _chunk_terms(q, k, g, beta, dtype):
    """What of a chunk is made for every chunk at once, the ``chunk`` x
    ``chunk`` matrices a value head: ``T = (I + A)^-1`` in float32, ``M * Q
    K^T`` in ``dtype`` and ``gamma``, the log decays summed from the chunk's
    start, float32 (n, b, h, chunk).  ``g`` and ``beta`` are (n, b, h,
    chunk) in float32, ``q`` and ``k`` (n, b, key heads, chunk, d_k): ``K
    K^T`` and ``Q K^T`` are computed a key head and met by its value heads'
    decays as a broadcast.  Either walk takes these with q, k, v and
    ``beta`` as they are and makes the ``chunk`` x d terms a chunk at a
    time (``_head_step``)."""
    if g.ndim == 5:
        return _chunk_terms_by_channel(q, k, g, beta, dtype)
    chunk = g.shape[-1]
    group = g.shape[2] // k.shape[2]

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
    qk = decay * of_value_heads(_mm("nbhid,nbhjd->nbhij", q, k, dtype))
    return t, qk.astype(dtype), gamma


def _chunk_terms_by_channel(q, k, g, beta, dtype):
    """``_chunk_terms`` with a decay a channel, ``g`` (n, b, h, chunk, d_k):
    ``A_ij = beta_i sum_c k_ic k_jc exp(gamma_ic - gamma_jc)`` and ``P_ij =
    sum_c q_ic k_jc exp(gamma_ic - gamma_jc)`` are no masked products, so
    the decay rides on the operands, a sub-block of ``SUB_BLOCK`` positions
    at a time: with ``r_I`` the summed log decay at sub-block ``I``'s first
    position, rows ``i`` in ``I`` take ``exp(gamma_i - r_I)`` <= 1 and
    columns ``j`` in ``J`` <= ``I`` take ``exp(r_I - gamma_j)``, <= 1 for an
    earlier sub-block and at most ``exp(-GATE_LOWER_BOUND x (SUB_BLOCK -
    1))`` inside ``I`` itself; one product a pair of sub-blocks (q's rows
    and k's share the columns), float32 sums, what lies above the diagonal
    masked after.  The columns' operand is ``chunk / SUB_BLOCK`` copies of k
    in ``dtype``, each under another ``r_I``; nothing is ``chunk`` x
    ``chunk`` x d_k.  Gives ``T``, ``P`` in ``dtype`` and ``gamma`` (n, b,
    h, chunk, d_k)."""
    n, b, h, chunk, d_k = g.shape
    group = h // k.shape[2]
    sub = _sub_block(chunk)
    m = chunk // sub

    def sub_blocks(x):  # (n, b, key heads, chunk, d) -> (n, b, h, m, sub, d)
        x = x if group == 1 else jnp.repeat(x, group, axis=2)
        return x.reshape(n, b, h, m, sub, d_k)

    gamma = jnp.cumsum(g, axis=-2)
    by_block = gamma.reshape(n, b, h, m, sub, d_k)
    first = by_block[..., :1, :]
    # (I, i) rows against (I, c) columns: the chunk's positions c as
    # sub-block I's rows meet them, nothing where c lies in a later one.
    earlier = (jnp.arange(m)[:, None] >= jnp.arange(chunk) // sub)[..., None]
    columns = jnp.exp(jnp.where(
        earlier, first - gamma[:, :, :, None], -jnp.inf))
    rows = jnp.exp(by_block - first)
    q, k = sub_blocks(q), sub_blocks(k)
    both = jnp.einsum(
        "nbhIid,nbhIcd->nbhIic",
        jnp.concatenate([k * rows, q * rows], axis=-2).astype(dtype),
        (k.reshape(n, b, h, 1, chunk, d_k) * columns).astype(dtype),
        preferred_element_type=jnp.float32)
    kk, qk = (x.reshape(n, b, h, chunk, chunk)
              for x in (both[..., :sub, :], both[..., sub:, :]))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    a = jnp.where(jnp.tril(lower, -1), beta[..., None] * kk, 0.0)
    with jax.named_scope("inverse"):
        t = unit_lower_inverse(a)
    return t, jnp.where(lower, qk, 0.0).astype(dtype), gamma


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
