"""Flash attention: fused blockwise attention as Pallas TPU kernels.

The per-chip hot op for every transformer in the zoo, and the per-block
compute of ring attention (``parallel/ring_attention.py``). K/V stream
through VMEM one block per grid step (3-D grid; online-softmax accumulators
live in VMEM scratch), so neither the (seq x seq) score matrix nor the full
K/V sequence is VMEM-resident — the long-context regime stays within the
~16MB/core budget.

Under ``causal`` a program does for each part of its score tile only what
that part's place against the diagonal needs.  A block whose every score is
masked is not run (one test a program).  A block that is run is seen as
sub-tiles of ``_SUB_TILE`` keys (512; one where the block is no longer than
that, and then nothing below applies), and the program's own positions
decide among three forms (``_for_keys``, ``_walk``; the offsets are
scalar-prefetch operands, so ring attention's traced ones are served by the
same rule).  Wholly below the diagonal: the block whole, without the mask's
arithmetic (no iota, compare, bias or ``where``).  Every sub-tile holding a
seen score: the block whole and masked, as before the walk.  A sub-tile
wholly above the diagonal (the masked quarter of a 1,024-token row under
blocks of 512 x 1,024; its scores would have added exact zeros): a loop on
the device over the sub-tiles that hold a seen score, each masked, the rest
not run; the dk/dv kernel cuts its k side the same way, a sub-tile of keys
updating its own slice of the dk and dv accumulators.  A block is kept whole
wherever nothing in it is skipped because what a step costs beside its tile
(the forward's row statistics above all: 1.5 us a step of 512 rows on the
v5e whatever the step's width, PERF.md PR 32) is paid once a step, not once
a program.  Where the loop runs, the forward's running maximum and rescale
step once a sub-tile: the same f32 sums in another order, no more.  Not
causal, or one sub-tile a block, the bodies are those of a program with no
walk, instruction for instruction.  Interpreted (``interpret=True``, the CPU
tests) nothing is skipped, the interpreter losing scratch writes under a
skipped conditional: the loop takes every sub-tile through the masked
arithmetic, whose ``where`` keeps a masked score's weight exactly zero.  The
``flash`` event and the gauges ``flash.causal_subtiles_visited`` /
``flash.causal_subtiles_total`` say what a call's shape and offsets make of
the walk (``_causal_plan``).

A program that is not run fetches nothing either (``_Sweep``).  The grid's
innermost dimension runs over the k blocks of a q block (forward, dq) or the
q blocks of a k block (dk/dv), and what an outer block sees of them follows
from the offsets, the block lengths and the window (``_reach``, which
``_visible`` and ``_walk`` read too).  The index map of every operand on the
inner side (k, v, the shared rotary key; q, ``do``, ``lse``, ``delta`` and
the rotary query for dk/dv) gives a program the grid's block where it holds a
seen score and else a neighbour's seen block (before the first, the first;
past the last, the block the next outer block starts on, which is then
copied behind the last body that ran), reading the offsets from the
scalar-prefetch operand, and the pipeline issues no copy where consecutive
programs map to the same block: an empty program costs a grid step and no
bytes.  Under a window the grid's
inner dimension is itself only as long as the most blocks any outer block
sees, a step's block ``first(outer) + j``.  A body takes its positions from
that nominal block, never from the clamped one, so where the two differ the
program is empty: skipped on the chip, masked to exact zeros by the
interpreter.  The same visible blocks in the same order: results are bit for
bit those of the whole rectangle.  A call that is not causal builds the grid
and maps it always built, and so does a causal one whose every program
holds a seen score (a row of 1,024 keys in one k block), which the maps'
scalar work would only slow.  Gauges ``flash.inner_blocks_fetched`` /
``flash.inner_blocks_total``.

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

The kernels read their operands in one of two layouts, chosen from the shape
and never by an argument.  **Packed**: ``(batch, s, heads x d)``, what the
projection matmuls write and the output projection and the weight-gradient
matmuls read.  A block is ``(rows, block, lanes)`` with ``lanes`` = lcm(d,
128), whole heads (two at d = 64, one at d = 128); the block's index along the
last axis picks the heads, so the pipeline's own transfers do what the head
split's transpose did and no copy of q, k, v, ``do`` or a result is run around
the kernels.  Inside a program a head is ``d`` of the block's lanes: its
products run over the whole width with the other heads' lanes zeroed on one
operand (exact zeros; at d = 64 a product fills half the MXU either way), the
accumulators are lane-dense ``(block, lanes)`` tiles, and the head is an index
of the loop on the device, so the body is traced once whatever the heads.
**Split**: ``(batch, heads, s, d)`` flattened to ``(batch x heads, s, d)``,
one head a block of ``d`` lanes: the same body with nothing to mask.  The rule
(``_heads_per_block``, ``make_flash_attn_fn``): packed where ``heads x d``
splits into blocks of lcm(d, 128) lanes that hold several heads and no mesh
axis splits the heads; split otherwise (25 heads of 64; heads over ``model``;
heads of 128, a block each, whose transposes cost less than packed blocks'
short rows cost the kernels), and for every caller of ``flash_attention(q,
k, v)`` itself: ring attention's ``block_attn_fwd`` / ``block_attn_bwd``,
Ulysses, the chip smoke.  Where the rule says split, ``models.layers.mha``
and the compiled step are what they were.

The row statistics (``lse``, ``delta``) are ``(batch, heads, s, 1)`` to a
caller in both layouts.  In the packed layout they cross HBM with the
sequence along the lanes: the kernels' own arrays are ``(batch, heads, 1,
s)``, 4 bytes a value where a last dimension of 1 occupies a 128-lane row
each (the reshape between the two is XLA's and moves nothing), and no
operand, result or scratch of a packed kernel has a last dimension of 1.  The
split layout still keeps ``(batch x heads, s, 1)`` in HBM, which is not
where it should end: lane-dense there too was built and measured, and one
cell's step fell for a reason outside this file (PERF.md section 6, PR 45;
ROADMAP S4 item 1 has what must come first).  ``_Layout.shape`` / ``block``
/ ``spec`` hold the difference, and ``_stat_rows``, ``_stat_columns`` and
the forward's last store read or write either block; a body does nothing
else by it.  ``flash_bwd_dkv`` computes its scores transposed,
keys down the sublanes and queries along the lanes (``s^T = k . q^T``), so
``lse`` and ``delta`` are ``(1, block_q)`` rows that broadcast down the
sublanes (the packed layout's as they were read, the split layout's columns
turned once a step: ``_stat_rows``), and all four of its products are plain
ones.  ``flash_fwd`` and ``flash_bwd_dq`` keep queries down the sublanes,
where their second products stream a block's q rows through the MXU:
``flash_fwd`` holds its running maximum and sum lane-replicated, ``(block_q,
128)`` with every lane of a row the row's value (``_across`` tiles it to a
tile's width: no lane broadcast a step), and turns ``lse`` to what crosses
HBM once a q block (``_as_rows``); ``flash_bwd_dq`` turns the packed
layout's rows to such columns (``_stat_columns``, ``_as_columns``).  A
compiled call's block of q rows is whole 128-lane tiles or the whole row;
any other says so in the log and takes the dense path (the interpreter takes
any block).

A program of the grid handles ``G`` (batch, head) rows, batch rows x the
heads of a block: where a row's score tile is small (128 x 128 at s = 128)
most of a one-row program is what a program costs whatever it computes, so
rows share a program until its tiles are as large as a long-sequence
program's or VMEM is full (``_rows_per_program``; the operands' shape alone
decides, and from s = 1,024 ``G`` is the heads of one block: the split
layout's one-row program unchanged).  Inside, a loop on the device takes as
many rows a step as fit the vector registers, through the same arithmetic
with a leading rows axis (``_for_rows``).  A row's results do not depend on
``G``, nor on the layout beyond the order of a sum: on the v5e the packed
kernels' o, dq, dk, dv at d = 64 are bit for bit the split ones' (PERF.md,
PR 28).

**The two-product form** (``flash_attention_two_product``; latent attention,
``models.layers.mla``).  The same three kernels take a score that is the sum
of two products, ``(q . k + q_rope . k_rope) * scale`` with the scale given:
the second over a width of its own against ONE key a position that all heads
of a batch row read (its block's index is the program's batch row, a
``lax.div`` of the grid's), and values, an accumulator and a result of the
values' own width.  Operands stay apart in HBM as the projections leave them:
nothing is concatenated to a common key, broadcast over the heads or padded.
The bodies read the two extra operands where a ``scale`` is passed and are the
one-product bodies, instruction for instruction, where it is not.  Split
layout, one (batch, head) row a program; the dk/dv kernel writes each head's
part of the shared key's gradient and XLA sums the heads.

Off TPU the dense jnp path runs instead (CPU tests use ``interpret=True``
to exercise the kernels in the Pallas interpreter); every trace logs once,
at info, which path it took and why.
"""
import dataclasses
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


def _kernels_serve(interpret, sq, block_q):
    """``interpret`` where the kernels serve blocks of ``block_q`` of a
    row's ``sq`` queries, None (the dense path, with a line in the log) where
    a compiled call's block of q rows is neither whole 128-lane tiles nor
    the whole row: the kernels turn a block's statistics between columns and
    rows (``_as_rows``, ``_as_columns``) by transposes of whole 128-lane
    tiles, in both layouts.  The interpreter takes any; no cell and no
    caller on the chip has such a shape."""
    block_q = min(block_q, sq)
    if interpret is False and block_q % _LANES and block_q != sq:
        _log_path("dense", f"a block of {block_q} of a row's {sq} queries is "
                           f"neither whole {_LANES}-lane tiles nor the row: "
                           f"the kernels turn row statistics in such tiles")
        return None
    return interpret


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
# The keys of one sub-tile of a causal program's walk over its k block, capped
# by the block.  On the v5e a 512 x 512 tile not run saves 0.5 / 1.4 / 1.3 us
# a (batch, head) row (fwd / dq / dkv); sub-tiles of 256 visit 10 of a
# 1,024-token row's 16 and cost more than they save (10.3 / 4.9 / 9.3 us a
# row against 5.1 / 5.4 / 6.5 with no walk: a step of the forward's running
# softmax costs 1.5 us whatever its width; PERF.md, PR 32).
_SUB_TILE = 512
# The lanes a statistic is replicated over where queries lie down the
# sublanes (``flash_fwd``'s running maximum and sum, ``flash_bwd_dq``'s
# ``lse`` and ``delta``): one vector register's.
_LANES = 128
_announced = set()


def _padded_bytes(shape, dtype):
    """VMEM bytes of a block of ``shape``: the last dimension occupies whole
    128-lane rows (a width of 64 or of 1 as much as one of 128), the one
    before it whole tiles of 8 sublanes of 32 bits (a statistic's ``(1,
    block_q)`` row eight times its values)."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    *lead, rows, lanes = shape
    return (math.prod(lead) * -(-rows // sublanes) * sublanes
            * -(-lanes // 128) * 128 * itemsize)


def _rows_per_program(units, block_q, block_k, blocks, scratch, heads=1):
    """``(G, vmem_bytes)``: how many (batch, head) rows one program handles,
    and the VMEM those ``G`` rows occupy.

    ``units`` is what may share a program: the batch x heads rows of the
    split layout, the batch rows of the packed one, where a unit is the
    ``heads`` heads of a 128-lane block and ``G`` counts batch rows x heads.
    ``blocks`` are one unit's pipelined in/out blocks (double-buffered),
    ``scratch`` its accumulators and statistics, each ``(shape, dtype)``.
    ``G`` is ``heads`` times the largest divisor of ``units`` whose score
    tiles stay within ``_MAX_TILE`` and whose padded VMEM stays within
    ``_VMEM_BUDGET``; one unit where not even one does (the blocks are the
    caller's choice, a block's lanes the layout's)."""
    vmem_a_unit = (2 * sum(_padded_bytes(*b) for b in blocks)
                   + sum(_padded_bytes(*b) for b in scratch))
    most = min(_MAX_TILE // (heads * block_q * block_k),
               _VMEM_BUDGET // vmem_a_unit)
    n = max((n for n in range(1, most + 1) if units % n == 0), default=1)
    return n * heads, n * vmem_a_unit


@dataclasses.dataclass(frozen=True)
class _Layout:
    """How a call's operands reach the kernels: the one difference between
    the two layouts, read by the code that builds a ``pallas_call`` and
    never by a kernel's body, which sees only its blocks.

    split: ``q`` is ``(batch, heads, s, d)`` and the kernels' arrays are
    ``(batch x heads, s, d)``: one head a block, ``d`` lanes, a unit of the
    grid's first dimension one (batch, head) row, the row statistics
    ``(batch x heads, s, 1)`` (still: the module's docstring).

    packed: ``q`` is ``(batch, s, heads, d)``, the projections' own
    ``(batch, s, heads x d)`` seen as heads, and the kernels' arrays are
    just that: a block holds ``lanes`` = lcm(d, 128) lanes, ``lanes / d``
    whole heads (two at d = 64, one at d = 128), a unit is one batch row's
    heads of one such block, and the block's index along the last axis picks
    the heads, so the pipeline's transfers do what a transpose did.  The row
    statistics are ``(batch, heads, 1, s)``, the sequence along the lanes, a
    block the ``lanes / d`` heads of its unit.  A caller sees ``(batch,
    heads, s, 1)`` in both."""
    packed: bool
    batch: int
    num_heads: int
    d: int

    @classmethod
    def of(cls, q, packed):
        if packed:
            batch, _, num_heads, d = q.shape
        else:
            batch, num_heads, _, d = q.shape
        return cls(packed, batch, num_heads, d)

    @property
    def name(self):
        return "packed" if self.packed else "split"

    @property
    def lanes(self):
        return math.lcm(self.d, 128) if self.packed else self.d

    @property
    def heads(self):
        """Heads a block."""
        return self.lanes // self.d

    @property
    def units(self):
        return self.batch if self.packed else self.batch * self.num_heads

    @property
    def lane_blocks(self):
        """Blocks along the arrays' last axis; a program's index in the
        grid's first dimension is (group of units) x lane_blocks + (lane
        block)."""
        return self.num_heads // self.heads if self.packed else 1

    def array(self, x):
        """``x`` (q, k, v, do) as the kernels read it: a reshape, no copy."""
        if self.packed:
            return x.reshape(x.shape[0], x.shape[1], -1)
        # -1: keys and values may hold fewer heads than q (grouped heads).
        return x.reshape(-1, x.shape[2], self.d)

    def stat(self, x):
        """A caller's row statistic ``(batch, heads, s, 1)`` as the kernels
        read it: a reshape, no copy."""
        return x.reshape(self.shape(x.shape[2], stat=True))

    def shape(self, length, stat=False, width=None):
        """A result of ``length`` positions, as the kernels write it;
        ``width`` lanes where the result has a width of its own (the split
        layout's two-product form: values, the rotary part)."""
        if stat:
            return ((self.batch, self.num_heads, 1, length) if self.packed
                    else (self.units, length, 1))
        if not self.packed:
            return self.units, length, width or self.d
        return self.batch, length, self.num_heads * self.d

    def result(self, x, stat=False):
        """A kernel's result in the layout ``q`` came in; a row statistic as
        a caller sees it, ``(batch, heads, s, 1)``."""
        if stat:
            return x.reshape(self.batch, self.num_heads, -1, 1)
        if self.packed:
            return x.reshape(self.batch, x.shape[1], self.num_heads, self.d)
        return x.reshape(self.batch, self.num_heads, x.shape[1], x.shape[2])

    def block(self, length, stat=False):
        """One unit's block of ``length`` positions; of a row statistic, a
        ``(1, length)`` row a head in the packed layout and a ``(length, 1)``
        column in the split one."""
        if stat:
            return (self.heads, 1, length) if self.packed else (length, 1)
        return length, self.lanes

    def spec(self, n, length, seq, stat=False, width=None, shared=False,
             sweep=None):
        """BlockSpec of ``n`` units' blocks whose position along the sequence
        is the grid's index number ``seq`` (1 or 2).  ``width`` is an
        operand's own lanes where they are not ``d`` (split layout only,
        one unit a program).  ``shared`` says which of the operand's rows a
        program reads where that is not its own unit's: True, the operand
        holds one row a batch row, which all of that row's heads read; an
        integer ``g``, one row for ``g`` consecutive units (a key-value
        head's query heads); ``(g, blocks)``, the grid's first index counts
        key-value heads and its index ``seq`` runs over the ``g`` query
        heads of one, ``blocks`` steps each (the dk/dv kernel's q side).
        ``sweep`` is given for an operand of a causal call's inner side
        (``seq`` is 2): its position is the block ``_Sweep.place`` maps the
        step to, by the offsets, which the map receives last."""
        across = self.lane_blocks

        def place(*grid):
            """``(the block's index along the arrays' first axis, along the
            sequence, along the packed layout's lane blocks)``."""
            i, j = grid[0], grid[seq]
            # lax, not ``//`` and ``%``: jnp's take a sign's care that costs
            # a step's lowering seconds over its hundreds of index maps.
            head = None
            if isinstance(shared, tuple):
                group, blocks = shared
                head, j = jax.lax.div(j, blocks), jax.lax.rem(j, blocks)
            if sweep is not None:
                j = sweep.place(grid[-1], grid[1], j)
            if head is not None:
                return i * group + head, j, 0
            if shared:
                return jax.lax.div(
                    i, self.num_heads if shared is True else shared), j, 0
            if across == 1:
                return i, j, 0
            return jax.lax.div(i, across), j, jax.lax.rem(i, across)

        def index(*grid):
            rows, j, block = place(*grid)
            if stat and self.packed:
                return rows, block, 0, j
            return rows, j, block
        if stat or (width is None and not shared):
            return pl.BlockSpec((n,) + self.block(length, stat), index)
        return pl.BlockSpec((n, length, width or self.d), index)


def _heads_per_block(num_heads, d):
    """Heads a block where the hook takes the packed layout, else None: the
    rule between the two layouts.  None where ``num_heads x d`` lanes do not
    split into blocks of lcm(d, 128) lanes, whole heads each (25 heads of
    64), and where a block would be one head (d = 128): there the head
    split's transposes are 128 lanes wide, run near the HBM's rate and fuse
    with rotary, and cost less than what the packed blocks' 256-byte rows
    add to the kernels (OLMoE at 4,096 x 128: 6.22 -> 6.74 ms of kernels and
    -1.5% tokens/s packed; PERF.md, PR 28).  The kernels themselves take one
    head a block in either layout."""
    heads = math.lcm(d, 128) // d
    return heads if heads > 1 and num_heads % heads == 0 else None


def _announce(kernel, layout, operand, sk, block_q, block_k, rows, vmem_bytes,
              offsets=None, group=1, window=None, sweep=None):
    """One info line a distinct kernel and shape, and with telemetry on the
    gauges ``flash.rows_per_program`` and ``flash.heads_per_block`` and a
    ``flash`` event: which layout the shape gave this call and which program
    the rule above made of it, read at trace time.  The line names the row
    statistics' array, and the gauge ``flash.stat_bytes_per_call`` carries
    the HBM bytes of ``lse`` as a call reads or writes it, padded as Mosaic
    lays it out (the packed layout's sequence in whole 128-lane rows; the
    split layout's a 128-lane row a value).  ``offsets`` are a causal
    call's ``(q_offset, k_offset)``: the line then ends with the sub-tiles a
    (batch, head) row visits by ``_causal_plan`` (decided on the device where
    an offset is traced), which the gauges ``flash.causal_subtiles_visited``
    and ``flash.causal_subtiles_total`` carry; both 0 where not causal.
    With ``group`` query heads a key-value head the line says so, and under a
    ``window`` it gives the window's own walk beside the causal one's (the
    gauges ``flash.window_subtiles_visited`` / ``_total``; 0 with no
    window).  ``sweep`` is a causal call's ``_Sweep``: the line then says how
    many inner blocks the grid steps through an outer block and, where the
    offsets are integers, how many of a (batch, head) row's inner blocks its
    programs run on (``_Sweep.fetched``: the rest are not copied, their
    programs mapped to a neighbour's block), which the gauges
    ``flash.inner_blocks_fetched`` / ``flash.inner_blocks_total`` carry; both
    0 where an offset is traced or the call is not causal."""
    sq = operand.shape[1]
    total = visited = in_window = fetched = inner_total = 0
    walk = "not causal: every score computed"
    if offsets is not None:
        sub = _sub_tile(True, block_k)
        walk = f"causal: sub-tiles of {block_q} x {sub}"
        if all(isinstance(offset, int) for offset in offsets):
            total, visited, masked = _causal_plan(sq, sk, block_q, block_k,
                                                  sub, *offsets)
            walk = (f"causal: {visited} of {total} sub-tiles of {block_q} x "
                    f"{sub} visited, {masked} masked")
            if window is not None:
                _, in_window, _ = _causal_plan(sq, sk, block_q, block_k, sub,
                                               *offsets, window=window)
                walk = (f"a window of {window} keys: {in_window} of {total} "
                        f"sub-tiles of {block_q} x {sub} visited, where the "
                        f"causal walk visits {visited}")
        else:
            walk += " visited by the offsets on the device"
            if window is not None:
                walk += f", inside a window of {window} keys"
    if sweep is not None:
        side = "q" if sweep.keys_outer else "k"
        walk += (f"; the grid steps through {sweep.extent} of an outer "
                 f"block's {sweep.blocks} {side} blocks")
        if all(isinstance(offset, int) for offset in offsets):
            fetched, inner_total = sweep.fetched(offsets)
            walk += (f", {fetched} of a row's {inner_total} {side} blocks "
                     f"fetched")
        else:
            walk += f", the {side} blocks fetched by the offsets on the device"
    if group > 1:
        walk += (f"; {group} query heads read one key-value head, "
                 f"{layout.num_heads // group} key-value heads in HBM")
    programs = (layout.units * layout.heads // rows * layout.lane_blocks
                * (sq // block_q) * (sk // block_k))
    if sweep is not None:       # the steps of a narrowed grid
        programs = programs // sweep.blocks * sweep.extent
    shape = ",".join(str(n) for n in operand.shape)
    stat = layout.shape(sq, stat=True)
    stat_bytes = math.prod(stat[:-1]) * -(-stat[-1] // _LANES) * _LANES * 4
    detail = (f"{kernel} {jnp.dtype(operand.dtype).name}[{shape}] "
              f"over {sk} keys: {layout.name} layout, {layout.heads} heads a "
              f"block of {layout.lanes} lanes, blocks {block_q} x {block_k}, "
              f"G = {rows} (batch, head) rows a program, {programs} programs "
              f"a call, {vmem_bytes} bytes of VMEM by the padded estimate, row "
              f"statistics f32[{','.join(str(n) for n in stat)}] "
              f"({stat_bytes} bytes a call in HBM); {walk}")
    _log_path("pallas", detail)
    from autodist_tpu import observability
    if not observability.enabled():
        return
    registry = observability.registry()
    registry.gauge("flash.rows_per_program").set(rows)
    registry.gauge("flash.heads_per_block").set(layout.heads)
    registry.gauge("flash.stat_bytes_per_call").set(stat_bytes)
    registry.gauge("flash.causal_subtiles_visited").set(visited)
    registry.gauge("flash.causal_subtiles_total").set(total)
    registry.gauge("flash.window_subtiles_visited").set(in_window)
    registry.gauge("flash.window_subtiles_total").set(
        total if window is not None else 0)
    registry.gauge("flash.inner_blocks_fetched").set(fetched)
    registry.gauge("flash.inner_blocks_total").set(inner_total)
    registry.gauge("flash.group").set(group)
    if detail not in _announced:
        _announced.add(detail)
        observability.record_event("flash", detail)


def _sub_tile(causal, block_k):
    """The keys a step of a program's walk over its k block takes: under
    ``causal`` a sub-tile of ``_SUB_TILE`` where the block holds several, the
    whole block otherwise (and then the bodies are those of a program with no
    walk, instruction for instruction)."""
    if causal and block_k > _SUB_TILE and block_k % _SUB_TILE == 0:
        return _SUB_TILE
    return block_k


def _whole(count, unit, most):
    """Whole units of ``unit`` positions in ``count`` of them, none for a
    negative count and no more than ``most``.  Python integers give integers
    (the plan, the gauges), the device's values traced ones (the bodies, the
    index maps): ``lax.div`` rounds towards zero, so a negative count gives at
    most 0."""
    if isinstance(count, int):
        return min(max(count, 0) // unit, most)
    return jnp.clip(jax.lax.div(count, unit), 0, most)


def _reach(start, length, window=None, keys=False):
    """``(lo, hi)``: the first and the last position of the other side with
    which a block of ``length`` positions from ``start`` holds a seen score,
    None where nothing bounds it.  Position t sees the keys s with ``t -
    window < s <= t`` (``s <= t`` with no window): a block of queries sees
    from its first row's window to its last row's diagonal; with ``keys`` the
    block is of keys, seen from its first key's diagonal to the last row whose
    window holds its last key.  The ONE definition of what a block sees:
    ``_visible`` (a program's tile), ``_walk`` (its sub-tiles) and
    ``_Sweep`` (the grid's inner dimension and its index maps) count blocks
    against these two positions."""
    last = start + length - 1
    if keys:
        return start, None if window is None else last + (window - 1)
    return None if window is None else start - (window - 1), last


def _least(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


def _most(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


@dataclasses.dataclass(frozen=True)
class _Sweep:
    """The innermost grid dimension of a causal call: which of the inner
    side's blocks an outer block's programs step through, and which block
    each step's inner-side operands are mapped to.  The forward and dq
    kernels' outer block is q's and the inner runs over k; with
    ``keys_outer`` (dk/dv) the outer is k's and the inner runs over the q
    blocks of a query head.  ``outer`` / ``block`` are the positions of an
    outer / inner block, ``outers`` / ``blocks`` their counts a row.

    An outer block holds a seen score with the inner blocks ``[behind,
    visited)`` (``seen``).  Its programs' NOMINAL inner blocks are ``first +
    j`` for the grid's ``j`` in ``[0, extent)``: with no window ``extent`` is
    the whole row and ``first`` 0, the grid it always was; under a window
    ``extent`` is the most blocks any outer block sees (exact where the
    offsets are integers, else what a span of ``outer + window - 1``
    positions can touch however it lies against the blocks) and ``first``
    the first seen block, held back so that the last of the ``extent`` is the
    row's last.  A body's positions come from the nominal block
    (``nominal``), so a program whose nominal block holds no seen score is
    skipped as it ever was; an operand's index map gives the nominal block
    where it is seen and a neighbour's seen block where it is not
    (``place``), and since the pipeline issues no copy where consecutive
    programs map to the same block, a skipped program costs a grid step and
    no bytes.  ``offs`` is the scalar-prefetch ref (or a
    pair of integers): traced offsets are read where the map runs.
    ``plain`` says that, at integer offsets, every program of the whole
    rectangle holds a seen score (a row of 1,024 keys in one k block): the
    sweep's maps are then the plain ones, and the call builds those."""
    keys_outer: bool
    outer: int
    outers: int
    block: int
    blocks: int
    window: int = None
    extent: int = None
    plain: bool = False

    @classmethod
    def of(cls, keys_outer, sq, sk, block_q, block_k, window, offsets):
        outer, block = (block_k, block_q) if keys_outer else (block_q, block_k)
        whole = cls(keys_outer, outer, (sk if keys_outer else sq) // outer,
                    block, (sq if keys_outer else sk) // block, window)
        extent, plain = whole.blocks, False
        if all(isinstance(o, int) for o in offsets):
            seen = [visited - behind for behind, visited in
                    (whole.seen(offsets, at) for at in range(whole.outers))]
            plain = min(seen) == whole.blocks
            if window is not None:
                extent = max(1, *seen)
        elif window is not None:
            extent = min(extent, 1 + -(-(outer + window - 2) // block))
        return dataclasses.replace(whole, extent=extent, plain=plain)

    def seen(self, offs, at):
        """``(behind, visited)`` of outer block ``at``: the inner blocks
        wholly before the first position it sees, and those that start at or
        before the last; none is seen where ``visited <= behind``."""
        mine, other = (1, 0) if self.keys_outer else (0, 1)
        lo, hi = _reach(offs[mine] + at * self.outer, self.outer, self.window,
                        self.keys_outer)
        behind = 0 if lo is None else \
            _whole(lo - offs[other], self.block, self.blocks)
        visited = self.blocks if hi is None else \
            _whole(hi - offs[other] + self.block, self.block, self.blocks)
        return behind, visited

    def _first(self, behind):
        if self.extent == self.blocks:
            return 0
        return _least(behind, self.blocks - self.extent)

    def nominal(self, offs, at, j):
        """The inner block whose positions step ``j`` of outer block ``at``
        computes with."""
        if self.extent == self.blocks:
            return j
        return self._first(self.seen(offs, at)[0]) + j

    def place(self, offs, at, j):
        """The inner block step ``j``'s operands are mapped to: the nominal
        one wherever it holds a seen score; before the first that does, the
        first; past the last, the block the next program that runs will
        read, which the pipeline then copies behind the last body that ran:
        the next outer block's first seen block for the forward and dq
        kernels (the library kernels' form; on the v5e 4-9% off the
        forward's time at 4,096 and 8,192 keys against resting on the last:
        PERF.md, PR 46), the outer block's own last for the last outer
        block and for dk/dv, whose next program is another query head's or
        another key block's (its empty programs lie before its first seen
        block, the diagonal's, but for a window's tail)."""
        behind, visited = self.seen(offs, at)
        nominal = self._first(behind) + j
        own = _least(_most(nominal, _least(behind, self.blocks - 1)),
                     _most(visited - 1, 0))
        if self.keys_outer:
            return own
        ahead = _least(self.seen(offs, _least(at + 1, self.outers - 1))[0],
                       self.blocks - 1)
        past = nominal >= visited
        if isinstance(past, bool):
            return ahead if past and at + 1 < self.outers else own
        return jnp.where(jnp.logical_and(past, at + 1 < self.outers), ahead,
                         own)

    def fetched(self, offsets):
        """``(the inner blocks a (batch, head) row's programs run on, each
        outer block's seen blocks counted; the blocks of the whole
        rectangle)`` at integer offsets: what the gauges
        ``flash.inner_blocks_fetched`` / ``flash.inner_blocks_total`` carry.
        No other block is copied (but the one an outer block that sees none
        rests on), and fewer are where an outer block starts on the block the
        one before ended on."""
        return (sum(max(visited - behind, 0) for behind, visited in
                    (self.seen(offsets, at) for at in range(self.outers))),
                self.outers * self.blocks)


def _walk(q_start, k_start, block_q, block_k, sub, window=None):
    """``(below, visited)`` of a causal program whose score tile has its
    first row at position ``q_start`` and its first key at ``k_start``: of
    its ``block_k / sub`` sub-tiles of keys, in order, the first ``below`` lie
    wholly below the diagonal (every score seen: ``k_start + (j + 1) sub - 1
    <= q_start``), those up to ``visited`` hold a seen score (they start at or
    before the last key the rows see, ``_reach``), and the rest are wholly
    masked.  Python integers give integers (``_causal_plan``), the device's
    values traced ones: the one rule for what a kernel runs and for what it
    says it ran.  Under a ``window`` a third count: the first ``behind``
    sub-tiles lie wholly before the first key any row sees and are not run; a
    visible block has ``behind < visited``."""
    n = block_k // sub
    below = _whole(q_start - k_start + 1, sub, n)
    lo, hi = _reach(q_start, block_q, window)
    walk = below, _whole(hi - k_start + sub, sub, n)
    if window is None:
        return walk
    return walk + (_whole(lo - k_start, sub, n),)


def _causal_plan(sq, sk, block_q, block_k, sub, q_offset=0, k_offset=0,
                 window=None):
    """``(sub-tiles a (batch, head) row, those visited, of them those that go
    through the mask's arithmetic)`` of a causal call with integer offsets,
    by ``_walk`` and ``_for_keys``' rule: a block wholly below the diagonal
    runs unmasked, every other visited sub-tile masked.  Under a ``window``
    the sub-tiles wholly behind it are not visited, and a block runs
    unmasked only where it also lies wholly inside every row's window."""
    n = block_k // sub
    starts = [(q_offset + q, k_offset + k)
              for q in range(0, sq, block_q) for k in range(0, sk, block_k)]
    visited = masked = 0
    for q, k in starts:
        below, seen, *behind = _walk(q, k, block_q, block_k, sub, window)
        run = max(seen - sum(behind), 0)
        inside = window is None or k >= q + block_q - window
        visited += run
        masked += 0 if below == n and inside else run
    return len(starts) * n, visited, masked


def _for_keys(skip_blocks, q_start, k_start, block_q, block_k, sub, run,
              window=None):
    """``run(keys, k_first, seen)`` over what a causal program's k block
    holds of seen scores, in the widest steps that hold no wholly masked
    sub-tile.  ``keys`` indexes a step's keys in a block's positions (None:
    the whole block), ``k_first`` is the position of its first key, and
    ``seen`` is True where every score of the step is seen and it needs no
    mask.  One sub-tile a block: the block, masked, and nothing else is
    traced.  Else, by the device's ``_walk``, one of three: the block whole
    and unmasked where it lies wholly below the diagonal; whole and masked
    where its last sub-tile still holds a seen score (what a step costs
    beside its tile, the row statistics' arithmetic above all, is then paid
    once a block, as without the walk); and where it does not, a loop on the
    device over the sub-tiles that do, each masked (traced once whatever
    their number), the rest not run.  Without ``skip_blocks`` (the
    interpreter) that loop takes every sub-tile.  Under a ``window`` the
    block runs whole and unmasked only where it also lies wholly inside the
    window of the program's every row, whole and masked only where no
    sub-tile lies wholly behind a window either, and the loop starts behind
    the sub-tiles that do (``_walk``'s third count)."""
    if sub == block_k:
        run(None, k_start, False)
        return
    n = block_k // sub

    def step(j, carry):
        first = pl.multiple_of(j * sub, sub)
        run(pl.ds(first, sub), k_start + first, False)
        return carry
    if not skip_blocks:
        jax.lax.fori_loop(0, n, step, 0)
        return
    if window is not None:
        below, visited, behind = _walk(q_start, k_start, block_q, block_k,
                                       sub, window)
        seen = jnp.logical_and(below == n,
                               k_start >= q_start + block_q - window)
        whole = jnp.logical_and(visited == n, behind == 0)
        pl.when(seen)(lambda: run(None, k_start, True))
        pl.when(jnp.logical_and(whole, jnp.logical_not(seen)))(
            lambda: run(None, k_start, False))

        @pl.when(jnp.logical_not(whole))
        def _windowed():
            jax.lax.fori_loop(behind, visited, step, 0)
        return
    below, visited = _walk(q_start, k_start, block_q, block_k, sub)
    pl.when(below == n)(lambda: run(None, k_start, True))
    pl.when(jnp.logical_and(visited == n, below < n))(
        lambda: run(None, k_start, False))

    @pl.when(visited < n)
    def _walked():
        jax.lax.fori_loop(0, visited, step, 0)


def _keys_of(at, keys):
    """The index ``at`` of a program's rows in an operand's block, narrowed
    to the positions ``keys`` of a sub-tile."""
    return at if keys is None else (at, keys)


def _for_rows(rows, heads, tile, body):
    """``body(at, head)`` over a program's (batch, head) rows, ``tile`` a
    row's score tile.  ``at`` indexes the rows of one step in a block's first
    dimension: the one row of a one-row program, which has no loop and
    two-dimensional arithmetic; else a loop on the device whose step takes as
    many rows as ``_STEP_TILE`` allows, one as an index, several as a slice,
    so that a kernel's code does not grow with ``rows``.  ``head`` is None
    where a block holds one head; where it holds several the loop takes them
    one a step, inside the rows', and ``head`` is its index on the device:
    the body masks lanes by it and is traced once whatever the heads."""
    a_step = 1 if rows == 1 else max(
        (n for n in range(1, rows + 1)
         if rows % n == 0 and n * tile <= _STEP_TILE), default=1)
    steps = rows // a_step

    def at(i):
        if rows == 1:
            return 0
        if steps == 1:
            return slice(None)
        return (i if a_step == 1
                else pl.ds(pl.multiple_of(i * a_step, a_step), a_step))

    if steps * heads == 1:
        body(at(0), None)
        return

    def step(i, carry):
        if heads == 1:
            body(at(i), None)
        else:
            body(at(jax.lax.div(i, heads)), jax.lax.rem(i, heads))
        return carry
    jax.lax.fori_loop(0, steps * heads, step, 0)


def _dot(a, b, contract_a, contract_b):
    """``a . b`` in f32 over the given axes, counted from the end; the
    leading axis of rank-3 operands is the rows of a loop step."""
    rows = tuple(range(a.ndim - 2))
    return jax.lax.dot_general(
        a, b, (((a.ndim + contract_a,), (b.ndim + contract_b,)),
               (rows, rows)), preferred_element_type=jnp.float32)


def _lanes_of(head, d, x):
    """Which lanes of a block's value ``x`` are ``head``'s ``d``, as a mask
    that broadcasts against it; None where the block is one head."""
    lanes = x.shape[-1]
    if lanes == d:
        return None
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (1,) * (x.ndim - 1) + (lanes,), x.ndim - 1)
    return jnp.logical_and(lane >= head * d, lane < (head + 1) * d)


def _only(keep, x):
    """``x`` with the lanes outside ``keep`` zeroed.  A product over a
    block's whole width with one operand so masked is one head's product
    (the other heads' lanes add exact zeros), and a product that makes the
    whole width from a masked operand leaves zeros in their lanes: at d = 64
    a product fills half the 128-wide MXU either way, so neither costs a
    pass more than the head's own 64 lanes would."""
    return x if keep is None else jnp.where(keep, x, jnp.zeros_like(x))


def _across(stat, width):
    """A lane-replicated statistic ``(..., block, _LANES)`` at ``width``
    lanes: whole registers side by side (no lane is broadcast), a slice of
    one where a tile is narrower.  A tile is narrower than a register or
    whole registers wide: the blocks divide the sequence and a head's lanes
    are 64, 128 or 256."""
    lanes = stat.shape[-1]
    if width == lanes:
        return stat
    if width < lanes:
        return stat[..., :width]
    assert width % lanes == 0, \
        f"a tile of {width} lanes is not whole {lanes}-lane registers"
    return jnp.tile(stat, (1,) * (stat.ndim - 1) + (width // lanes,))


def _as_columns(rows):
    """Statistics read as they cross HBM, ``(..., 1, block)`` rows along the
    lanes, turned to lane-replicated columns ``(..., block, _LANES)``: a
    broadcast down the sublanes and one transpose of 128-lane tiles."""
    return jnp.swapaxes(jnp.broadcast_to(
        rows, rows.shape[:-2] + (_LANES, rows.shape[-1])), -1, -2)


def _as_rows(columns):
    """Lane-replicated statistics ``(..., block, _LANES)`` as the rows
    ``(..., 1, block)`` that cross HBM."""
    return jnp.swapaxes(columns, -1, -2)[..., :1, :]


def _stat_columns(ref, at, width):
    """A block of ``lse`` or ``delta`` at the rows ``at`` for a tile with
    queries down the sublanes: the packed layout's ``(1, block)`` rows
    turned to lane-replicated columns at the tile's ``width``, the split
    layout's ``(block, 1)`` columns as they are (they broadcast)."""
    stat = ref[at]
    return _across(_as_columns(stat), width) if len(ref.shape) == 4 else stat


def _stat_rows(ref, at):
    """The same block for a tile with queries along the lanes (the dk/dv
    kernel's): the packed layout's rows as they were read, the split
    layout's columns turned to ``(1, block)`` rows."""
    stat = ref[at]
    if len(ref.shape) == 4:
        return stat
    return _as_rows(jnp.broadcast_to(stat, stat.shape[:-1] + (_LANES,)))


def _over_lanes(stat, d, lanes):
    """A program's lane-replicated statistics spread over a block's
    ``lanes``: the packed layout's ``(rows, heads, block, _LANES)`` each
    head's over its own ``d`` lanes, the split layout's one head's over
    all."""
    if len(stat.shape) < 4:
        return _across(stat, lanes)
    out = _across(stat[:, 0], lanes)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, lanes), 2)
    for head in range(1, stat.shape[1]):
        out = jnp.where(lane >= head * d, _across(stat[:, head], lanes), out)
    return out


def _row_of(ref, at, head=None, keys=None):
    """The rows ``at`` of a program's scratch or of a block of statistics.
    Scratch has no rows dimension in a program of one row of the split
    layout (``_scratch``): the long-sequence cells' kernels compile to the
    same Mosaic module whether or not short rows group.  The packed layout's
    statistics have a heads dimension after the rows', in scratch ``(rows,
    heads, block, _LANES)`` and in a block ``(rows, heads, 1, block)``, and
    give those of ``head`` (the one head where a block holds one).  ``keys``
    narrows an accumulator over a k block to a sub-tile's positions."""
    if len(ref.shape) == 4:
        return at, 0 if head is None else head
    if len(ref.shape) == 2:
        return slice(None) if keys is None else keys
    return _keys_of(at, keys)


def _all_rows(scratch):
    """The index of all of a program's rows in a block, for a value read from
    its scratch: the one row where the scratch has no rows dimension."""
    return 0 if len(scratch.shape) == 2 else slice(None)


def _scratch(layout, n, shape):
    """f32 scratch of ``shape`` a unit for a program of ``n`` units."""
    return pltpu.VMEM(shape if n == 1 and not layout.packed else (n,) + shape,
                      jnp.float32)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "name", "body", "layout", "n", "grid_tail", "ins", "outs", "scratch",
    "block_q", "block_k", "sub", "causal", "interpret", "scale", "window",
    "group", "sweep"))
def _kernel_call(offs, *arrays, name, body, layout, n, grid_tail, ins, outs,
                 scratch, block_q, block_k, sub, causal, interpret,
                 scale=None, window=None, group=1, sweep=None):
    """One kernel over ``arrays`` in the kernels' own shapes, ``n`` units a
    program.  ``ins`` give each array's block as ``(length, which of the
    grid's indices places it along the sequence, whether it is a row
    statistic)``, ``outs`` each result's the same way and then ``(dtype,
    whole length)``, ``scratch`` the f32 accumulators' shapes a unit; an
    entry of either may end in the lanes of an operand whose width is its
    own and, for an input, whether a batch row's heads share it
    (``_Layout.spec``).  ``scale`` is given for the two-product form, whose
    bodies read two more operands (``_two_product``); ``sub`` is the keys a
    sub-tile of a causal program's k block (``_sub_tile``: the caller reads
    the module's constant, so that this function's cache is keyed by it).
    ``window`` and ``group`` reach a body only where there is a window or
    several query heads a key-value head: a call with neither builds the
    body it built before they existed.  ``sweep`` is a causal call's
    ``_Sweep``: the operands placed by the grid's last index (the inner
    side's) take their blocks through it, and under a window, where the grid
    is narrower than the row, the body takes its positions from it too; a
    call that is not causal has none and builds the maps it always built, as
    does one whose every program holds a seen score (``_Sweep.plain``).

    An inlined ``jit``: a model's layers make the same call, and every one
    after the first takes the first's equations from the cache, the kernel's
    body traced once and (its jaxpr being one object) lowered to Mosaic once;
    the benchmark's process spends 5-10 times a clean process's time on
    tracing (PERF.md section 7), and a step's attention is most of a model's
    traced operations."""
    form = {} if scale is None else {"scale": scale}
    if window is not None:
        form["window"] = window
    if group > 1:
        form["group"] = group
    if sweep is not None and sweep.plain:   # nothing to clamp or narrow
        sweep = None
    if window is not None and sweep is not None:
        form["sweep"] = sweep
    return pl.pallas_call(
        functools.partial(body, d=layout.d, block_q=block_q, block_k=block_k,
                          sub=sub, causal=causal, skip_blocks=not interpret,
                          **form),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(layout.units // n * layout.lane_blocks,) + grid_tail,
            in_specs=[layout.spec(n, *block,
                                  sweep=sweep if block[1] == 2 else None)
                      for block in ins],
            out_specs=[layout.spec(n, *out[:3], *out[5:]) for out in outs],
            scratch_shapes=[_scratch(layout, n, shape) for shape in scratch],
        ),
        out_shape=[_sds(layout.shape(out[4], out[2], *out[5:]), out[3], offs,
                        *arrays) for out in outs],
        # Programs of the first two dimensions are independent; only the
        # innermost carries the accumulators.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(offs, *arrays)


def causal_bias(sq, sk, q_offset=0, k_offset=0, window=None,
                keys_first=False):
    """Additive causal bias (0 where visible, -inf where masked) for a
    (sq, sk) score block whose rows/cols sit at the given global offsets
    (offsets may be traced scalars). The single definition of causal
    masking shared by the dense reference, the Pallas kernels, and the
    ring/Ulysses SP paths.  Under a ``window`` position t sees the keys s
    with ``t - window < s <= t``.  ``keys_first`` gives the transposed
    block's, (sk, sq): the dk/dv kernel's scores."""
    shape, q_dim = ((sk, sq), 1) if keys_first else ((sq, sk), 0)
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    if window is None:
        return jnp.where(q_pos >= k_pos, 0.0, _NEG_INF)
    return jnp.where(jnp.logical_and(q_pos >= k_pos, k_pos > q_pos - window),
                     0.0, _NEG_INF)


# ---------------------------------------------------------------------------
# dense reference (CPU fallback and numerics oracle)


def _group_of(q, k, heads_dim=1):
    """Query heads a key-value head; the heads are dimension ``heads_dim``."""
    heads, kv_heads = q.shape[heads_dim], k.shape[heads_dim]
    assert heads % kv_heads == 0, \
        f"{heads} query heads do not group over {kv_heads} key-value heads"
    return heads // kv_heads


def _by_kv_head(x, k):
    """A q-side operand ``x`` (batch, heads, sq, ...) of the dense path as
    (batch, key-value heads, group, sq, ...) where ``k`` holds fewer heads
    than it (query head h reads key-value head ``h // group``); as it is
    where they hold as many.  The einsums below let ``...`` stand for the
    group, so keys and values are never repeated over the query heads."""
    if x.shape[1] == k.shape[1]:
        return x
    return x.reshape((x.shape[0], k.shape[1], _group_of(x, k)) + x.shape[2:])


def _dense_scores(q, k, causal, q_offset, k_offset, window):
    """The dense path's scaled, masked f32 scores (batch, key-value heads,
    [group,] sq, sk) and the scale."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bh...qd,bhkd->bh...qk", _by_kv_head(q, k), k) \
        .astype(jnp.float32) * scale
    if causal:
        s = s + causal_bias(q.shape[2], k.shape[2], q_offset, k_offset,
                            window)
    return s, scale


def _dense_fwd(q, k, v, causal, q_offset=0, k_offset=0, window=None):
    """Returns (o f32, lse f32 (..., sq, 1)); k and v may hold fewer heads
    than q (:func:`_by_kv_head`)."""
    s, _ = _dense_scores(q, k, causal, q_offset, k_offset, window)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    lse = m + jnp.log(l)
    o = jnp.einsum("bh...qk,bhkd->bh...qd", p, v.astype(jnp.float32)) / l
    return o.reshape(q.shape[:3] + o.shape[-1:]), \
        lse.reshape(q.shape[:3] + (1,))


def _dense_reference(q, k, v, causal, q_offset=0, window=None):
    o, _ = _dense_fwd(q, k, v, causal, q_offset, window=window)
    return o.astype(q.dtype)


def _dense_bwd(q, k, v, do, lse, delta, causal, q_offset=0, k_offset=0,
               window=None):
    """FA2-style dense backward from the saved lse: p = exp(s - lse).

    delta = rowsum(do * o); returns (dq, dk, dv) in f32, dk and dv summed
    over the query heads of a key-value head where k and v hold fewer heads.
    """
    s, scale = _dense_scores(q, k, causal, q_offset, k_offset, window)
    p = jnp.exp(s - _by_kv_head(lse, k))       # (..., sq, sk); masked -> 0
    dof = _by_kv_head(do.astype(jnp.float32), k)
    dv = jnp.einsum("bh...qk,bh...qd->bhkd", p, dof)
    dp = jnp.einsum("bh...qd,bhkd->bh...qk", dof, v.astype(jnp.float32))
    ds = p * (dp - _by_kv_head(delta, k)) * scale
    dq = jnp.einsum("bh...qk,bhkd->bh...qd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bh...qk,bh...qd->bhkd", ds,
                    _by_kv_head(q.astype(jnp.float32), k))
    return dq.reshape(q.shape), dk, dv


# ---------------------------------------------------------------------------
# forward kernel


def _two_product(refs, n_in, scale):
    """``(refs as the one-product body names them, q_rope ref, k_rope ref)``.

    With ``scale`` given a kernel is in its two-product form: the score is
    ``(q . k + q_rope . k_rope) * scale``, the second product over a width
    of its own against a key that one batch row's heads share (latent
    attention's rotary part), and the values' width is v's own.  Its two
    operands follow the ``n_in`` of the one-product form; the program is one
    (batch, head) row, so ``k_rope``'s block is that row's batch row."""
    if scale is None:
        return refs, None, None
    return refs[:n_in] + refs[n_in + 2:], refs[n_in], refs[n_in + 1]


def _visible(causal, skip_blocks, q_start, k_start, block_q, block_k, window):
    """Whether a program's score tile holds a seen score.  A causal block is
    fully masked iff its largest q position is still left of its smallest k
    position, and under a ``window`` iff besides its largest k position is
    not behind the window of its smallest q position: skip the MXU work
    entirely; inside a block that is not, ``_for_keys`` makes the same test
    a sub-tile.  ``skip_blocks`` is off in interpret mode (the Pallas
    interpreter's state discharge loses multi-scratch writes under a skipped
    runtime-conditional); the p-masking keeps skipped-block contributions
    exactly zero either way."""
    lo, hi = _reach(q_start, block_q, window)
    visible = jnp.logical_or(not (causal and skip_blocks), hi >= k_start)
    if window is None or not skip_blocks:
        return visible
    return jnp.logical_and(visible, k_start + block_k - 1 >= lo)


def _fwd_kernel(offs_ref, *refs, d, block_q, block_k, sub, causal,
                skip_blocks, scale=None, window=None, group=1, sweep=None):
    """Grid (units / rows a program x lane blocks, q-blocks, k-blocks): k
    innermost, accumulators in VMEM scratch carried across the k dimension,
    each of a program's rows with its own; ``d`` lanes a head; ``sub`` keys a
    step of the walk over the k block (``_for_keys``).  With ``group`` query
    heads a key-value head the body is the same: the k and v blocks' index
    maps pick the head (``_Layout.spec``).  Under a ``window`` the grid's
    last dimension is the ``sweep``'s extent and a step's k block the one it
    names (``_Sweep.nominal``)."""
    (q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l), qr_ref, kr_ref = \
        _two_product(refs, 3, scale)
    rows = q_ref.shape[0]
    iq = pl.program_id(1)
    step = ik = pl.program_id(2)
    num_kb = pl.num_programs(2)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if sweep is not None:
        ik = sweep.nominal(offs_ref, iq, step)

    @pl.when(step == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, _NEG_INF)
        l[:] = jnp.zeros_like(l)

    q_start = offs_ref[0] + iq * block_q
    k_start = offs_ref[1] + ik * block_k
    visible = _visible(causal, skip_blocks, q_start, k_start, block_q,
                       block_k, window)

    @pl.when(visible)
    def _block():
        def _run(keys, k_first, seen):
            def _rows(at, head):
                row, stat = _row_of(acc, at), _row_of(m, at, head)
                q = q_ref[at]
                k = k_ref[_keys_of(at, keys)]
                v = v_ref[_keys_of(at, keys)]
                keep = _lanes_of(head, d, q)
                q, v = _only(keep, q), _only(keep, v)
                s = _dot(q, k, -1, -1)
                if qr_ref is not None:
                    s = s + _dot(qr_ref[at], kr_ref[_keys_of(0, keys)], -1, -1)
                s = s * scale
                if causal and not seen:
                    s = s + causal_bias(block_q, k.shape[-2], q_start, k_first,
                                        window)
                # The running maximum and sum are lane-replicated: a
                # row's value in every lane of its register.
                m_prev = m[stat]
                m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # Masked entries contribute EXACTLY zero (not exp(-1e30 -
                # m)): in a fully-masked block m_new stays at the sentinel
                # and s - m_new = 0.
                wide = _across(m_new, s.shape[-1])
                p = jnp.exp(s - wide) if seen else jnp.where(
                    s > _NEG_INF / 2, jnp.exp(s - wide), 0.0)
                l[stat] = l[stat] * alpha + p.sum(-1, keepdims=True)
                alpha = _across(alpha, acc.shape[-1])
                if keep is not None:    # the other heads' lanes keep theirs
                    alpha = jnp.where(keep, alpha, 1.0)
                acc[row] = acc[row] * alpha + _dot(p.astype(v.dtype), v, -1,
                                                   -2)
                m[stat] = m_new
            _for_rows(rows, q_ref.shape[-1] // d, block_q * block_k, _rows)
        _for_keys(skip_blocks, q_start, k_start, block_q, block_k, sub, _run,
                  window)

    @pl.when(step == num_kb - 1)
    def _finalize():
        every = _all_rows(acc)
        # 1e-30, NOT 1e-38: f32 subnormals flush to zero on TPU (and in the
        # interpret pipeline), and max(0, ftz(1e-38)) / 0 is how a guard
        # epsilon turns into NaN for rows that saw no visible block.
        total = jnp.maximum(l[:], 1e-30)
        o_ref[every] = (acc[:] / _over_lanes(total, d, acc.shape[-1])) \
            .astype(o_ref.dtype)
        # Rows that saw no visible block keep the finite sentinel (not -inf:
        # downstream combines subtract lse values and -inf - -inf = nan).
        lse = jnp.where(l[:] > 0, m[:] + jnp.log(total), _NEG_INF) \
            .astype(lse_ref.dtype)
        # From lane-replicated columns to what crosses HBM, once a q block:
        # the packed layout's rows a head, the split layout's columns.
        if len(lse.shape) == 4:
            for head in range(lse.shape[1]):
                lse_ref[:, head] = _as_rows(lse[:, head])
        else:
            lse_ref[every] = lse[..., :1]


def _blocks(sq, sk, block_q, block_k):
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, \
        f"seq ({sq},{sk}) must divide blocks ({block_q},{block_k})"
    return block_q, block_k


def _grouping(q, k, packed, causal, window):
    """Query heads a key-value head of a kernel call, and the checks of what
    the kernels serve: grouped heads on the split layout, a window under
    ``causal``."""
    group = _group_of(q, k, 2 if packed else 1)
    if group > 1 and packed:
        raise NotImplementedError(
            "the flash kernels read grouped key-value heads on the split "
            "layout (batch, heads, s, d) only")
    if window is not None and not (causal and window > 0):
        raise ValueError(f"a window ({window!r}) is a positive number of "
                         f"keys behind a causal diagonal")
    return group


def _grid(causal, keys_outer, sq, sk, block_q, block_k, window, offsets,
          group=1):
    """``(sweep, grid_tail)`` of a kernel call: a causal call's ``_Sweep``,
    none where the call is not causal, whose grid and index maps are then
    what they always were; and the grid behind its first dimension, the
    outer blocks (k's with ``keys_outer``, the dk/dv kernel's) and then the
    inner ones an outer block's programs step through, for each of a
    key-value head's ``group`` query heads."""
    outers, inners = sq // block_q, sk // block_k
    if keys_outer:
        outers, inners = inners, outers
    sweep = _Sweep.of(keys_outer, sq, sk, block_q, block_k, window,
                      offsets) if causal else None
    return sweep, (outers,
                   group * (inners if sweep is None else sweep.extent))


def _kv_block(block_k, at, group):
    """``_kernel_call``'s entry of a k or v block that follows the grid's
    index ``at``: a unit's own, or one for the ``group`` query heads of its
    key-value head."""
    return (block_k, at, False) if group == 1 else \
        (block_k, at, False, None, group)


def _flash_fwd(q, k, v, causal, block_q, block_k, q_offset, k_offset,
               interpret, out_dtype=None, packed=False, window=None):
    """Fused forward. Returns (o out_dtype in q's layout, lse f32
    (b,h,sq,1)): q/k/v are (b,h,s,d), or with ``packed`` (b,s,h,d); k and v
    may hold fewer heads than q (split layout).

    ``q_offset``/``k_offset`` may be traced scalars (scalar-prefetch)."""
    group = _grouping(q, k, packed, causal, window)
    layout = _Layout.of(q, packed)
    qr, kr, vr = layout.array(q), layout.array(k), layout.array(v)
    sq, sk = qr.shape[1], kr.shape[1]
    block_q, block_k = _blocks(sq, sk, block_q, block_k)
    if isinstance(q_offset, int) and causal:
        assert q_offset % block_q == 0, \
            f"q_offset {q_offset} must be a multiple of block_q {block_q}"
    out_dtype = jnp.dtype(out_dtype or q.dtype)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)
    f32 = jnp.dtype(jnp.float32)
    q_block, k_block = layout.block(block_q), layout.block(block_k)
    row_block = layout.block(block_q, stat=True)
    # The accumulator, then the running maximum and sum, lane-replicated.
    running = row_block[:-2] + (block_q, _LANES)
    scratch = (q_block, running, running)
    # With grouped heads a program is one row: its k block is one
    # key-value head's.
    g, vmem = _rows_per_program(
        layout.units if group == 1 else 1, block_q, block_k,
        [(q_block, q.dtype), (k_block, k.dtype), (k_block, v.dtype),
         (q_block, out_dtype), (row_block, f32)],
        [(shape, f32) for shape in scratch], layout.heads)
    sweep, grid_tail = _grid(causal, False, sq, sk, block_q, block_k, window,
                             (q_offset, k_offset))
    _announce("flash_fwd", layout, qr, sk, block_q, block_k, g, vmem,
              (q_offset, k_offset) if causal else None, group, window, sweep)
    # q's blocks follow the grid's second index, k's and v's its third.
    out, lse = _kernel_call(
        offs, qr, kr, vr, name="flash_fwd", body=_fwd_kernel, layout=layout,
        n=g // layout.heads, grid_tail=grid_tail,
        ins=((block_q, 1, False), _kv_block(block_k, 2, group),
             _kv_block(block_k, 2, group)),
        outs=((block_q, 1, False, out_dtype, sq),
              (block_q, 1, True, f32, sq)),
        scratch=scratch, block_q=block_q, block_k=block_k,
        sub=_sub_tile(causal, block_k), causal=causal, interpret=interpret,
        window=window, group=group, sweep=sweep)
    return layout.result(out), layout.result(lse, stat=True)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2: dq over K blocks, dk/dv over Q blocks)


def _bwd_dq_kernel(offs_ref, *refs, d, block_q, block_k, sub, causal,
                   skip_blocks, scale=None, window=None, group=1,
                   sweep=None):
    refs, qr_ref, kr_ref = _two_product(refs, 6, scale)
    if qr_ref is None:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
         dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dqr_ref,
         dq_acc, dqr_acc) = refs
    rows = q_ref.shape[0]
    iq = pl.program_id(1)
    step = ik = pl.program_id(2)
    num_kb = pl.num_programs(2)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if sweep is not None:
        ik = sweep.nominal(offs_ref, iq, step)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        if qr_ref is not None:
            dqr_acc[:] = jnp.zeros_like(dqr_acc)

    q_start = offs_ref[0] + iq * block_q
    k_start = offs_ref[1] + ik * block_k
    visible = _visible(causal, skip_blocks, q_start, k_start, block_q,
                       block_k, window)

    @pl.when(visible)
    def _block():
        def _run(keys, k_first, seen):
            def _rows(at, head):
                stat = _row_of(lse_ref, at, head)
                q = q_ref[at]
                k = k_ref[_keys_of(at, keys)]
                v = v_ref[_keys_of(at, keys)]
                do = do_ref[at]
                keep = _lanes_of(head, d, q)
                k, v = _only(keep, k), _only(keep, v)
                s = _dot(q, k, -1, -1)
                if qr_ref is not None:
                    kr = kr_ref[_keys_of(0, keys)]
                    s = s + _dot(qr_ref[at], kr, -1, -1)
                s = s * scale
                if causal and not seen:
                    s = s + causal_bias(block_q, k.shape[-2], q_start, k_first,
                                        window)
                lse = _stat_columns(lse_ref, stat, s.shape[-1])
                delta = _stat_columns(delta_ref, stat, s.shape[-1])
                p = jnp.exp(s - lse) if seen else jnp.where(
                    s > _NEG_INF / 2, jnp.exp(s - lse), 0.0)
                dp = _dot(do, v, -1, -1)
                ds = p * (dp - delta) * scale
                dq_acc[_row_of(dq_acc, at)] += _dot(ds.astype(k.dtype), k, -1,
                                                    -2)
                if qr_ref is not None:
                    dqr_acc[:] += _dot(ds.astype(kr.dtype), kr, -1, -2)
            _for_rows(rows, q_ref.shape[-1] // d, block_q * block_k, _rows)
        _for_keys(skip_blocks, q_start, k_start, block_q, block_k, sub, _run,
                  window)

    @pl.when(step == num_kb - 1)
    def _finalize():
        dq_ref[_all_rows(dq_acc)] = dq_acc[:].astype(dq_ref.dtype)
        if qr_ref is not None:
            dqr_ref[0] = dqr_acc[:].astype(dqr_ref.dtype)


def _bwd_dkv_kernel(offs_ref, *refs, d, block_q, block_k, sub, causal,
                    skip_blocks, scale=None, window=None, group=1,
                    sweep=None):
    """Grid (units, k-blocks, q-blocks): q innermost, the dk and dv
    accumulators carried across it.  The scores are computed transposed,
    keys down the sublanes and queries along the lanes (``s^T = k . q^T``,
    ``dp^T = v . do^T``): a block of ``lse`` or ``delta`` is the ``(1,
    block_q)`` row it crossed HBM as and broadcasts down the sublanes, and
    ``dv += p^T . do`` and ``dk += ds^T . q`` are plain products (no
    transposed left operand for Mosaic to turn).  With ``group`` query heads a key-value
    head the grid's first index counts key-value heads and its innermost
    runs over the q blocks of the group's query heads one after another
    (``_Layout.spec``'s ``(group, blocks)``), so one head's dk and dv are
    summed over its query heads where they are accumulated.  Under a
    ``window`` a query head's steps are the ``sweep``'s extent, from the
    first q block that sees the k block's keys (``_Sweep.nominal``)."""
    refs, qr_ref, kr_ref = _two_product(refs, 6, scale)
    if qr_ref is None:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dkr_ref, dk_acc, dv_acc, dkr_acc) = refs
    rows = q_ref.shape[0]
    ik = pl.program_id(1)
    step = iq = pl.program_id(2)
    num_qb = pl.num_programs(2)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if group > 1:       # the q block within its query head
        iq = jax.lax.rem(step, num_qb // group)
    if sweep is not None:
        iq = sweep.nominal(offs_ref, ik, iq)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if qr_ref is not None:
            dkr_acc[:] = jnp.zeros_like(dkr_acc)

    q_start = offs_ref[0] + iq * block_q
    k_start = offs_ref[1] + ik * block_k
    visible = _visible(causal, skip_blocks, q_start, k_start, block_q,
                       block_k, window)

    @pl.when(visible)
    def _block():
        def _run(keys, k_first, seen):
            def _rows(at, head):
                # A sub-tile of keys updates its own slice of dk and dv.
                row = _row_of(dk_acc, at, keys=keys)
                stat = _row_of(lse_ref, at, head)
                q = q_ref[at]
                k = k_ref[_keys_of(at, keys)]
                v = v_ref[_keys_of(at, keys)]
                do = do_ref[at]
                keep = _lanes_of(head, d, q)
                q, do = _only(keep, q), _only(keep, do)
                # Everything below is transposed, (keys, queries): s is s^T.
                s = _dot(k, q, -1, -1)
                if qr_ref is not None:
                    qr = qr_ref[at]
                    s = s + _dot(kr_ref[_keys_of(0, keys)], qr, -1, -1)
                s = s * scale
                if causal and not seen:
                    s = s + causal_bias(block_q, k.shape[-2], q_start, k_first,
                                        window, keys_first=True)
                lse = _stat_rows(lse_ref, stat)
                p = jnp.exp(s - lse) if seen else jnp.where(
                    s > _NEG_INF / 2, jnp.exp(s - lse), 0.0)
                dv_acc[row] += _dot(p.astype(do.dtype), do, -1, -2)  # p^T do
                dp = _dot(v, do, -1, -1)
                ds = p * (dp - _stat_rows(delta_ref, stat)) * scale
                dk_acc[row] += _dot(ds.astype(q.dtype), q, -1, -2)   # ds^T q
                if qr_ref is not None:  # this head's part of the shared key's
                    dkr_acc[_row_of(dkr_acc, at, keys=keys)] += _dot(
                        ds.astype(qr.dtype), qr, -1, -2)
            _for_rows(rows, q_ref.shape[-1] // d, block_q * block_k, _rows)
        _for_keys(skip_blocks, q_start, k_start, block_q, block_k, sub, _run,
                  window)

    @pl.when(step == num_qb - 1)
    def _finalize():
        every = _all_rows(dk_acc)
        dk_ref[every] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[every] = dv_acc[:].astype(dv_ref.dtype)
        if qr_ref is not None:
            dkr_ref[0] = dkr_acc[:].astype(dkr_ref.dtype)


def _flash_bwd(q, k, v, do, lse, delta, causal, block_q, block_k, q_offset,
               k_offset, interpret, out_dtype=None, packed=False,
               window=None):
    """Fused backward. Returns (dq, dk, dv) in out_dtype and the layout of
    q, k, v: the f32 accumulators are rounded once, by the kernels' last
    step.  ``lse`` and ``delta`` are (b,h,sq,1) in either layout.  Where k
    and v hold fewer heads than q, dk and dv are theirs: the dk/dv kernel's
    grid counts key-value heads and sums a head's query heads where it
    accumulates."""
    group = _grouping(q, k, packed, causal, window)
    layout = _Layout.of(q, packed)
    qr, kr, vr, dor = (layout.array(x) for x in (q, k, v, do))
    sq, sk = qr.shape[1], kr.shape[1]
    block_q, block_k = _blocks(sq, sk, block_q, block_k)
    out_dtype = jnp.dtype(out_dtype or q.dtype)
    lser, deltar = layout.stat(lse), layout.stat(delta)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)

    f32 = jnp.dtype(jnp.float32)
    q_block, k_block = layout.block(block_q), layout.block(block_k)
    row_block = layout.block(block_q, stat=True)
    in_blocks = [(q_block, q.dtype), (k_block, k.dtype), (k_block, v.dtype),
                 (q_block, do.dtype), (row_block, f32), (row_block, f32)]

    def call(name, body, at_q, at_k, out_block, out_len, n_out, of=layout,
             fan=1):
        """One backward kernel: ``n_out`` results of ``out_block`` a unit at
        the grid's second index, each with its f32 accumulator in scratch;
        q's blocks follow the grid's index ``at_q``, k's ``at_k``, and the
        side at index 2 is the inner one, a causal call's stepped through by
        its ``_Sweep``.  ``of`` is the layout whose units the grid's first
        index counts; with ``fan`` above 1 those are key-value heads and the
        q side runs over a head's ``fan`` query heads."""
        sweep, grid_tail = _grid(causal, at_k == 1, sq, sk, block_q, block_k,
                                 window, (q_offset, k_offset), fan)
        g, vmem = _rows_per_program(
            of.units if group == 1 else 1, block_q, block_k,
            in_blocks + [(out_block, out_dtype)] * n_out,
            [(out_block, f32)] * n_out, of.heads)
        _announce(name, layout, qr, sk, block_q, block_k, g, vmem,
                  (q_offset, k_offset) if causal else None, group, window,
                  sweep)
        if fan == 1:
            at_k = _kv_block(block_k, at_k, group)
            at_q = (block_q, at_q, False)
            stat = at_q[:2] + (True,)
        else:
            heads = (fan, grid_tail[1] // fan)
            at_k = (block_k, at_k, False)
            at_q = (block_q, at_q, False, None, heads)
            stat = (block_q, at_q[1], True, None, heads)
        return _kernel_call(
            offs, qr, kr, vr, dor, lser, deltar, name=name, body=body,
            layout=of, n=g // of.heads, grid_tail=grid_tail,
            ins=(at_q, at_k, at_k, at_q, stat, stat),
            outs=((out_block[0], 1, False, out_dtype, out_len),) * n_out,
            scratch=(out_block,) * n_out, block_q=block_q, block_k=block_k,
            sub=_sub_tile(causal, block_k), causal=causal,
            interpret=interpret, window=window, group=group, sweep=sweep)

    # dq: q blocks outside, accumulated over the k blocks inside; dk and dv:
    # k blocks outside, accumulated over the q blocks inside.
    dq, = call("flash_bwd_dq", _bwd_dq_kernel, 1, 2, q_block, sq, 1)
    if group == 1:
        dk, dv = call("flash_bwd_dkv", _bwd_dkv_kernel, 2, 1, k_block, sk, 2)
        return layout.result(dq), layout.result(dk), layout.result(dv)
    kv = _Layout.of(k, packed)
    dk, dv = call("flash_bwd_dkv", _bwd_dkv_kernel, 2, 1, k_block, sk, 2,
                  of=kv, fan=group)
    return layout.result(dq), kv.result(dk), kv.result(dv)


# ---------------------------------------------------------------------------
# the two-product form (latent attention: a rotary key shared by the heads)


def two_product_reference(q, q_rope, k, k_rope, v, scale, causal=True,
                          mask=None):
    """The two-product attention in plain jnp, f32 statistics:
    ``softmax((q . k + q_rope . k_rope) * scale [+ causal mask]) v`` with
    ``q``, ``k`` (batch, heads, s, d), ``q_rope`` (batch, heads, s, r),
    ``k_rope`` (batch, s, r), one key a position for every head, and ``v``
    (batch, heads, s, value width); ``mask`` (True where a key is seen,
    broadcast against (batch, heads, q, k)) hides keys besides.  The path
    off the TPU, and the kernels' oracle; differentiated by autodiff."""
    s = (jnp.einsum("bhqd,bhkd->bhqk", q, k)
         + jnp.einsum("bhqr,bkr->bhqk", q_rope, k_rope)).astype(jnp.float32)
    s = s * scale
    if causal:
        s = s + causal_bias(q.shape[2], k.shape[2])
    if mask is not None:
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _two_product_plan(q, q_rope, k, k_rope, v, block_q, block_k):
    """What both passes of the two-product form share: the split layout of
    ``q``, the kernels' arrays, the blocks, and each operand's block entry
    (``_kernel_call``'s ``ins``) by the grid index that places it."""
    layout = _Layout.of(q, packed=False)
    arrays = tuple(x.reshape(layout.units, x.shape[2], x.shape[3])
                   for x in (q, q_rope, k, v)) + (k_rope,)
    sq, sk = q.shape[2], k.shape[2]
    block_q, block_k = _blocks(sq, sk, block_q, block_k)
    r, dv = q_rope.shape[-1], v.shape[-1]

    def ins(at_q, at_k):
        """Blocks of q, k, v and of q_rope, k_rope, q's following the grid's
        index ``at_q`` and k's ``at_k``."""
        return ((block_q, at_q, False), (block_k, at_k, False),
                (block_k, at_k, False, dv)), \
            ((block_q, at_q, False, r), (block_k, at_k, False, r, True))
    return layout, arrays, (sq, sk), (block_q, block_k), (r, dv), ins


def _announce_two_product(kernel, layout, q, sk, blocks, widths, in_blocks,
                          out_blocks, causal, scratch=None, sweep=None):
    """``scratch`` are the f32 scratch shapes where they are not the
    results' blocks (the forward's running maximum and sum)."""
    r, dv = widths
    _, vmem = _rows_per_program(
        1, *blocks, in_blocks + out_blocks,
        [(shape, jnp.float32)
         for shape in scratch or [shape for shape, _ in out_blocks]])
    _announce(f"{kernel} two-product ({layout.d} + {r} lanes a score, one "
              f"{r}-lane key a position shared by {layout.num_heads} heads, "
              f"values of {dv})", layout, q, sk, *blocks, 1, vmem,
              (0, 0) if causal else None, sweep=sweep)


def _flash_fwd2(q, q_rope, k, k_rope, v, scale, causal, block_q, block_k,
                interpret):
    """The forward kernel in its two-product form, one (batch, head) row a
    program: ``(o (b,h,s,value width), lse f32 (b,h,s,1))``."""
    layout, (qr, qrr, kr, vr, krr), (sq, sk), blocks, (r, dv), ins = \
        _two_product_plan(q, q_rope, k, k_rope, v, block_q, block_k)
    block_q, block_k = blocks
    f32 = jnp.dtype(jnp.float32)
    main, rope = ins(1, 2)
    o_block, row_block = (block_q, dv), layout.block(block_q, stat=True)
    scratch = (o_block, (block_q, _LANES), (block_q, _LANES))
    sweep, grid_tail = _grid(causal, False, sq, sk, block_q, block_k, None,
                             (0, 0))
    _announce_two_product(
        "flash_fwd", layout, qr, sk, blocks, (r, dv),
        [((block_q, layout.d), q.dtype), ((block_k, layout.d), k.dtype),
         ((block_k, dv), v.dtype), ((block_q, r), q.dtype),
         ((block_k, r), k_rope.dtype)],
        [(o_block, q.dtype), (row_block, f32)], causal, scratch, sweep)
    out, lse = _kernel_call(
        jnp.zeros((2,), jnp.int32), qr, kr, vr, qrr, krr, name="flash_fwd",
        body=_fwd_kernel, layout=layout, n=1,
        grid_tail=grid_tail, ins=main + rope,
        outs=((block_q, 1, False, jnp.dtype(q.dtype), sq, dv),
              (block_q, 1, True, f32, sq)),
        scratch=scratch, block_q=block_q,
        block_k=block_k, sub=_sub_tile(causal, block_k), causal=causal,
        interpret=interpret, scale=scale, sweep=sweep)
    return (out.reshape(q.shape[:3] + (dv,)),
            layout.result(lse, stat=True))


def _flash_bwd2(q, q_rope, k, k_rope, v, do, lse, delta, scale, causal,
                block_q, block_k, interpret):
    """The two backward kernels in their two-product form: the gradients of
    all five operands in their dtypes, ``k_rope``'s summed over the heads
    (the dk/dv kernel writes each head's part, (batch x heads, s, r); the
    sum over a batch row's heads is XLA's)."""
    layout, (qr, qrr, kr, vr, krr), (sq, sk), blocks, (r, dv), ins = \
        _two_product_plan(q, q_rope, k, k_rope, v, block_q, block_k)
    block_q, block_k = blocks
    dor = do.reshape(layout.units, sq, dv)
    lser, deltar = layout.stat(lse), layout.stat(delta)
    f32 = jnp.dtype(jnp.float32)
    dtype = jnp.dtype(q.dtype)
    row_block = layout.block(block_q, stat=True)

    def call(name, body, at_q, at_k, outs):
        """One backward kernel; ``outs`` are ``(length, whole length,
        lanes)`` of each result, all at the grid's second index, the side at
        index 2 the inner one."""
        main, rope = ins(at_q, at_k)
        stat = (block_q, at_q, True)
        out_blocks = [((length, lanes), dtype) for length, _, lanes in outs]
        sweep, grid_tail = _grid(causal, at_k == 1, sq, sk, block_q, block_k,
                                 None, (0, 0))
        _announce_two_product(
            name, layout, qr, sk, blocks, (r, dv),
            [((block_q, layout.d), dtype), ((block_k, layout.d), dtype),
             ((block_k, dv), dtype), ((block_q, dv), dtype),
             (row_block, f32), (row_block, f32),
             ((block_q, r), dtype), ((block_k, r), dtype)], out_blocks,
            causal, sweep=sweep)
        return _kernel_call(
            jnp.zeros((2,), jnp.int32), qr, kr, vr, dor, lser, deltar, qrr,
            krr, name=name, body=body, layout=layout, n=1,
            grid_tail=grid_tail,
            ins=main + ((block_q, at_q, False, dv), stat, stat) + rope,
            outs=tuple((length, 1, False, dtype, whole, lanes)
                       for length, whole, lanes in outs),
            scratch=tuple(shape for shape, _ in out_blocks), block_q=block_q,
            block_k=block_k, sub=_sub_tile(causal, block_k), causal=causal,
            interpret=interpret, scale=scale, sweep=sweep)

    dq, dq_rope = call("flash_bwd_dq", _bwd_dq_kernel, 1, 2,
                       ((block_q, sq, layout.d), (block_q, sq, r)))
    dk, dv_, dk_rope = call("flash_bwd_dkv", _bwd_dkv_kernel, 2, 1,
                            ((block_k, sk, layout.d), (block_k, sk, dv),
                             (block_k, sk, r)))
    b, h = layout.batch, layout.num_heads
    dk_rope = dk_rope.reshape(b, h, sk, r).astype(jnp.float32).sum(1)
    return (dq.reshape(q.shape), dq_rope.reshape(q_rope.shape),
            dk.reshape(k.shape), dk_rope.astype(k_rope.dtype),
            dv_.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _two_product_kernels(q, q_rope, k, k_rope, v, scale, causal, block_q,
                         block_k, interpret):
    """:func:`flash_attention_two_product` by the kernels only
    (``interpret`` True or False)."""
    return _flash_fwd2(q, q_rope, k, k_rope, v, scale, causal, block_q,
                       block_k, interpret)[0]


def _two_product_fwd_rule(q, q_rope, k, k_rope, v, scale, causal, block_q,
                          block_k, interpret):
    o, lse = _flash_fwd2(q, q_rope, k, k_rope, v, scale, causal, block_q,
                         block_k, interpret)
    return o, (q, q_rope, k, k_rope, v, o, lse)


def _two_product_bwd_rule(scale, causal, block_q, block_k, interpret, res,
                          do):
    q, q_rope, k, k_rope, v, o, lse = res
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
        .sum(-1, keepdims=True)
    return _flash_bwd2(q, q_rope, k, k_rope, v, do, lse, delta, scale,
                       causal, block_q, block_k, interpret)


_two_product_kernels.defvjp(_two_product_fwd_rule, _two_product_bwd_rule)


def flash_attention_two_product(q, q_rope, k, k_rope, v, scale, causal=True,
                                block_q=512, block_k=1024, interpret=None):
    """Attention whose score is the sum of two products, fused forward and
    backward: ``softmax((q . k + q_rope . k_rope) * scale [+ causal mask])
    v``.  ``q``, ``k``: (batch, heads, s, d); ``q_rope``: (batch, heads, s,
    r); ``k_rope``: (batch, s, r), ONE key a position that every head of the
    row reads (its block's index is the program's batch row; its gradient is
    the sum over the heads); ``v``: (batch, heads, s, value width), a width
    of its own, as the result's.  Nothing is concatenated, broadcast over the
    heads or padded to a common width in HBM.  The three kernels are
    :func:`flash_attention`'s (``flash_fwd``, ``flash_bwd_dq``,
    ``flash_bwd_dkv``) in their two-product form on the split layout, one
    (batch, head) row a program.  ``interpret=None`` picks the kernels on
    TPU and :func:`two_product_reference` elsewhere, as it does where the
    blocks do not divide the sequence."""
    s = q.shape[2]
    if s % min(block_q, s) or s % min(block_k, s):
        _log_path("dense", f"two-product: seq {s} does not divide blocks "
                           f"({block_q}, {block_k})")
        interpret = None
    else:
        interpret = _kernels_serve(_pallas_interpret(interpret, q.dtype), s,
                                   block_q)
    if interpret is None:
        return two_product_reference(q, q_rope, k, k_rope, v, scale, causal)
    return _two_product_kernels(q, q_rope, k, k_rope, v, scale, causal,
                                block_q, block_k, interpret)


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
    return _kernels_serve(_pallas_interpret(None, q.dtype), sq,
                          block_q) is not None


def block_attn_fwd(q, k, v, causal, q_offset, k_offset, block_q=512,
                   block_k=1024, interpret=False, window=None):
    """One attention block: (o f32, lse f32 (..., sq, 1)).

    Offsets may be traced scalars (ring hop positions). Rows with no
    visible key get o = 0 and lse = -1e30 (finite sentinel), which the
    logsumexp-combine treats as an empty partial."""
    if _use_pallas(q, k, block_q, block_k, interpret):
        return _flash_fwd(q, k, v, causal, block_q, block_k, q_offset,
                          k_offset, interpret, out_dtype=jnp.float32,
                          window=window)
    o, lse = _dense_fwd(q, k, v, causal, q_offset, k_offset, window)
    if causal:
        # Match the kernel's fully-masked-row convention: the dense softmax
        # spreads weight uniformly over masked keys instead; zero it.
        empty = lse <= _NEG_INF / 2
        o = jnp.where(empty, 0.0, o)
        lse = jnp.where(empty, _NEG_INF, lse)
    return o, lse


def block_attn_bwd(q, k, v, do, lse, delta, causal, q_offset, k_offset,
                   block_q=512, block_k=1024, interpret=False, window=None):
    """Fused per-block backward vs the GLOBAL lse (FA2 cross-block form):
    p = exp(s - lse) are the true softmax probabilities even when this block
    is one hop of a longer ring. Returns (dq, dk, dv) f32."""
    if _use_pallas(q, k, block_q, block_k, interpret):
        return _flash_bwd(q, k, v, do, lse, delta, causal, block_q, block_k,
                          q_offset, k_offset, interpret,
                          out_dtype=jnp.float32, window=window)
    return _dense_bwd(q, k, v, do, lse, delta, causal, q_offset, k_offset,
                      window)


def combine_blocks(o_a, lse_a, o_b, lse_b):
    """Merge two finalized attention partials (o, lse) -> (o, lse).

    Standard logsumexp reweighting; empty partials (lse = -1e30) get weight
    ~0 without any nan path (sentinels are finite)."""
    lse = jnp.logaddexp(lse_a, lse_b)
    return (o_a * jnp.exp(lse_a - lse) + o_b * jnp.exp(lse_b - lse)), lse


# ---------------------------------------------------------------------------
# public fused attention


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=False, block_q=512, block_k=1024,
                    q_offset=0, interpret=None, window=None):
    """softmax(qk^T/sqrt(d) [+ causal mask]) v, fused fwd AND bwd.

    q: (batch, heads, seq, head_dim); k/v the same, or with fewer heads
    (batch, key-value heads, seq, head_dim): query head h then reads
    key-value head ``h // (heads / key-value heads)`` from where it lies,
    and dk and dv come back that wide. ``q_offset`` shifts q's global
    positions for causal masking (used when q is a shard of a longer
    sequence); it must be a multiple of ``block_q``. Under ``causal`` a
    ``window`` lets position t see the keys s with ``t - window < s <= t``
    only. ``interpret=None`` picks the Pallas kernels on TPU and the dense
    path elsewhere.
    """
    interpret = _kernels_serve(_pallas_interpret(interpret, q.dtype),
                               q.shape[2], block_q)
    if interpret is None:
        return _dense_reference(q, k, v, causal, q_offset, window)
    o, _ = _flash_fwd(q, k, v, causal, block_q, block_k, q_offset, 0,
                      interpret, window=window)
    return o


def _fwd_rule(q, k, v, causal, block_q, block_k, q_offset, interpret,
              window):
    interpret = _kernels_serve(_pallas_interpret(interpret, q.dtype),
                               q.shape[2], block_q)
    if interpret is None:
        o, lse = _dense_fwd(q, k, v, causal, q_offset, window=window)
        return o.astype(q.dtype), (q, k, v, o.astype(q.dtype), lse)
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k, q_offset, 0,
                        interpret, window=window)
    return o, (q, k, v, o, lse)


def _bwd_rule(causal, block_q, block_k, q_offset, interpret, window, res,
              do):
    q, k, v, o, lse = res
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
        .sum(-1, keepdims=True)
    # interpret semantics match the forward: None = auto (Pallas on TPU,
    # dense elsewhere); False = native Pallas kernels; True = interpreted
    # Pallas. An explicit False must NOT mean "dense" — that would hand the
    # default TPU transformer path the O(s^2) dense backward.
    interpret = _kernels_serve(_pallas_interpret(interpret, q.dtype),
                               q.shape[2], block_q)
    if interpret is None:
        dq, dk, dv = _dense_bwd(q, k, v, do, lse, delta, causal, q_offset,
                                window=window)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
    return _flash_bwd(q, k, v, do, lse, delta, causal, block_q, block_k,
                      q_offset, 0, interpret, window=window)


flash_attention.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_packed(q, k, v, causal, block_q, block_k, interpret):
    """:func:`flash_attention` on the packed layout: q/k/v and the result
    are ``(batch, seq, heads, head_dim)``, the ``(batch, seq, heads x
    head_dim)`` a projection writes seen as heads.  The kernels only
    (``interpret`` is True or False): the caller has settled that they serve
    the shape (``_heads_per_block``) and the backend."""
    return _packed_fwd_rule(q, k, v, causal, block_q, block_k, interpret)[0]


def _packed_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k, 0, 0, interpret,
                        packed=True)
    return o, (q, k, v, o, lse)


def _packed_bwd_rule(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    # (b,s,h) sums to the statistics' (b,h,s,1): 1/d of an operand's bytes.
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
        .sum(-1).transpose(0, 2, 1)[..., None]
    return _flash_bwd(q, k, v, do, lse, delta, causal, block_q, block_k,
                      0, 0, interpret, packed=True)


_flash_attention_packed.defvjp(_packed_fwd_rule, _packed_bwd_rule)


def _free_axes():
    """``(mesh, abstract mesh, {axis: size})`` for the axes of the active
    mesh that no manual region covers yet, None where there is nothing to
    cover (no mesh, one device, or every axis manual already)."""
    from autodist_tpu.parallel import context as parallel_ctx
    ctx = parallel_ctx.current()
    mesh = ctx.mesh if ctx is not None else None
    if mesh is None or mesh.size == 1:
        return None
    am = jax.sharding.get_abstract_mesh()
    free = {a: n for a, n in mesh.shape.items() if a not in am.manual_axes}
    return (mesh, am, free) if free else None


def _under_full_manual(fn, *operands, heads_dim=1):
    """``fn(*operands)`` where a Mosaic kernel can lower on the active mesh.

    jax refuses to partition a ``pallas_call`` automatically ("Mosaic
    kernels cannot be automatically partitioned"): on a mesh of several
    devices the kernel must sit in a region that is manual over every mesh
    axis.  The Runner's explicit path over ``{data}`` alone already is one;
    on the GSPMD path, or with further axes left automatic, the call goes
    under a ``shard_map`` over the axes still free — batch split over
    ``data``, heads (dimension ``heads_dim`` of the rank-4 operands, the
    first of which is q) over ``model``; an operand of lower rank has no
    heads (the two-product form's shared key) and is split over ``data``
    alone.  Any other axis of size > 1 would run the whole kernel on each of
    its devices, so it raises instead.
    """
    found = _free_axes()
    if found is None:
        return fn(*operands)
    q = operands[0]
    mesh, am, free = found
    sizes = dict(mesh.shape)
    dim_of = {const.MESH_AXIS_DATA: 0, const.MESH_AXIS_MODEL: heads_dim}
    spec = [None] * q.ndim
    for a, size in free.items():
        if size == 1:
            continue
        dim = dim_of.get(a)
        if dim is None or any(x.shape[dim] % size for x in operands
                              if x.ndim == q.ndim):
            raise NotImplementedError(
                f"flash attention on mesh {sizes}: axis {a!r} cannot split "
                f"q {q.shape} (batch over 'data', heads over 'model'), and "
                f"leaving it automatic would run the whole kernel on each "
                f"of its {size} devices; pass attn_fn= to the model or "
                f"pick a strategy without that axis")
        spec[dim] = a
    specs = tuple(P(*spec) if x.ndim == q.ndim else P(spec[0])
                  for x in operands)
    _log_path("pallas", f"under shard_map over {list(free)} of mesh {sizes}")
    # Nested in a manual region, jax wants the context's own mesh.
    return jax.shard_map(fn, mesh=am if dict(am.shape) == sizes else mesh,
                         in_specs=specs, out_specs=specs[0],
                         axis_names=set(free), check_vma=False)(*operands)


def make_flash_attn_fn(causal=False, block_q=512, block_k=1024):
    """An ``attn_fn(q, k, v, mask)`` hook (models.layers.mha signature).

    Uses the Pallas kernels on TPU when the sequence divides the block
    size; anything else — including an explicit boolean ``mask``, which the
    fused kernel does not consume — takes the dense reference so masking
    semantics are never dropped, and logs that it did.

    The hook carries ``attn_fn.bshd(num_heads, head_dim)``, which
    ``models.layers.mha`` reads off it: the same attention for q/k/v and a
    result of ``(batch, seq, heads, head_dim)``, the projections' own layout
    seen as heads, which the kernels read and write themselves so that
    ``mha`` transposes nothing; or None where heads of that shape keep
    ``(batch, heads, seq, head_dim)`` (``_heads_per_block``; a mesh axis
    that splits the heads) and ``mha`` and the program are as they were.
    It also carries ``attn_fn.two_product(q, q_rope, k, k_rope, v, scale)``,
    the two-product form for ``models.layers.mla``, and says by
    ``attn_fn.grouped`` and ``attn_fn.windowed`` that it reads keys and
    values of fewer heads than q where they lie (the split layout) and
    takes ``window=`` (position t sees the keys s, ``t - window < s <= t``;
    an explicit ``mask`` then has to hold the window itself).
    """
    from autodist_tpu.models import layers as L

    def kernels(s, dtype, mask):
        """``(block_q, block_k, interpret)`` where the kernels serve a call
        of ``s`` positions, None where the dense reference does."""
        if mask is not None:
            _log_path("dense", "an explicit mask was passed")
            return None
        bq, bk = min(block_q, s), min(block_k, s)
        if s % bq != 0 or s % bk != 0:
            _log_path("dense", f"seq {s} does not divide blocks "
                               f"({block_q}, {block_k})")
            return None
        interpret = _kernels_serve(_pallas_interpret(None, dtype), s, bq)
        return None if interpret is None else (bq, bk, interpret)

    def attn_fn(q, k, v, mask=None, window=None):
        plan = kernels(q.shape[2], q.dtype, mask)
        if plan is None:
            return (L.dot_product_attention(q, k, v, mask) if mask is not None
                    else _dense_reference(q, k, v, causal, window=window))
        bq, bk, interpret = plan
        return _under_full_manual(
            lambda ql, kl, vl: flash_attention(ql, kl, vl, causal, bq, bk,
                                               0, interpret, window),
            q, k, v)

    def packed(q, k, v, mask=None):
        plan = kernels(q.shape[1], q.dtype, mask)
        if plan is None:        # the dense reference, in the layout it reads
            q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
            return attn_fn(q, k, v, mask).transpose(0, 2, 1, 3)
        bq, bk, interpret = plan
        return _under_full_manual(
            lambda ql, kl, vl: _flash_attention_packed(
                ql, kl, vl, causal, bq, bk, interpret), q, k, v, heads_dim=2)

    def bshd(num_heads, head_dim):
        found = _free_axes()
        if (_heads_per_block(num_heads, head_dim) is None
                or found and found[2].get(const.MESH_AXIS_MODEL, 1) > 1):
            return None
        return packed

    def two_product(q, q_rope, k, k_rope, v, scale):
        """:func:`flash_attention_two_product` under the hook's causality and
        blocks: what ``models.layers.mla`` calls."""
        return _under_full_manual(
            lambda *operands: flash_attention_two_product(
                *operands, scale, causal, block_q, block_k),
            q, q_rope, k, k_rope, v)

    attn_fn.bshd = bshd
    attn_fn.two_product = two_product
    # ``models.layers.mha`` reads these: the hook takes keys and values of
    # fewer heads than q as they are, and a ``window`` argument.
    attn_fn.grouped = attn_fn.windowed = True
    return attn_fn
