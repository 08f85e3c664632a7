"""The ``fsdp`` gradients' reduce-scatter in a form the chip keeps in flight.

Under ``PartitionedPS`` a variable is stored as its shard, gathered for the
forward pass, and its gradient is born reduce-scattered: the transpose of
the gather.  The TPU compiler turns that transpose into a fused
``all-reduce-scatter`` that runs on the core with nothing beside it, and
sinks all of them below the last layer's backward pass (PERF.md section 6,
PR 33: one burst, a quarter of the four-chip step).  Two things together
move them (PR 35):

* :func:`exchange_scatter`: the same sum as ``lax.psum_scatter(...,
  tiled=True)``, written as ``n - 1`` independent ``ppermute``s (each chip
  sends shard ``j`` of its gradient straight to chip ``j``) and one sum of
  ``n`` shards in the gradient's own dtype.  The compiler makes each a
  ``collective-permute-start`` / ``-done`` pair, which the chip runs beside
  arithmetic; no hop waits on another; ``(n - 1) / n`` of the unpadded
  payload leaves a chip, as a ring would send.
* :meth:`GradScatter.boundary`: the layer loop's op (``parallel/context.py:
  layer_boundary``).  Forward it gathers the shards of the parameters
  ahead; backward it scatters their gradients and passes the scattered
  shards and the activation's cotangent through one
  ``lax.optimization_barrier``, so that the scatter must be done before the
  backward pass goes on below that activation and cannot be sunk under it.
  ``models/transformer.py`` calls it twice a layer with half a layer's
  parameters, a whole layer ahead: on the v5e that hid half of the burst,
  where one call a layer hid nothing (the scheduler starts a permute only
  some eight fusions before its done, wherever the gradient was ready;
  PERF.md section 6, PR 35).

Which leaves take the form is decided at trace time from what can be seen:
the leaf's bytes (:data:`ASYNC_MIN_BYTES`: bandwidth, not latency, must be
its cost; biases and norm scales keep the compiler's path, which combines
them), the axis size and whether it divides the scatter dimension
(:func:`why_not`).  The Runner's explicit step offers its ``fsdp`` leaves
(:meth:`GradScatter.offer`); a model whose loop never calls the boundary
op, and every leaf the op is not handed, keeps the plain gather and its
plain transpose.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: A full gradient's bytes from which a leaf takes the exchange: under it a
#: collective's cost is its latency, and the compiler's combined path wins.
ASYNC_MIN_BYTES = 1 << 20
#: ``collective-permute``s the chip may hold in flight at once, passed by the
#: Runner to the compile of a step whose leaves took the exchange (the
#: compiler's default chains them at the end of their window).
PERMUTES_IN_FLIGHT = 32


def why_not(shape, dtype, dim, n, min_bytes=ASYNC_MIN_BYTES):
    """'' where a full gradient of this shape, scattered along ``dim`` over
    ``n`` chips, takes :func:`exchange_scatter`; else the reason it keeps
    the compiler's reduce-scatter."""
    if n < 2:
        return "one chip on the axis"
    if len(shape) < 2:
        return "rank under 2"
    if shape[dim] % n:
        return f"dimension {dim} of {shape[dim]} not divisible by {n}"
    nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    if nbytes < min_bytes:
        return f"{nbytes} bytes, under {min_bytes}"
    return ""


@functools.partial(jax.jit, static_argnums=(1, 2, 3), inline=True)
def exchange_scatter(g, axis, n, dim):
    """``lax.psum_scatter(g, axis, scatter_dimension=dim, tiled=True)`` as
    ``n - 1`` permutes that do not depend on each other: chip ``r`` sends
    shard ``(r + k) % n`` to its owner for ``k = 1 .. n - 1`` and adds what
    it receives to its own shard, in ``g``'s dtype, its own first."""
    rows = g.shape[dim] // n
    me = lax.axis_index(axis)

    def shard_of(k):
        return lax.dynamic_slice_in_dim(g, ((me + k) % n) * rows, rows, dim)
    total = shard_of(0)
    for k in range(1, n):
        total = total + lax.ppermute(
            shard_of(k), axis, [(r, (r + k) % n) for r in range(n)])
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _gather_tied(axis, n, dims, shards, x):
    with jax.named_scope("param_gather"):
        return [lax.all_gather(s, axis, axis=d, tiled=True)
                for s, d in zip(shards, dims)], x


def _gather_tied_fwd(axis, n, dims, shards, x):
    return _gather_tied(axis, n, dims, shards, x), None


def _gather_tied_bwd(axis, n, dims, _, cotangents):
    grads, x_bar = cotangents
    with jax.named_scope("grad_sync"):
        shards = [exchange_scatter(g, axis, n, d)
                  for g, d in zip(grads, dims)]
        # The scattered shards and the cotangent that enters the next
        # layer's backward pass leave together: the scheduler cannot sink
        # the permutes below what consumes ``x_bar``.
        return tuple(lax.optimization_barrier((shards, x_bar)))


_gather_tied.defvjp(_gather_tied_fwd, _gather_tied_bwd)


class GradScatter:
    """One trace's account of the ``fsdp`` leaves: what the Runner offered,
    what the model's boundary op took."""

    def __init__(self, axis, n, min_bytes=ASYNC_MIN_BYTES):
        self.axis, self.n, self.min_bytes = axis, n, min_bytes
        self._offered = {}      # id(full leaf) -> (full leaf, shard, dim)
        self.offered = 0
        self.async_leaves = 0
        self.async_bytes = 0    # what a chip sends a step
        self.boundaries = 0

    def offer(self, full, shard, dim):
        """The Runner's: ``full`` is what the model will see of ``shard``.
        The op knows a leaf by being handed this very array: a model that
        maps its parameters before its layer loop (a cast of the whole
        tree, say) hands it others, and they keep the compiler's path
        (``grad_sync.compiler_leaves`` counts them)."""
        self.offered += 1
        if not why_not(full.shape, full.dtype, dim, self.n, self.min_bytes):
            self._offered[id(full)] = (full, shard, dim)

    def boundary(self, params, x):
        """``(params, x)`` with the offered leaves of ``params`` gathered
        here and their gradients' scatter tied to ``x``'s cotangent."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        taken = [(i, *self._offered.pop(id(leaf)))
                 for i, leaf in enumerate(leaves) if id(leaf) in self._offered]
        if not taken:
            return params, x
        gathered, x = _gather_tied(
            self.axis, self.n, tuple(dim for *_, dim in taken),
            [shard for _, _, shard, _ in taken], x)
        for (i, full, _, _), leaf in zip(taken, gathered):
            leaves[i] = leaf
            self.async_bytes += (full.size * full.dtype.itemsize
                                 * (self.n - 1) // self.n)
        self.async_leaves += len(taken)
        self.boundaries += 1
        return jax.tree_util.tree_unflatten(treedef, leaves), x

    @property
    def compiler_leaves(self):
        return self.offered - self.async_leaves

    def detail(self):
        """The ``grad_sync`` event's line."""
        return (f"{self.async_leaves} leaves ({self.async_bytes} bytes a chip "
                f"a step) scattered by exchange: {self.n - 1} independent "
                f"permutes a leaf, no hop waits on another, tied to the "
                f"backward pass at {self.boundaries} boundaries; "
                f"{self.compiler_leaves} leaves keep the compiler's "
                f"reduce-scatter (under {self.min_bytes} bytes, rank 1, "
                f"uneven shards, or never handed to layer_boundary)")
