"""Gradient compressors for the explicit (shard_map) reduction path.

Parity: ``/root/reference/autodist/kernel/synchronization/compressor.py:36-284``
— ``Compressor`` wraps the collective all-reduce of one gradient:
``reduced = decompress(all_reduce(compress(grad)))`` with optional
error-feedback state.  The reference's half-precision wire format maps to
bfloat16 on TPU (native MXU/ICI dtype); PowerSGD (drafted but disabled in the
reference, ``compressor.py:208-284``) is implemented fully here since its
factor reductions are small dense matmuls — exactly what the MXU wants.

All compressors are pure: state (error residual, PowerSGD Q factor) is
threaded through, so they compose with jit/shard_map.
"""
from abc import ABC, abstractmethod

import numpy as np
import jax
import jax.numpy as jnp

from autodist_tpu.proto import strategy_pb2

_C = strategy_pb2.AllReduceSynchronizer.Compressor


class Compressor(ABC):
    """Wraps the mean-all-reduce of one gradient over a named mesh axis."""

    def __init__(self, var_name=""):
        self.var_name = var_name

    def init_state(self, shape, dtype):
        """Per-device compressor state for one variable (default: none)."""
        return ()

    @abstractmethod
    def reduce(self, grad, state, axis_name):
        """Return (mean-reduced gradient, new state). Runs inside shard_map."""

    @staticmethod
    def create(kind, var_name=""):
        """Name/enum-based factory (parity: ``compressor.py:116``)."""
        if isinstance(kind, str):
            kind = _C.Value(kind)
        return {_C.NoneCompressor: NoneCompressor,
                _C.HorovodCompressor: HorovodCompressor,
                _C.HorovodCompressorEF: HorovodCompressorEF,
                _C.PowerSGDCompressor: PowerSGDCompressor,
                _C.Int8Compressor: Int8Compressor,
                _C.Int8CompressorEF: Int8CompressorEF}[kind](var_name)


def mean_bf16_wire(x, axis_name):
    """Mean-reduce with a bfloat16 wire format.

    On TPU this is a true bf16 collective (half the ICI bytes).  XLA CPU's
    AllReducePromotion pass CHECK-fails on *grouped* bf16 all-reduce
    (multi-axis meshes), so on CPU the wire quantization is emulated —
    cast to bf16 and back — and the collective runs in the original dtype.
    """
    wire = x.astype(jnp.bfloat16)
    if jax.default_backend() == "cpu":
        return jax.lax.pmean(wire.astype(x.dtype), axis_name)
    return jax.lax.pmean(wire, axis_name).astype(x.dtype)


_INT8_BLOCK = 256


def _int8_quantize(x, block=_INT8_BLOCK):
    """Blockwise max-abs int8 quantization of a flat f32 vector.

    Returns (q int8 [nblk, block], scale f32 [nblk, 1], pad).  All-zero
    blocks quantize to zeros with scale 0 (dequantizes exactly)."""
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    chunks = x.reshape(-1, block).astype(jnp.float32)
    scale = jnp.max(jnp.abs(chunks), axis=1, keepdims=True) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(chunks / safe), -127, 127).astype(jnp.int8)
    return q, scale, pad


def _axis_size(axis_name):
    # Static at trace time for a named mesh axis.
    return jax.lax.axis_size(axis_name)


def _int8_allgather_mean(q, scale, pad, shape, dtype, axis_name):
    """Transport + decompress for pre-quantized (q, scale, pad): int8
    all_gather + local dequantized mean.  Summing int8 across devices would
    overflow, and XLA collectives carry the payload dtype, so the gather IS
    the compressed transport (visible as an s8 all-gather in HLO)."""
    qs = jax.lax.all_gather(q, axis_name)          # (W, nblk, block) int8
    ss = jax.lax.all_gather(scale, axis_name)      # (W, nblk, 1) f32
    deq = qs.astype(jnp.float32) * ss
    mean = deq.mean(axis=0).ravel()
    if pad:
        mean = mean[:-pad]
    return mean.reshape(shape).astype(dtype)


# Above this group size the int8 all_gather transport receives more bytes
# than an uncompressed ring all-reduce ((W-1)*N/4 vs ~2*N f32 words) and
# the gathered buffer is W x the gradient — switch to the requantizing
# ring (below), which stays compressed at any W.
_INT8_MAX_AXIS = 8


def int8_transport(group_size):
    """Transport choice for an int8 reduction over ``group_size`` devices.

    The crossover is a property of the GROUP the reduction actually runs
    over, not of the global axis: a hierarchical DCN leg spanning 2 hosts
    should gather even when the flat axis spans 32 devices, and vice
    versa.  Callers that reduce over a subgroup (``axis_index_groups``)
    must pass the live group size."""
    return "ring" if int(group_size) > _INT8_MAX_AXIS else "allgather"


def _ring_int8_mean(x, axis_name, block=_INT8_BLOCK):
    """Requantizing int8 ring all-reduce (EQuARX family — cf. PAPERS.md).

    Phase 1 is a ring reduce-scatter whose WIRE stays int8 at every hop:
    each device receives a quantized partial chunk over ``ppermute``,
    dequantizes, adds its own f32 contribution, REQUANTIZES, and forwards.
    Phase 2 all-gathers the final quantized chunks.  Received bytes per
    device: ~2N int8 payload (+ scales, 1 f32 per ``block``) independent
    of W — ~4x fewer than the 2N f32 words of an uncompressed ring, at
    ANY axis size, with O(N/W) working buffers (the gather transport's
    O(W*N) receive and W-times buffer are what it replaces past
    ``_INT8_MAX_AXIS``).  The cost is requantization noise accumulating
    over the W-1 hops (stateless; convergence pinned by
    ``tests/test_int8_compressor.py``)."""
    W = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.ravel().astype(jnp.float32)
    n = flat.shape[0]
    chunk = max(block, -(-n // (W * block)) * block)  # block multiple
    total = chunk * W
    if total > n:
        flat = jnp.concatenate([flat, jnp.zeros((total - n,), jnp.float32)])
    chunks = flat.reshape(W, chunk)
    perm = [(i, (i + 1) % W) for i in range(W)]

    def quant(c):
        q, s, _ = _int8_quantize(c, block)
        return q, s

    def deq(q, s):
        return (q.astype(jnp.float32) * s).ravel()

    # Phase 1: device i starts with its own chunk i; after hop s it holds
    # the partial sum of chunk (i - s - 1) mod W; after W-1 hops, the FULL
    # sum of chunk (i + 1) mod W.
    q, s = quant(jax.lax.dynamic_index_in_dim(chunks, idx, 0,
                                              keepdims=False))

    def body(step, carry):
        q, s = carry
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        local = jax.lax.dynamic_index_in_dim(
            chunks, jnp.mod(idx - step - 1, W), 0, keepdims=False)
        return quant(deq(q, s) + local)

    q, s = jax.lax.fori_loop(0, W - 1, body, (q, s))

    # Phase 2: int8 all-gather of the final chunks; source j holds chunk
    # (j + 1) mod W, so a roll of 1 restores flat order.
    qg = jax.lax.all_gather(q, axis_name)          # (W, nblk, block) int8
    sg = jax.lax.all_gather(s, axis_name)          # (W, nblk, 1) f32
    ordered = jnp.roll(qg.astype(jnp.float32) * sg, 1, axis=0)
    mean = ordered.reshape(-1)[:n] / W
    return mean.reshape(shape).astype(dtype)


def mean_int8_wire(x, axis_name, block=_INT8_BLOCK, group_size=None):
    """Mean-reduce with a blockwise-scaled int8 wire format (QSGD/EQuARX
    family — cf. PAPERS.md).  Payload is 1 byte/element + one f32 scale per
    ``block`` elements.  At group sizes <= ``_INT8_MAX_AXIS`` the transport
    is an all_gather (one quantization, lowest noise); beyond that the
    gather transport loses (O(W*N) receive + a W-times gradient-size
    buffer) and the reduction switches to the requantizing ring, which
    stays int8 on the wire at any axis size.  ``group_size`` overrides the
    crossover input when the reduction spans a subgroup of the axis (see
    :func:`int8_transport`); default is the full axis size."""
    live = group_size if group_size else _axis_size(axis_name)
    if int8_transport(live) == "ring":
        return _ring_int8_mean(x, axis_name, block)
    shape, dtype = x.shape, x.dtype
    q, scale, pad = _int8_quantize(x.ravel(), block)
    return _int8_allgather_mean(q, scale, pad, shape, dtype, axis_name)


class NoneCompressor(Compressor):
    """Identity wire format: plain pmean."""

    def reduce(self, grad, state, axis_name):
        return jax.lax.pmean(grad, axis_name), state


class HorovodCompressor(Compressor):
    """Half-width wire format: reduce in bfloat16, accumulate back in f32.

    (The reference casts fp16<->fp32, ``compressor.py:169-201``; bf16 keeps
    fp32's exponent range, the right trade on TPU.)
    """

    def reduce(self, grad, state, axis_name):
        return mean_bf16_wire(grad, axis_name), state


class HorovodCompressorEF(Compressor):
    """bf16 wire format + error feedback: the quantization error is carried
    forward and re-injected next step (``compressor.py:120-143,204-205``)."""

    def init_state(self, shape, dtype):
        return jnp.zeros(shape, dtype)

    def reduce(self, grad, state, axis_name):
        corrected = grad + state
        wire = corrected.astype(jnp.bfloat16)
        residual = corrected - wire.astype(grad.dtype)
        reduced = mean_bf16_wire(corrected, axis_name)
        return reduced, residual


class Int8Compressor(Compressor):
    """Blockwise-scaled int8 wire format (stateless; fusable)."""

    def reduce(self, grad, state, axis_name):
        return mean_int8_wire(grad, axis_name), state


class Int8CompressorEF(Compressor):
    """int8 wire format + error feedback: the local quantization error is
    carried forward and re-injected next step, recovering full-precision
    convergence in expectation (same contract as HorovodCompressorEF).
    The residual is computed from the SAME (q, scale) tensors that go on
    the wire, so send and correction cannot drift apart."""

    def init_state(self, shape, dtype):
        return jnp.zeros(shape, dtype)

    def reduce(self, grad, state, axis_name):
        corrected = grad + state
        if int8_transport(_axis_size(axis_name)) == "ring":
            # Wide axes: bf16 wire + EF (NOT the requantizing ring the
            # stateless wire switches to).  EF's contract is "the residual
            # is the error of quantizing MY gradient", but the ring never
            # quantizes the local gradient — its noise lives in shared
            # partial sums across hops, which no single device can observe
            # or carry forward.  2x compression with honest error feedback
            # beats 4x with noise EF cannot see.
            wire = corrected.astype(jnp.bfloat16)
            residual = corrected - wire.astype(grad.dtype)
            return mean_bf16_wire(corrected, axis_name), residual
        q, scale, pad = _int8_quantize(corrected.ravel())
        deq_local = (q.astype(jnp.float32) * scale).ravel()
        if pad:
            deq_local = deq_local[:-pad]
        residual = corrected - deq_local.reshape(grad.shape).astype(grad.dtype)
        reduced = _int8_allgather_mean(q, scale, pad, grad.shape, grad.dtype,
                                       axis_name)
        return reduced, residual


class PowerSGDCompressor(Compressor):
    """Rank-r PowerSGD (arXiv:1905.13727) with error feedback.

    The gradient is viewed as a 2-D matrix M (dim0 x rest); the all-reduce of
    M is replaced by all-reduces of the rank-r factors P = M Q and
    Q' = M^T P-hat — O(r*(n+m)) words on the wire instead of O(n*m).
    The reference drafted this but left it disabled
    (``compressor.py:208-284``); here it is a supported wire format.
    """

    def __init__(self, var_name="", rank=2):
        super().__init__(var_name)
        self.rank = rank

    def _matrix_shape(self, shape):
        if len(shape) < 2:
            return None
        m = int(shape[0])
        n = int(np.prod(shape[1:]))
        return m, n

    def init_state(self, shape, dtype):
        mn = self._matrix_shape(shape)
        if mn is None:  # vectors/scalars are reduced uncompressed
            return ()
        m, n = mn
        # Deterministic Q init: every process/device must derive the same seed
        # (Python hash() is salted per-process — md5 is stable).
        import hashlib
        seed = int(hashlib.md5(self.var_name.encode()).hexdigest()[:8], 16)
        q = jax.random.normal(jax.random.PRNGKey(seed),
                              (n, self.rank), dtype=jnp.float32)
        residual = jnp.zeros(shape, dtype)
        return {"q": q, "residual": residual}

    @staticmethod
    def _orthogonalize(p):
        q, _ = jnp.linalg.qr(p)
        return q

    def reduce(self, grad, state, axis_name):
        mn = self._matrix_shape(grad.shape)
        if mn is None:
            return jax.lax.pmean(grad, axis_name), state
        m, n = mn
        matrix = (grad + state["residual"]).reshape(m, n).astype(jnp.float32)
        p = jax.lax.pmean(matrix @ state["q"], axis_name)          # (m, r)
        p_hat = self._orthogonalize(p)
        q = jax.lax.pmean(matrix.T @ p_hat, axis_name)             # (n, r)
        approx = (p_hat @ q.T).astype(grad.dtype)                  # (m, n)
        residual = (matrix - approx.astype(jnp.float32)).reshape(grad.shape).astype(grad.dtype)
        return approx.reshape(grad.shape), {"q": q, "residual": residual}
