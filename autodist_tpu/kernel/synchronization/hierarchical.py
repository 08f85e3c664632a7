"""Two-level topology-aware collectives: full-precision ICI, quantized DCN.

The flat compressor path (``compressor.py``) quantizes the whole wire, so
the fast intra-host ICI leg pays the same quantization noise as the slow
cross-host DCN leg it is trying to hide.  This module splits one gradient
all-reduce into three legs expressed over the topology (EQuARX family —
quantize *inside* the collective; cf. PAPERS.md):

  1. reduce-scatter, full precision, over intra-host ICI groups;
  2. all-reduce of the 1/d-size shard across hosts (DCN), with the shard
     quantized to the chosen DCN codec (``bf16`` / ``int8`` /
     ``int8ef`` = int8 + error feedback on the shard);
  3. all-gather, full precision, back over the ICI groups.

Wire effect: the ICI leg carries full-precision bytes (it is ~an order of
magnitude faster, per ``Topology`` tiers), the DCN leg carries
``codec_factor x (1/d)`` of the gradient — exactly what
``CostModel.hierarchical_ar_cost`` prices.

Leg layout over the runner's flat ``data`` axis (host-major device order,
as produced by ``ResourceSpec``): with d = devices/host and h = hosts,
ICI group g_h = [h*d .. h*d+d-1], DCN group g_i = [i, d+i, 2d+i, ...].
Execution uses subgroup collectives (``axis_index_groups``);
``grouped=False`` forces the same schedule over intra-group ppermute rings
(the tests' second transport).  :func:`hier_mean_nested` is the same
schedule over explicit nested ``(dcn, ici)`` mesh axes (see
``cluster.build_hierarchical_mesh``).

Single-host (h == 1) degenerates to the FLAT codec path — bitwise
identical wire and numerics, zero cost delta — so hierarchical plans are
safe to leave enabled everywhere.
"""
import jax
import jax.numpy as jnp

from autodist_tpu import const
from autodist_tpu.kernel.synchronization.compressor import (
    _INT8_BLOCK, _axis_size, _int8_quantize, int8_transport, mean_bf16_wire,
    mean_int8_wire)

# DCN-leg wire bytes as a fraction of f32 (int8: 1 byte/elem + one f32
# scale per _INT8_BLOCK elems; keep in sync with tuner/cost_model.py).
CODEC_FACTORS = {
    "f32": 1.0,
    "bf16": 0.5,
    "int8": (1.0 + 4.0 / _INT8_BLOCK) / 4.0,
    "int8ef": (1.0 + 4.0 / _INT8_BLOCK) / 4.0,
}


def resolve_legs(world, devices_per_host=None):
    """Split a flat data axis of ``world`` devices into (ici, dcn) legs.

    Returns ``(d, h)`` with ``d * h == world``: d devices per host (ICI
    leg), h hosts (DCN leg).  ``AUTODIST_HIER_ICI`` overrides the
    resource-spec hint (bench/test knob for faking multi-host on one
    host).  Any invalid split — unknown, non-divisor, or >= world —
    degenerates to ``(world, 1)``: a single all-ICI leg, i.e. the flat
    path."""
    world = int(world)
    d = int(const.ENV.AUTODIST_HIER_ICI.val or 0) or int(devices_per_host or 0)
    if d <= 0 or d >= world or world % d:
        return world, 1
    return d, world // d


def ici_groups(world, d):
    """Host-major intra-host groups: [[0..d-1], [d..2d-1], ...]."""
    return [[h * d + i for i in range(d)] for h in range(world // d)]


def dcn_groups(world, d):
    """Cross-host groups at equal ICI position: [[0, d, 2d..], [1, d+1..]]."""
    return [[h * d + i for h in range(world // d)] for i in range(d)]


# ---------------------------------------------------------------------------
# Trace-time wire tally.  Every hierarchical (and degenerate-flat) reduce
# records its per-device wire bytes per leg while being TRACED; bench and
# tests read the tally to check measured bytes against the cost model's
# prediction.  Reset before (re)compiling — retraces re-add.
# ---------------------------------------------------------------------------
_WIRE_TALLY = {"ici": 0.0, "dcn": 0.0}


def reset_wire_tally():
    _WIRE_TALLY["ici"] = 0.0
    _WIRE_TALLY["dcn"] = 0.0


def wire_tally():
    """Per-device wire bytes received per leg, summed over traced reduces."""
    return dict(_WIRE_TALLY)


def _tally(leg, nbytes):
    _WIRE_TALLY[leg] += float(nbytes)


def _tally_hier(nbytes, d, h, codec):
    """Per-device received bytes for one hierarchical reduce of ``nbytes``
    (f32 payload): RS + AG full precision on ICI, codec-compressed shard
    on DCN.  Mirrors ``Topology.hier_wire_split`` exactly — the bench's
    measured-vs-predicted check rides this equality."""
    _tally("ici", 2.0 * nbytes * (d - 1) / d)
    f = CODEC_FACTORS[codec]
    shard = nbytes / d
    if codec.startswith("int8") and int8_transport(h) == "allgather":
        _tally("dcn", (h - 1) * shard * f)
    else:
        if codec.startswith("int8"):  # wide DCN leg: bf16 switch (below)
            f = CODEC_FACTORS["bf16"]
        _tally("dcn", 2.0 * shard * f * (h - 1) / h)


def _tally_flat(nbytes, d, h, factor=1.0):
    """Per-device received bytes for a FLAT ring all-reduce of ``nbytes``
    whose ring happens to span ``h`` hosts (the flat arm of the same
    topology, for ratio baselines)."""
    w = nbytes * factor
    _tally("ici", 2.0 * w * (d - 1) / d)
    if h > 1:
        _tally("dcn", 2.0 * (w / d) * (h - 1) / h)


# ---------------------------------------------------------------------------
# DCN-leg codecs.  Each takes the full-precision per-host shard sum `rs`
# (f32, 1-D, length a multiple of _INT8_BLOCK) plus optional EF state and
# a pair of transport closures; returns (sum over all W devices, state').
# Transport closures abstract over grouped collectives vs nested axes:
#   psum_fn(x)       -> sum of x across the h hosts of this device's group
#   gather_fn(x)     -> stack of x from the h hosts, shape (h,) + x.shape
# ---------------------------------------------------------------------------


def _dcn_leg(rs, state, codec, h, psum_fn, gather_fn):
    if codec == "f32":
        return psum_fn(rs), state
    if codec == "bf16":
        # bf16 wire; XLA CPU's AllReducePromotion CHECK-fails on grouped
        # bf16 all-reduce, so on CPU quantization is emulated by a cast
        # round-trip and the collective runs f32 (same wire semantics as
        # compressor.mean_bf16_wire).
        wire = rs.astype(jnp.bfloat16)
        if jax.default_backend() == "cpu":
            return psum_fn(wire.astype(rs.dtype)), state
        return psum_fn(wire).astype(rs.dtype), state
    # int8 family.  Wide DCN legs (h past the transport crossover) switch
    # to the bf16 wire — same policy, same rationale, as the flat
    # Int8CompressorEF: the gather transport loses past the crossover and
    # a requantizing ring has noise EF cannot observe.
    if int8_transport(h) == "ring":
        wire = rs.astype(jnp.bfloat16)
        if codec == "int8ef":
            corrected = rs + state
            wire = corrected.astype(jnp.bfloat16)
            residual = corrected - wire.astype(rs.dtype)
            if jax.default_backend() == "cpu":
                return psum_fn(wire.astype(rs.dtype)), residual
            return psum_fn(wire).astype(rs.dtype), residual
        if jax.default_backend() == "cpu":
            return psum_fn(wire.astype(rs.dtype)), state
        return psum_fn(wire).astype(rs.dtype), state
    corrected = rs + state if codec == "int8ef" else rs
    q, scale, pad = _int8_quantize(corrected)
    qs = gather_fn(q)                                   # (h, nblk, block) i8
    ss = gather_fn(scale)                               # (h, nblk, 1) f32
    summed = (qs.astype(jnp.float32) * ss).sum(axis=0).ravel()
    if pad:
        summed = summed[:-pad]
    if codec == "int8ef":
        deq = (q.astype(jnp.float32) * scale).ravel()
        if pad:
            deq = deq[:-pad]
        # Residual from the SAME (q, scale) that went on the wire.
        return summed, corrected - deq
    return summed, state


def _flat_degenerate(x, axis_name, codec, state):
    """h == 1: the flat codec path, bitwise identical to compressor.py."""
    if codec == "f32":
        return jax.lax.pmean(x, axis_name), state
    if codec == "bf16":
        return mean_bf16_wire(x, axis_name), state
    if codec == "int8":
        return mean_int8_wire(x, axis_name), state
    # int8ef, flat: mirror Int8CompressorEF.reduce (full-gradient state).
    corrected = x + state
    if int8_transport(_axis_size(axis_name)) == "ring":
        wire = corrected.astype(jnp.bfloat16)
        residual = corrected - wire.astype(x.dtype)
        return mean_bf16_wire(corrected, axis_name), residual
    q, scale, pad = _int8_quantize(corrected.ravel())
    deq = (q.astype(jnp.float32) * scale).ravel()
    if pad:
        deq = deq[:-pad]
    residual = corrected - deq.reshape(x.shape).astype(x.dtype)
    from autodist_tpu.kernel.synchronization.compressor import \
        _int8_allgather_mean
    return _int8_allgather_mean(q, scale, pad, x.shape, x.dtype,
                                axis_name), residual


def padded_shard_len(n, d):
    """Length of the per-device ICI shard for an n-element gradient: the
    flat vector is padded so every shard is a whole number of int8 blocks
    (quantization blocks then never straddle shard boundaries)."""
    return (n + (-n) % (d * _INT8_BLOCK)) // d


def init_hier_state(n, d, h, codec, dtype=jnp.float32):
    """EF state for one variable: a DCN-shard-shaped residual when the
    legs are real, the full gradient shape when degenerate (flat EF)."""
    if codec != "int8ef":
        return ()
    if h == 1:
        return jnp.zeros((n,), dtype).reshape(-1)
    return jnp.zeros((padded_shard_len(n, d),), jnp.float32)


def hier_mean(x, axis_name, codec="bf16", devices_per_host=None, state=(),
              grouped=True):
    """Hierarchical mean all-reduce of ``x`` over the flat ``axis_name``.

    Returns ``(mean, new_state)``.  ``state`` is the EF residual for
    ``int8ef`` (from :func:`init_hier_state`), ``()`` otherwise.
    ``grouped=False`` swaps the subgroup collectives for ppermute rings
    (``tests/test_hierarchical.py`` runs both on the installed stack)."""
    W = _axis_size(axis_name)
    d, h = resolve_legs(W, devices_per_host)
    if h == 1:
        # Degenerate: EF state is kept 1-D (init_hier_state contract);
        # the flat codec works on gradient shapes.
        st_in = jnp.asarray(state).reshape(x.shape) if codec == "int8ef" \
            else state
        out, st = _flat_degenerate(x, axis_name, codec, st_in)
        _tally_flat(x.size * 4.0, W, 1, CODEC_FACTORS[codec])
        if codec == "int8ef":
            st = st.reshape(-1)
        return out, st
    shape, dtype = x.shape, x.dtype
    flat = x.ravel().astype(jnp.float32)
    n = flat.shape[0]
    shard = padded_shard_len(n, d)
    pad = shard * d - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    _tally_hier(n * 4.0, d, h, codec)
    if grouped:
        gi, gd = ici_groups(W, d), dcn_groups(W, d)
        rs = jax.lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                  tiled=True, axis_index_groups=gi)
        total, st = _dcn_leg(
            rs, state, codec, h,
            psum_fn=lambda v: jax.lax.psum(v, axis_name,
                                           axis_index_groups=gd),
            gather_fn=lambda v: jax.lax.all_gather(v, axis_name,
                                                   axis_index_groups=gd))
        mean = total / W
        out = jax.lax.all_gather(mean, axis_name, tiled=True,
                                 axis_index_groups=gi)
    else:
        out, st = _hier_mean_ppermute(flat, state, axis_name, codec,
                                      d, h, shard)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype), st


def _hier_mean_ppermute(flat, state, axis_name, codec, d, h, shard):
    """Second transport: the same three-leg schedule built from
    intra-group ppermute rings (every edge stays within one ICI or one
    DCN group).  ``flat`` is padded f32 of length ``shard * d``."""
    W = d * h
    idx = jax.lax.axis_index(axis_name)
    pos = jnp.mod(idx, d)                       # position within the host
    chunks = flat.reshape(d, shard)
    perm_i = [(hh * d + i, hh * d + (i + 1) % d)
              for hh in range(h) for i in range(d)]
    perm_d = [(hh * d + i, ((hh + 1) % h) * d + i)
              for hh in range(h) for i in range(d)]

    # Leg 1: intra-host ring reduce-scatter, full precision.  Start with
    # our own chunk; after d-1 hops we hold the full intra-host sum of
    # chunk (pos + 1) mod d.
    c = jax.lax.dynamic_index_in_dim(chunks, pos, 0, keepdims=False)

    def rs_body(step, c):
        c = jax.lax.ppermute(c, axis_name, perm_i)
        return c + jax.lax.dynamic_index_in_dim(
            chunks, jnp.mod(pos - step - 1, d), 0, keepdims=False)

    rs = jax.lax.fori_loop(0, d - 1, rs_body, c)
    own = jnp.mod(pos + 1, d)                   # chunk index we now own

    # Leg 2: cross-host ring all-reduce of the shard, codec wire.
    def ring_psum(v):
        def body(_, acc_buf):
            acc, buf = acc_buf
            buf = jax.lax.ppermute(buf, axis_name, perm_d)
            return acc + buf, buf
        acc, _ = jax.lax.fori_loop(0, h - 1, body, (v, v))
        return acc

    def ring_gather(v):
        def body(step, out_buf):
            out, buf = out_buf
            buf = jax.lax.ppermute(buf, axis_name, perm_d)
            out = jax.lax.dynamic_update_index_in_dim(
                out, buf, jnp.mod(idx // d - step - 1, h), 0)
            return out, buf
        out = jnp.zeros((h,) + v.shape, v.dtype)
        out = jax.lax.dynamic_update_index_in_dim(out, v, idx // d, 0)
        out, _ = jax.lax.fori_loop(0, h - 1, body, (out, v))
        return out

    total, st = _dcn_leg(rs, state, codec, h, ring_psum, ring_gather)
    mean = total / W

    # Leg 3: intra-host ring all-gather of the mean chunks.
    gath = jnp.zeros((d, shard), mean.dtype)
    gath = jax.lax.dynamic_update_index_in_dim(gath, mean, own, 0)

    def ag_body(step, carry):
        gath, buf = carry
        buf = jax.lax.ppermute(buf, axis_name, perm_i)
        gath = jax.lax.dynamic_update_index_in_dim(
            gath, buf, jnp.mod(pos - step, d), 0)
        return gath, buf

    gath, _ = jax.lax.fori_loop(0, d - 1, ag_body, (gath, mean))
    return gath.ravel(), st


def hier_mean_nested(x, codec="bf16", state=(), ici_axis="ici",
                     dcn_axis="dcn"):
    """The same three-leg schedule over explicit nested mesh axes (see
    ``cluster.build_hierarchical_mesh``): RS over ``ici_axis``, codec
    all-reduce over ``dcn_axis``, AG over ``ici_axis``.  For callers that
    own their mesh (and for parity tests of the grouped-collective
    expression); returns ``(mean, new_state)``."""
    d = _axis_size(ici_axis)
    h = _axis_size(dcn_axis)
    shape, dtype = x.shape, x.dtype
    flat = x.ravel().astype(jnp.float32)
    n = flat.shape[0]
    shard = padded_shard_len(n, d)
    pad = shard * d - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    _tally_hier(n * 4.0, d, h, codec)
    rs = jax.lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    total, st = _dcn_leg(
        rs, state, codec, h,
        psum_fn=lambda v: jax.lax.psum(v, dcn_axis),
        gather_fn=lambda v: jax.lax.all_gather(v, dcn_axis))
    mean = total / (d * h)
    out = jax.lax.all_gather(mean, ici_axis, tiled=True)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype), st


def program_wire_split(synchronizers, variables, world):
    """Predicted per-device wire bytes per leg for a compiled program's
    gradient reductions — feeds the ``comms.wire_ici_bytes`` /
    ``comms.wire_dcn_bytes`` gauges.  ``variables`` maps name -> nbytes;
    only dense all-reduce synchronizers are counted (sharded-state and PS
    wire is priced by the cost model, not per-leg here)."""
    from autodist_tpu.proto import strategy_pb2
    _C = strategy_pb2.AllReduceSynchronizer.Compressor
    factors = {_C.NoneCompressor: 1.0, _C.HorovodCompressor: 0.5,
               _C.HorovodCompressorEF: 0.5,
               _C.Int8Compressor: CODEC_FACTORS["int8"],
               _C.Int8CompressorEF: CODEC_FACTORS["int8ef"]}
    ici = dcn = 0.0
    for name, sync in synchronizers.items():
        ckind = getattr(sync, "compressor_kind", None)
        if ckind is None or name not in variables:
            continue
        pconfig = getattr(sync, "pconfig", None)
        if pconfig is not None and pconfig.active:
            continue  # sharded-state vars: RS/AG wire, not a dense AR
        nbytes = float(variables[name])
        codec = getattr(sync, "hier_codec", None)
        d, h = resolve_legs(world, getattr(sync, "devices_per_host", None))
        if codec and h > 1:
            ici += 2.0 * nbytes * (d - 1) / d
            f = CODEC_FACTORS[codec]
            if codec.startswith("int8") and int8_transport(h) == "allgather":
                dcn += (h - 1) * (nbytes / d) * f
            elif codec.startswith("int8"):
                dcn += 2.0 * (nbytes / d) * CODEC_FACTORS["bf16"] * (h - 1) / h
            else:
                dcn += 2.0 * (nbytes / d) * f * (h - 1) / h
        else:
            f = factors.get(ckind, 1.0)
            w = nbytes * f
            ici += 2.0 * w * (d - 1) / d
            if h > 1:
                dcn += 2.0 * (w / d) * (h - 1) / h
    return {"ici": ici, "dcn": dcn}


def gather_wire_split(synchronizers, variables, world):
    """Predicted per-device wire bytes per leg for ONE serve dispatch's
    parameter all-gathers: storage sharded over the data axis must be
    materialized on every request (docs/serving.md), a single (g-1)/g
    sweep whose shard hops cross hosts exactly like the flat ring —
    mirrors ``Topology.ag_wire_split`` byte for byte."""
    ici = dcn = 0.0
    if world <= 1:
        return {"ici": ici, "dcn": dcn}
    for name, sync in synchronizers.items():
        if name not in variables:
            continue
        pconfig = getattr(sync, "pconfig", None)
        if pconfig is None or not pconfig.active:
            continue
        try:
            if not sync.partitioned_over(const.MESH_AXIS_DATA):
                continue  # model/seq shard: activations move, not params
        except Exception:  # noqa: BLE001 - axis missing from mesh etc.
            continue
        nbytes = float(variables[name])
        d, h = resolve_legs(world, getattr(sync, "devices_per_host", None))
        ici += nbytes * (d - 1) / d
        if h > 1:
            dcn += (nbytes / d) * (h - 1) / h
    return {"ici": ici, "dcn": dcn}
