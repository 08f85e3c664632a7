"""Shared pure-JAX layer library for the model zoo.

Models are plain functions over explicit parameter pytrees (dicts keyed by
logical names) — the names are what strategy builders see (GraphItem
``VariableItem.name``), so layout here is API surface: ``embed*`` tables get
sparse-access detection (gather), kernels named ``*/kernel`` get axis-aware
partitioning, and Megatron-style column/row splits key off ``attn/*`` and
``mlp/*`` scopes.

TPU notes: every matmul/conv takes a ``dtype`` compute policy (default
bfloat16 on TPU-class inputs keeps the MXU fed); parameters stay float32 and
are cast at use — the standard mixed-precision recipe. All control flow is
static; recurrence uses ``lax.scan``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# -- initializers ------------------------------------------------------------

def glorot(key, shape, dtype=jnp.float32, in_axis=-2, out_axis=-1):
    fan_in = shape[in_axis] * int(np.prod([shape[i] for i in range(len(shape))
                                           if i not in (in_axis % len(shape),
                                                        out_axis % len(shape))]))
    fan_out = shape[out_axis] * int(np.prod([shape[i] for i in range(len(shape))
                                             if i not in (in_axis % len(shape),
                                                          out_axis % len(shape))]))
    scale = math.sqrt(2.0 / max(1.0, (fan_in + fan_out) / 2.0))
    return scale * jax.random.truncated_normal(key, -2, 2, shape, dtype)


def he_conv(key, shape, dtype=jnp.float32):
    """He-normal for HWIO conv kernels."""
    fan_in = int(np.prod(shape[:-1]))
    return jax.random.normal(key, shape, dtype) * math.sqrt(2.0 / fan_in)


def normal(key, shape, stddev=0.02, dtype=jnp.float32):
    return stddev * jax.random.normal(key, shape, dtype)


# -- dense / conv ------------------------------------------------------------

def dense_init(key, in_dim, out_dim, use_bias=True):
    p = {"kernel": glorot(key, (in_dim, out_dim))}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,))
    return p


def dense(p, x, dtype=None):
    k = p["kernel"]
    if dtype is not None:
        x, k = x.astype(dtype), k.astype(dtype)
    y = x @ k
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


def conv_init(key, kh, kw, in_ch, out_ch, use_bias=False):
    p = {"kernel": he_conv(key, (kh, kw, in_ch, out_ch))}
    if use_bias:
        p["bias"] = jnp.zeros((out_ch,))
    return p


def conv(p, x, stride=1, padding="SAME", dtype=None):
    """NHWC conv with HWIO kernel (XLA's native TPU layout)."""
    k = p["kernel"]
    if dtype is not None:
        x, k = x.astype(dtype), k.astype(dtype)
    y = lax.conv_general_dilated(
        x, k, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


# -- normalization -----------------------------------------------------------

def batchnorm_init(ch):
    return {"scale": jnp.ones((ch,)), "bias": jnp.zeros((ch,))}


def batchnorm(p, x, eps=1e-5):
    """Train-mode batch norm (batch statistics; no running averages).

    Cross-replica statistics are intentionally *local* per data shard — the
    standard large-batch training setup; sync-BN would be a psum here.
    Statistics are computed in float32 regardless of compute dtype.
    """
    axes = tuple(range(x.ndim - 1))
    xf = x.astype(jnp.float32)
    mean = xf.mean(axes)
    var = xf.var(axes)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def layernorm_init(dim):
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def layernorm(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def rmsnorm_init(dim):
    return {"scale": jnp.ones((dim,))}


def rmsnorm(p, x, eps=1e-5):
    """``x / sqrt(mean(x^2) + eps) * scale`` in float32, no centring."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * p["scale"]).astype(x.dtype)


# -- embedding ---------------------------------------------------------------

def embed_init(key, vocab, dim, stddev=0.02):
    return {"embedding": normal(key, (vocab, dim), stddev)}


def embed(p, ids):
    """Gather lookup — detected as sparse access by GraphItem."""
    return p["embedding"][ids]


# -- attention ---------------------------------------------------------------

def mha_init(key, dim, num_heads, use_bias=True, qk_norm=False, head_dim=None,
             kv_heads=None, gate=False):
    """Parameters of :func:`mha`: ``query`` (dim -> num_heads x head_dim),
    ``key`` and ``value`` (dim -> kv_heads x head_dim), ``out`` (num_heads x
    head_dim -> dim); ``head_dim`` is ``dim / num_heads`` and ``kv_heads``
    is ``num_heads`` where not given, and then all four are dim x dim.
    ``qk_norm`` adds ``q_norm`` and ``k_norm``: scales over the whole
    projected vector, or with ``"head"`` one ``head_dim``-wide scale each
    that the heads share.  ``gate`` adds ``gate`` (no bias): dim ->
    num_heads, one scalar a head, or with ``"lane"`` dim -> num_heads x
    head_dim, one a lane."""
    for name, value, form in (("qk_norm", qk_norm, "head"),
                              ("gate", gate, "lane")):
        if value not in (False, True, form):
            raise ValueError(f"{name} must be False, True or {form!r}, got "
                             f"{value!r}")
    head_dim = head_dim or dim // num_heads
    wide, kv_wide = num_heads * head_dim, (kv_heads or num_heads) * head_dim
    ks = jax.random.split(key, 4)
    p = {
        "query": dense_init(ks[0], dim, wide, use_bias),
        "key": dense_init(ks[1], dim, kv_wide, use_bias),
        "value": dense_init(ks[2], dim, kv_wide, use_bias),
        "out": dense_init(ks[3], wide, dim, use_bias),
    }
    if qk_norm == "head":
        p["q_norm"], p["k_norm"] = rmsnorm_init(head_dim), \
            rmsnorm_init(head_dim)
    elif qk_norm:
        p["q_norm"], p["k_norm"] = rmsnorm_init(wide), rmsnorm_init(kv_wide)
    if gate:
        p["gate"] = dense_init(jax.random.fold_in(key, 4), dim,
                               wide if gate == "lane" else num_heads,
                               use_bias=False)
    return p


def rope_tables(seq_len, head_dim, theta=10000.0):
    """``(cos, sin)``, each (seq, head_dim) in float32, of the rotate-half
    rotary embedding: ``inv_freq_i = theta^(-2i / head_dim)`` and the
    angles repeated over both halves of a head."""
    return _angle_tables(seq_len, 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _angle_tables(seq_len, inv_freq):
    """Cos and sin of ``position x inv_freq``, the angles repeated over
    both halves of the rotated lanes."""
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def yarn_rope_tables(seq_len, lanes, theta, factor, original_len, beta_fast,
                     beta_slow, attention_factor):
    """``(cos, sin)``, each (seq, lanes) in float32, of the rotate-half
    rotary embedding over ``lanes`` lanes with YaRN's frequencies
    (arXiv:2309.00071, as the transformers library computes them): pair
    ``i`` of the ``lanes / 2`` turns by ``inv_freq_i = (1 - r_i) / (factor
    theta^(2i / lanes)) + r_i / theta^(2i / lanes)``, ``r_i = 1 - clip((i -
    low) / (high - low), 0, 1)``, ``low`` / ``high`` the floor / ceiling of
    ``lanes ln(original_len / (2 pi beta)) / (2 ln theta)`` at ``beta_fast``
    / ``beta_slow``, clamped to ``[0, lanes - 1]``: the fast pairs keep their
    frequency, the slow ones are stretched ``factor`` times.  Cos and sin
    carry ``attention_factor``.  Static: the same table at every length."""
    def correction(beta):
        return lanes * math.log(original_len / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), lanes - 1)
    if low == high:
        high += 0.001       # the library's guard against a ramp of no width
    pair = jnp.arange(lanes // 2, dtype=jnp.float32)
    keep = 1.0 - jnp.clip((pair - low) / (high - low), 0.0, 1.0)
    freq = theta ** (2.0 * pair / lanes)
    cos, sin = _angle_tables(
        seq_len, (1.0 - keep) / (factor * freq) + keep / freq)
    return cos * attention_factor, sin * attention_factor


def apply_rope(x, tables):
    """Rotate ``x`` (..., head_dim) by its positions, ``tables`` broadcasting
    against it ((seq, head_dim) for (batch, heads, seq, head_dim)): element
    i pairs with element i + head_dim / 2 (the rotate-half form).  Tables
    narrower than a head rotate its first lanes and pass the rest."""
    cos, sin = tables
    lanes = cos.shape[-1]
    if lanes < x.shape[-1]:
        return jnp.concatenate([apply_rope(x[..., :lanes], tables),
                                x[..., lanes:]], axis=-1)
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rotated * sin).astype(x.dtype)


_mha_announced = set()


def _announce_mha(heads, kv_heads, head_dim, window, rope, gate_lanes,
                  norm_lanes):
    """Gauges ``attn.*`` and one ``attn`` event a distinct shape, at trace
    time, for a mixer that is more than heads of ``dim / heads`` all the way
    (grouped key-value heads, a window, a gate, a part of the lanes
    rotated, a norm a head): full layers set ``attn.heads_full`` and
    ``attn.rotary_lanes_full``, window layers ``attn.heads_window`` and
    ``attn.window``.  ``gate_lanes`` are the gate's outputs a head (0: no
    gate, 1: a scalar a head, ``head_dim``: one a lane), ``norm_lanes`` the
    lanes one q / k norm's scale spans where it is a head's (0 elsewhere);
    the gauges ``attn.gate_lanes`` and ``attn.qk_norm_lanes`` are set where
    they are a head's width."""
    from autodist_tpu import observability
    if not observability.enabled():
        return
    registry = observability.registry()
    lanes = 0 if rope is None else rope[0].shape[-1]
    registry.gauge("attn.kv_heads").set(kv_heads)
    if window is None:
        registry.gauge("attn.heads_full").set(heads)
        registry.gauge("attn.rotary_lanes_full").set(lanes)
    else:
        registry.gauge("attn.heads_window").set(heads)
        registry.gauge("attn.window").set(window)
    if gate_lanes > 1:
        registry.gauge("attn.gate_lanes").set(gate_lanes)
    if norm_lanes:
        registry.gauge("attn.qk_norm_lanes").set(norm_lanes)
    detail = (f"attention: {heads} heads of {head_dim} read {kv_heads} "
              f"key-value heads ({heads // kv_heads} a group), "
              + ("every key behind the diagonal" if window is None
                 else f"a window of {window} keys")
              + f", {lanes} of a head's {head_dim} lanes rotated, "
              + {0: "no gate", 1: "a sigmoid gate a head on the output"}.get(
                  gate_lanes, f"a sigmoid gate a lane ({gate_lanes} a head) "
                              f"on the output")
              + (f", q and k RMS-normalised a head over {norm_lanes} lanes"
                 if norm_lanes else ""))
    if detail not in _mha_announced:
        _mha_announced.add(detail)
        observability.record_event("attn", detail)


def mha(p, x, num_heads, mask=None, dtype=None, attn_fn=None, rope=None,
        norm_eps=1e-5, kv_heads=None, window=None):
    """Multi-head self-attention.

    ``attn_fn(q, k, v, mask)`` may override the inner attention computation
    (the hook used to swap in the Pallas flash kernel or ring attention).
    q/k/v are (batch, heads, seq, head_dim).  A hook may carry
    ``attn_fn.bshd(num_heads, head_dim)``; where that gives a function, it
    is the same attention on (batch, seq, heads, head_dim), the projections'
    own (batch, seq, dim) seen as heads, and it is called instead: neither
    q, k, v nor the result is transposed (the flash kernels read and write
    that layout, ``ops/flash_attention.py``).  Where the parameters hold
    ``q_norm`` / ``k_norm`` (QK-norm), q and k are RMS-normalised over the
    whole projected vector before the split into heads, or, where the
    scales are a head wide (``mha_init(qk_norm="head")``), each head over
    its own lanes after it, one scale shared by the heads; ``rope``
    (:func:`rope_tables`, :func:`yarn_rope_tables`; tables narrower than a
    head rotate its first lanes) rotates q and k after it.

    The head width is the parameters' (``query`` is dim -> num_heads x
    head_dim).  ``kv_heads`` < ``num_heads``: k and v are (batch, kv_heads,
    seq, head_dim), query head h reads key-value head ``h // (num_heads /
    kv_heads)``, and they reach the core that wide, never repeated.
    ``window``: position t sees the keys s with ``t - window < s <= t``
    (under the hook's causality; an explicit ``mask`` has to hold it).  A
    hook serves either only if it says so (``attn_fn.grouped``,
    ``attn_fn.windowed``, ``make_flash_attn_fn``'s).  Where the parameters
    hold ``gate``, head h's output is multiplied by ``sigmoid(W_g x)_h``
    (float32) before ``out``: one scalar a head, or where ``gate`` is dim ->
    num_heads x head_dim (``mha_init(gate="lane")``) one a lane.  The named
    scopes ``qkv``, ``rope``, ``core``
    (``window_core`` under a window), ``gate`` and ``out`` are rows of the
    profiler's table under the block's ``attn``.
    """
    b, s, _ = x.shape
    kv_heads = kv_heads or num_heads
    head_dim = p["query"]["kernel"].shape[1] // num_heads
    plain = kv_heads == num_heads and window is None
    for what, need in (("grouped", kv_heads != num_heads),
                       ("windowed", window is not None)):
        if need and attn_fn is not None and not getattr(attn_fn, what, False):
            raise NotImplementedError(
                f"attention with {kv_heads} key-value heads for {num_heads} "
                f"query heads and window {window} needs an attention hook "
                f"that is .{what} (ops.flash_attention.make_flash_attn_fn), "
                f"and this one (ring, Ulysses or a caller's own) is not")
    gate_lanes = p["gate"]["kernel"].shape[1] // num_heads \
        if "gate" in p else 0
    # A scale a head wide is a head's norm (with one head the two forms are
    # the same numbers).
    head_norm = "q_norm" in p and num_heads > 1 \
        and p["q_norm"]["scale"].shape[0] == head_dim
    if not plain or gate_lanes or head_norm or (
            rope is not None and rope[0].shape[-1] != head_dim):
        _announce_mha(num_heads, kv_heads, head_dim, window, rope,
                      gate_lanes, head_dim if head_norm else 0)
    bshd = getattr(attn_fn, "bshd", None) if plain else None
    if bshd is not None:
        bshd = bshd(num_heads, head_dim)

    def project(name, norm=None, heads=num_heads):
        t = dense(p[name], x, dtype)
        if norm in p and not head_norm:
            t = rmsnorm(p[norm], t, norm_eps)
        t = t.reshape(b, s, heads, -1)
        if norm in p and head_norm:
            t = rmsnorm(p[norm], t, norm_eps)
        return t if bshd else t.transpose(0, 2, 1, 3)

    with jax.named_scope("qkv"):
        q = project("query", "q_norm")
        k, v = project("key", "k_norm", kv_heads), project("value",
                                                           heads=kv_heads)
    if rope is not None:
        with jax.named_scope("rope"):
            if bshd:    # (seq, lanes) against (batch, seq, heads, head_dim)
                rope = tuple(t[:, None] for t in rope)
            q, k = apply_rope(q, rope), apply_rope(k, rope)
    with jax.named_scope("core" if window is None else "window_core"):
        extra = {} if window is None else {"window": window}
        if bshd:
            o = bshd(q, k, v, mask)
        elif attn_fn is not None:
            o = attn_fn(q, k, v, mask, **extra)
        else:
            o = dot_product_attention(q, k, v, mask)
    def merged(o):      # the heads' outputs as ``out`` reads them
        return (o if bshd else o.transpose(0, 2, 1, 3)).reshape(b, s, -1)

    if "gate" in p:
        with jax.named_scope("gate"):
            g = jax.nn.sigmoid(dense(p["gate"], x, dtype).astype(jnp.float32))
            if gate_lanes > 1:
                # A gate a lane meets the outputs merged, (batch, s, heads x
                # head_dim): the gate itself is never transposed.
                o = merged(o)
            else:
                g = g[..., None] if bshd else g.transpose(0, 2, 1)[..., None]
            o = (o.astype(jnp.float32) * g).astype(o.dtype)
    with jax.named_scope("out"):
        return dense(p["out"], o if o.ndim == 3 else merged(o), dtype)


def dot_product_attention(q, k, v, mask=None):
    """Reference attention: softmax(qk^T/sqrt(d))v with f32 softmax.  k and
    v may hold fewer heads than q: query head h reads key-value head ``h //
    group`` (the group is the einsums' ``...``; nothing is repeated)."""
    hd = q.shape[-1]
    heads, kv_heads = q.shape[1], k.shape[1]
    if kv_heads != heads:
        q = q.reshape((q.shape[0], kv_heads, heads // kv_heads) + q.shape[2:])
    logits = jnp.einsum("bh...qd,bhkd->bh...qk", q, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    if mask is not None:
        if logits.ndim == 5:    # (batch, 1 or heads, sq, sk) beside the group
            mask = mask[:, :, None] if mask.shape[1] == 1 else mask.reshape(
                mask.shape[0], kv_heads, -1, *mask.shape[2:])
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bh...qk,bhkd->bh...qd", probs, v.astype(jnp.float32))
    return out.reshape(out.shape[0], heads, *out.shape[-2:]).astype(q.dtype)


def causal_mask(seq_len, window=None):
    """True where a key is seen, (1, 1, seq, seq): ``s <= t`` and, under a
    ``window``, ``t - window < s``."""
    mask = jnp.tril(jnp.ones((1, 1, seq_len, seq_len), bool))
    if window is not None:
        mask = jnp.logical_and(mask, jnp.triu(mask, 1 - window))
    return mask


def mha_decode(p, x, num_heads, k_cache, v_cache, pos, dtype=None):
    """Single-token self-attention against a preallocated KV cache.

    ``x`` is one token per slot — (slots, 1, dim); ``k_cache``/``v_cache``
    are (slots, heads, cache_len, head_dim); ``pos`` (slots,) is each
    slot's current position.  This token's k/v are written at ``pos`` and
    attention runs over the FULL cache with a ``j <= pos`` mask: masked
    columns get ``finfo.min`` logits, whose softmax probability underflows
    to exactly 0.0 in float32, so stale cache rows beyond ``pos`` (zeros,
    or a previous occupant's values) contribute exactly nothing — the
    decode output is bitwise-equal to a full-prefix forward recompute at
    the padded cache length (tier-1 pinned, tests/test_decode.py).

    Bitwise detail: the single query row is BROADCAST to ``cache_len``
    rows before :func:`dot_product_attention`, so XLA lowers the q·kᵀ
    contraction to the same batched-matmul kernel (same accumulation
    order) the full forward uses — a q-length-1 GEMV accumulates in a
    different order and drifts by ~1 ulp.  The redundant rows are sliced
    off; the projections/MLP (the dominant per-token cost) stay O(1).

    Returns ``(out, k_cache, v_cache)`` with the updated caches.
    """
    b, s, d = x.shape
    hd = d // num_heads
    cache_len = k_cache.shape[2]

    def split(t):
        return t.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)

    q = split(dense(p["query"], x, dtype))
    k = split(dense(p["key"], x, dtype))      # (slots, heads, 1, hd)
    v = split(dense(p["value"], x, dtype))
    # Scatter this token's k/v at each slot's position: an exact select,
    # not an arithmetic blend, so cached values are bitwise the forward's.
    at = (jnp.arange(cache_len)[None, None, :, None] ==
          pos[:, None, None, None])
    k_cache = jnp.where(at, k.astype(k_cache.dtype), k_cache)
    v_cache = jnp.where(at, v.astype(v_cache.dtype), v_cache)
    mask = (jnp.arange(cache_len)[None, None, None, :] <=
            pos[:, None, None, None])
    qb = jnp.broadcast_to(q, (b, num_heads, cache_len, hd))
    o = dot_product_attention(qb, k_cache, v_cache, mask)[:, :, :1, :]
    o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
    return dense(p["out"], o, dtype), k_cache, v_cache


# -- latent attention ----------------------------------------------------------

def mla_init(key, dim, heads, q_rank, kv_rank, nope_dim, rope_dim, value_dim,
             gate=False):
    """Parameters of one latent-attention mixer (:func:`mla`): the queries'
    low-rank path ``q_down`` (dim -> q_rank), ``q_norm``, ``q_up`` (q_rank
    -> heads x (nope_dim + rope_dim), a head's unrotated part first), or
    with ``q_rank=0`` no such path: one full-rank matrix ``q`` (dim -> heads
    x (nope_dim + rope_dim)) and neither ``q_down`` nor ``q_norm``; the
    keys' and values' ``kv_down`` (dim -> kv_rank + rope_dim: the latent,
    then the one rotary key of the position), ``kv_norm`` over the latent,
    ``kv_up`` (kv_rank -> heads x (nope_dim + value_dim), a head's keys
    first); ``out`` (heads x value_dim -> dim); with ``gate`` a ``gate``
    (dim -> heads: one scalar a head).  No bias anywhere."""
    ks = jax.random.split(key, 5)
    queries = {
        "q_down": dense_init(ks[0], dim, q_rank, use_bias=False),
        "q_norm": rmsnorm_init(q_rank),
        "q_up": dense_init(ks[1], q_rank, heads * (nope_dim + rope_dim),
                           use_bias=False),
    } if q_rank else {"q": dense_init(
        ks[0], dim, heads * (nope_dim + rope_dim), use_bias=False)}
    gates = {"gate": dense_init(jax.random.fold_in(key, 5), dim, heads,
                                use_bias=False)} if gate else {}
    return {
        **queries, **gates,
        "kv_down": dense_init(ks[2], dim, kv_rank + rope_dim, use_bias=False),
        "kv_norm": rmsnorm_init(kv_rank),
        "kv_up": dense_init(ks[3], kv_rank, heads * (nope_dim + value_dim),
                            use_bias=False),
        "out": dense_init(ks[4], heads * value_dim, dim, use_bias=False),
    }


def rope_pair_tables(seq_len, rope_dim, theta):
    """``(cos, sin)``, each (seq, rope_dim / 2) in float32: the angle of
    pair ``i`` at position ``t`` is ``t * theta^(-2i / rope_dim)``."""
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, rope_dim, 2, dtype=jnp.float32) / rope_dim)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope_pairs(x, tables):
    """Rotate the adjacent pairs ``(2i, 2i + 1)`` of ``x`` (..., seq,
    rope_dim) by their positions, as a checkpoint with ``rope_interleave``
    stores them; float32 arithmetic.  The result holds the pairs' first
    elements in its lower half and their second in its upper: one fixed
    permutation of the lanes, the same for queries and keys, which no score
    sees."""
    cos, sin = tables
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    first, second = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin],
                           axis=-1).astype(x.dtype)


_mla_announced = set()


def _announce_mla(heads, q_rank, kv_rank, nope_dim, rope_dim, value_dim, path):
    """Gauges ``mla.*`` and one ``mla`` event a distinct shape and path, at
    trace time."""
    from autodist_tpu import observability
    if not observability.enabled():
        return
    registry = observability.registry()
    registry.gauge("mla.heads").set(heads)
    registry.gauge("mla.q_rank").set(q_rank)
    registry.gauge("mla.kv_rank").set(kv_rank)
    registry.gauge("mla.nope_width").set(nope_dim)
    registry.gauge("mla.rope_width").set(rope_dim)
    registry.gauge("mla.value_width").set(value_dim)
    detail = (f"latent attention: {heads} heads, queries "
              + (f"through a latent of {q_rank}" if q_rank
                 else "by one full-rank matrix")
              + f", keys and values through one of {kv_rank}; scores "
              f"{nope_dim} + {rope_dim} wide (the rotary key one a position, "
              f"shared by the heads), values {value_dim}; core: {path}")
    if detail not in _mla_announced:
        _mla_announced.add(detail)
        observability.record_event("mla", detail)


def mla(p, x, heads, nope_dim, rope_dim, value_dim, rope, mask=None,
        dtype=None, attn_fn=None, norm_eps=1e-6, causal=True):
    """Latent attention (multi-head, DeepSeek-V2's) over ``x`` (batch, s,
    dim).

    ``c_q = rmsnorm(W_dq x)``; a head's query is ``W_uq c_q`` (where the
    parameters hold no low-rank path, ``mla_init(q_rank=0)``: ``W_q x``,
    one matrix and no norm) split into an
    unrotated part of ``nope_dim`` and a rotary part of ``rope_dim``;
    ``[c_kv ; k_r] = W_dkv x``, ``c_kv`` normalised, ``k_r`` the ONE rotary
    key of the position that every head reads; a head's unrotated key and
    its value (``value_dim``) are ``W_ukv c_kv``; ``rope``
    (:func:`rope_pair_tables`) rotates the queries' rotary parts and
    ``k_r``; the score is the sum of the two products over
    ``sqrt(nope_dim + rope_dim)``, softmax, values, ``W_o``.  Where the
    parameters hold ``gate`` (``mla_init(gate=True)``), head h's output is
    multiplied by ``sigmoid(W_g x)_h`` (float32) before ``W_o``, under the
    scope ``gate``.

    The core is ``attn_fn.two_product`` where the hook has one (the flash
    kernels' two-product form: ``k_r`` is never broadcast over the heads
    and nothing is padded to a common width); the same two products in
    plain jnp (``flash_attention.two_product_reference``) with no hook or
    with an explicit ``mask`` (``causal`` is the hook's own where it has
    one).  The five named scopes are rows of the profiler's table under
    the block's ``attn``."""
    b, s, _ = x.shape
    scale = (nope_dim + rope_dim) ** -0.5
    two_product = getattr(attn_fn, "two_product", None)
    if attn_fn is not None and two_product is None:
        raise NotImplementedError(
            "latent attention's keys and values differ in width and its "
            "rotary key is shared by the heads: it needs an attention hook "
            "with .two_product (ops.flash_attention.make_flash_attn_fn), and "
            "this one (ring, Ulysses or a caller's own) has none")
    with jax.named_scope("q_latent"):
        if "q" in p:
            q = dense(p["q"], x, dtype)
        else:
            c_q = rmsnorm(p["q_norm"], dense(p["q_down"], x, dtype), norm_eps)
            q = dense(p["q_up"], c_q, dtype)
        q = q.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)
        q_nope, q_rope = q[..., :nope_dim], q[..., nope_dim:]
    with jax.named_scope("kv_latent"):
        down = dense(p["kv_down"], x, dtype)
        kv_rank = down.shape[-1] - rope_dim
        c_kv = rmsnorm(p["kv_norm"], down[..., :kv_rank], norm_eps)
        k_rope = down[..., kv_rank:]
        kv = dense(p["kv_up"], c_kv, dtype).reshape(b, s, heads, -1) \
            .transpose(0, 2, 1, 3)
        k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
    with jax.named_scope("rope"):
        q_rope = apply_rope_pairs(q_rope, rope)
        k_rope = apply_rope_pairs(k_rope, rope)
    with jax.named_scope("core"):
        fused = two_product is not None and mask is None
        _announce_mla(heads,
                      0 if "q" in p else p["q_down"]["kernel"].shape[1],
                      kv_rank,
                      nope_dim, rope_dim, value_dim,
                      "the hook's two-product form" if fused
                      else "the two products in plain jnp")
        if fused:
            o = two_product(q_nope, q_rope, k_nope, k_rope, v, scale)
        else:
            from autodist_tpu.ops.flash_attention import two_product_reference
            o = two_product_reference(q_nope, q_rope, k_nope, k_rope, v,
                                      scale, causal and mask is None, mask)
    if "gate" in p:
        with jax.named_scope("gate"):
            g = jax.nn.sigmoid(dense(p["gate"], x, dtype).astype(jnp.float32))
            o = (o.astype(jnp.float32)
                 * g.transpose(0, 2, 1)[..., None]).astype(o.dtype)
    with jax.named_scope("out"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, heads * value_dim)
        return dense(p["out"], o, dtype)


# -- gated delta rule (linear attention) ---------------------------------------

def gdn_init(key, dim, heads, key_dim, value_dim, conv_width=4,
             key_heads=None):
    """Parameters of one gated-delta mixer (:func:`gdn`): six projections
    in (``q``, ``k`` of key_heads x key_dim, ``v`` and the output gate ``z``
    of heads x value_dim, the decay's ``a`` and the write strength's ``b`` of
    heads), one depthwise convolution kernel (conv_width, 2 key_heads key_dim
    + heads value_dim) over q, k and v together, ``A_log`` and ``dt_bias`` a
    head, one norm scale of value_dim shared by the heads, and ``out``.
    ``key_heads`` (None: ``heads``) divides ``heads``: value head ``h`` reads
    the queries and keys of key head ``h // (heads / key_heads)``.
    No bias anywhere.  ``A_log`` is the log of uniform(0, 16) and
    ``dt_bias`` the inverse softplus of a step drawn log-uniformly in
    [1e-3, 1e-1], as the Gated DeltaNet paper's code draws them."""
    key_heads = key_heads or heads
    if heads % key_heads:
        raise ValueError(f"{key_heads} key heads do not group the {heads} "
                         f"heads of a gated-delta mixer")
    ks = jax.random.split(key, 10)
    qk, vz = key_heads * key_dim, heads * value_dim
    step = jnp.exp(jax.random.uniform(ks[8], (heads,), minval=math.log(1e-3),
                                      maxval=math.log(1e-1)))
    return {
        "q": dense_init(ks[0], dim, qk, use_bias=False),
        "k": dense_init(ks[1], dim, qk, use_bias=False),
        "v": dense_init(ks[2], dim, vz, use_bias=False),
        "z": dense_init(ks[3], dim, vz, use_bias=False),
        "a": dense_init(ks[4], dim, heads, use_bias=False),
        "b": dense_init(ks[5], dim, heads, use_bias=False),
        # A tap is one of conv_width inputs of its channel, as a row of a
        # dense kernel is one of fan_in: uniform in +-1/sqrt(conv_width).
        "conv": {"kernel": jax.random.uniform(
            ks[6], (conv_width, 2 * qk + vz), minval=-conv_width ** -0.5,
            maxval=conv_width ** -0.5)},
        "A_log": jnp.log(jax.random.uniform(ks[7], (heads,), minval=1e-4,
                                            maxval=16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "norm": rmsnorm_init(value_dim),
        "out": dense_init(ks[9], vz, dim, use_bias=False),
    }


@jax.custom_vjp
def causal_depthwise_conv(kernel, x):
    """``y_t = sum_j kernel[j] * x_(t - (taps - 1) + j)`` per channel of
    ``x`` (batch, s, channels), positions before the row's start zero; the
    taps are accumulated in float32.  Its gradient is written out, from
    ``kernel`` and ``x`` alone: autodiff would keep every tap's float32
    slice of ``x``."""
    return _conv(kernel, x)


def _tap_slices(x, taps):
    """For each tap, the positions of ``x`` (zero-padded at its start) that
    the tap meets: ``taps`` arrays of ``x``'s shape."""
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return [padded[:, j:j + x.shape[1]] for j in range(taps)]


def _conv(kernel, x):
    y = sum(w.astype(jnp.float32) * t
            for w, t in zip(kernel, _tap_slices(x, kernel.shape[0])))
    return y.astype(x.dtype)


def _conv_fwd(kernel, x):
    return _conv(kernel, x), (kernel, x)


def _conv_bwd(res, dy):
    kernel, x = res
    dkernel = jnp.stack([jnp.sum(dy.astype(jnp.float32) * t, axis=(0, 1))
                         for t in _tap_slices(x, kernel.shape[0])])
    # dx_t = sum_j kernel[j] * dy_(t + (taps - 1) - j): the same taps met
    # from the other end.
    dx = _conv(kernel, dy[:, ::-1])[:, ::-1]
    return dkernel.astype(kernel.dtype), dx.astype(x.dtype)


causal_depthwise_conv.defvjp(_conv_fwd, _conv_bwd)


def _convolved(kernel, q, k, v):
    """A linear mixer's q~, k~, v~ (batch, s, channels), each channel
    convolved causally with its own taps of the one ``kernel`` (taps, q's +
    k's + v's channels), then ``silu``."""
    qk = q.shape[-1]
    return tuple(jax.nn.silu(causal_depthwise_conv(kernel[:, lo:hi], t))
                 for t, lo, hi in ((q, 0, qk), (k, qk, 2 * qk),
                                   (v, 2 * qk, kernel.shape[1])))


def l2_unit(t, eps=1e-6):
    """``t / sqrt(sum(t^2) + eps)`` over the last axis, in float32."""
    t = t.astype(jnp.float32)
    return t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + eps)


def gated_rmsnorm(p, x, gate, eps=1e-6):
    """``rmsnorm(x) * silu(gate)``: the mixer's output gate."""
    return rmsnorm(p, x, eps) * jax.nn.silu(gate)


def gdn(p, x, heads, dtype=None, allow_neg_eigval=True, norm_eps=1e-6,
        key_heads=None):
    """One gated-delta mixer over ``x`` (batch, s, dim): ``(y, final
    state)``, the state (batch, heads, key_dim, value_dim) in float32.

    ``q~, k~, v~, z, a, b`` are projections of ``x``; each channel of q~,
    k~, v~ is convolved causally over time with its own taps, then
    ``silu``; per key head (``key_heads``, None: ``heads``; value head ``h``
    reads key head ``h // (heads / key_heads)``, and the rule takes q and k
    that wide) q and k are L2-normalised (eps 1e-6 inside the root;
    q also scaled by key_dim^-1/2); ``beta = sigmoid(b)`` (twice that with
    ``allow_neg_eigval``, so that the transition's eigenvalues reach -1),
    ``g = -exp(A_log) softplus(a + dt_bias)`` in float32; the chunked rule
    (``ops/gated_delta.py``); ``y = W_o(rmsnorm(o) * silu(z))``, the norm a
    head at a time with one scale shared by the heads.  The five named
    scopes are the rows of the profiler's table (``gdn/<part>``)."""
    from autodist_tpu.ops import gated_delta
    b, s, _ = x.shape
    with jax.named_scope("proj"):
        q, k, v, z, a, beta = (dense(p[name], x, dtype)
                               for name in ("q", "k", "v", "z", "a", "b"))
    with jax.named_scope("conv"):
        qk = q.shape[-1]
        q, k, v = _convolved(p["conv"]["kernel"], q, k, v)
    with jax.named_scope("gates"):
        key_heads = key_heads or heads
        key_dim = qk // key_heads
        q = (l2_unit(q.reshape(b, s, key_heads, key_dim))
             * key_dim ** -0.5).astype(q.dtype)
        k = l2_unit(k.reshape(b, s, key_heads, key_dim)).astype(k.dtype)
        beta = jax.nn.sigmoid(beta.astype(jnp.float32))
        if allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            a.astype(jnp.float32) + p["dt_bias"])
    with jax.named_scope("scan"):
        o, state = gated_delta.gated_delta_rule(
            q, k, v.reshape(b, s, heads, -1), g, beta)
    with jax.named_scope("out"):
        o = gated_rmsnorm(p["norm"], o, z.reshape(o.shape), norm_eps)
        return dense(p["out"], o.reshape(b, s, -1), dtype), state


# -- delta rule with a decay a channel (Kimi Delta Attention) -----------------

def kda_init(key, dim, heads, key_dim, value_dim, conv_width=4,
             gate_lower_bound=-5.0):
    """Parameters of one KDA mixer (:func:`kda`; Kimi Linear,
    arXiv:2510.26692): ``q``, ``k`` (heads x key_dim), ``v`` (heads x
    value_dim), the decay's full-rank projection ``f`` (heads x key_dim: a
    gate a CHANNEL of the key), the write strength's ``b`` and the output
    gate's ``z`` (heads: one scalar a head each), one depthwise convolution
    kernel (conv_width, 2 heads key_dim + heads value_dim) over q, k and v
    together, ``A_log`` a head and ``dt_bias`` a channel, one norm scale over
    all heads x value_dim lanes, and ``out``.  No bias anywhere.  ``A_log``
    is the log of uniform(1, 16); ``dt_bias`` is drawn so that at a zero
    projection the bounded gate ``gate_lower_bound x sigmoid(exp(A_log)
    dt_bias)`` is ``-exp(A_log) dt`` with ``dt`` log-uniform in [1e-3,
    1e-1], the decays flash-linear-attention's draw gives the unbounded
    gate."""
    ks = jax.random.split(key, 11)
    qk, vz = heads * key_dim, heads * value_dim
    rate = jax.random.uniform(ks[7], (heads, 1), minval=1.0, maxval=16.0)
    step = jnp.exp(jax.random.uniform(
        ks[8], (heads, key_dim), minval=math.log(1e-3), maxval=math.log(1e-1)))
    share = rate * step / -gate_lower_bound
    return {
        "q": dense_init(ks[0], dim, qk, use_bias=False),
        "k": dense_init(ks[1], dim, qk, use_bias=False),
        "v": dense_init(ks[2], dim, vz, use_bias=False),
        "f": dense_init(ks[3], dim, qk, use_bias=False),
        "b": dense_init(ks[4], dim, heads, use_bias=False),
        "z": dense_init(ks[5], dim, heads, use_bias=False),
        "conv": {"kernel": jax.random.uniform(
            ks[6], (conv_width, 2 * qk + vz), minval=-conv_width ** -0.5,
            maxval=conv_width ** -0.5)},
        "A_log": jnp.log(rate[:, 0]),
        "dt_bias": (jnp.log(share / (1.0 - share)) / rate).reshape(-1),
        "norm": rmsnorm_init(vz),
        "out": dense_init(ks[9], vz, dim, use_bias=False),
    }


def kda(p, x, heads, dtype=None, norm_eps=1e-6, gate_lower_bound=-5.0):
    """One KDA mixer over ``x`` (batch, s, dim): ``(y, final state, the
    most negative log decay summed inside a sub-block)``, the state (batch,
    heads, key_dim, value_dim) in float32.

    ``q~, k~, v~, f, b, z`` are projections of ``x``; each channel of q~,
    k~, v~ is convolved causally over time with its own taps, then ``silu``;
    per head q and k are L2-normalised (eps 1e-6 inside the root; q also
    scaled by key_dim^-1/2); ``beta = sigmoid(b)``, one a head; the gate a
    CHANNEL of a head, in float32, ``g = gate_lower_bound x
    sigmoid(exp(A_log) (f + dt_bias))`` (the bounded, "safe" gate: ``exp g``
    in ``(exp gate_lower_bound, 1)``, which is what the chunked rule's
    sub-block form rests on, ``gated_delta.GATE_LOWER_BOUND``); the chunked
    rule with a decay a channel (``ops/gated_delta.py``); ``y =
    W_o(sigmoid(z)_h * rmsnorm(o))``, the norm over all heads x value_dim
    lanes together with a scale as wide, the gate one scalar a head.  The
    five named scopes are the rows of the profiler's table
    (``kda/<part>``)."""
    from autodist_tpu.ops import gated_delta
    if gate_lower_bound < gated_delta.GATE_LOWER_BOUND:
        raise ValueError(
            f"a gate down to {gate_lower_bound} a position overflows the "
            f"chunked rule's sub-blocks: it holds down to "
            f"{gated_delta.GATE_LOWER_BOUND}")
    b, s, _ = x.shape
    with jax.named_scope("proj"):
        q, k, v, f, beta, z = (dense(p[name], x, dtype)
                               for name in ("q", "k", "v", "f", "b", "z"))
    with jax.named_scope("conv"):
        qk = q.shape[-1]
        q, k, v = _convolved(p["conv"]["kernel"], q, k, v)
    with jax.named_scope("gates"):
        key_dim = qk // heads
        q = (l2_unit(q.reshape(b, s, heads, key_dim))
             * key_dim ** -0.5).astype(q.dtype)
        k = l2_unit(k.reshape(b, s, heads, key_dim)).astype(k.dtype)
        beta = jax.nn.sigmoid(beta.astype(jnp.float32))
        g = gate_lower_bound * jax.nn.sigmoid(
            jnp.exp(p["A_log"])[:, None]
            * (f.astype(jnp.float32) + p["dt_bias"])
            .reshape(b, s, heads, key_dim))
        gate_min = gated_delta.sub_block_gate_min(lax.stop_gradient(g))
    with jax.named_scope("scan"):
        o, state = gated_delta.gated_delta_rule(
            q, k, v.reshape(b, s, heads, -1), g, beta)
    with jax.named_scope("out"):
        o = rmsnorm(p["norm"], o.reshape(b, s, -1), norm_eps)
        gate = jax.nn.sigmoid(z.astype(jnp.float32))
        o = (o.reshape(b, s, heads, -1).astype(jnp.float32)
             * gate[..., None]).astype(o.dtype)
        return dense(p["out"], o.reshape(b, s, -1), dtype), state, gate_min


# -- recurrent ---------------------------------------------------------------

def lstm_init(key, in_dim, hidden):
    k1, k2 = jax.random.split(key)
    return {
        "wi": glorot(k1, (in_dim, 4 * hidden)),
        "wh": glorot(k2, (hidden, 4 * hidden)),
        "bias": jnp.zeros((4 * hidden,)),
    }


def lstm(p, xs, hidden, reverse=False, dtype=None):
    """LSTM over time via lax.scan. xs: (batch, time, in_dim) -> (batch, time, hidden)."""
    b = xs.shape[0]
    wi, wh, bias = p["wi"], p["wh"], p["bias"]
    if dtype is not None:
        wi, wh = wi.astype(dtype), wh.astype(dtype)

    def cell(carry, x):
        h, c = carry
        z = x.astype(wi.dtype) @ wi + h.astype(wh.dtype) @ wh + bias.astype(wi.dtype)
        i, f, g, o = jnp.split(z.astype(jnp.float32), 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    init = (jnp.zeros((b, hidden)), jnp.zeros((b, hidden)))
    ts = xs.transpose(1, 0, 2)  # time-major for scan
    _, hs = lax.scan(cell, init, ts, reverse=reverse)
    return hs.transpose(1, 0, 2)


# -- losses ------------------------------------------------------------------

def softmax_xent(logits, labels):
    """Mean cross-entropy over int labels; f32 softmax."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def sigmoid_bce(logits, targets):
    logits = logits.astype(jnp.float32)
    return jnp.mean(jnp.clip(logits, 0) - logits * targets +
                    jnp.log1p(jnp.exp(-jnp.abs(logits))))
