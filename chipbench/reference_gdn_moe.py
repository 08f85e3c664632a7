"""The plain reference of the Qwen3-Next block: ``jax.numpy``, float32, the
gated delta rule one position at a time with value head ``h`` reading key
head ``h // (H_v / H_k)``, dense causal attention with keys and values
indexed ``h // group``, a norm a head, a gate a lane, every held expert
applied by mask, the shared expert behind its gate.

It imports nothing from ``autodist_tpu``, uses no kernel, no chunked form and
sorts nothing.  It reads the parameter tree by the names the program gives it
(``embed/embedding``, ``layer<i>/{ln1,ln2}/scale``,
``layer<i>/gdn/{q,k,v,z,a,b,out}/kernel``, ``layer<i>/gdn/conv/kernel``,
``layer<i>/gdn/{A_log,dt_bias}``, ``layer<i>/gdn/norm/scale``,
``layer<i>/attn/{query,key,value,gate,out}/kernel``,
``layer<i>/attn/{q_norm,k_norm}/scale``,
``layer<i>/moe/{gate,glu,up,down}/kernel``,
``layer<i>/moe/shared/{glu,up,down}/kernel``,
``layer<i>/moe/shared_gate/kernel``, ``ln_f/scale``, ``lm_head/kernel``)
because the two sides of the check start from the same values.  ``x`` is a
position's input; ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * s``; no bias;
SwiGLU is ``W_down(silu(W_glu u) * W_up u)``:

* block ``i``: ``h = x + Mixer_i(RMSNorm(x))``; ``out = h + MoE(RMSNorm(h))``;
  the mixer is the gated-delta one where ``layer_types[i]`` is
  ``"linear_attention"`` and full attention where ``"full_attention"``.
* gated-delta mixer of input ``u``: ``q~ = W_q u``, ``k~ = W_k u`` (``H_k``
  heads of ``d_k``), ``v~ = W_v u``, ``z = W_z u`` (``H_v`` heads of ``d_v``),
  ``a = W_a u``, ``b = W_b u`` (``H_v``); each channel of q~, k~, v~
  convolved causally with its own taps (zeros before the row's start), then
  SiLU; per key head ``q = q' / sqrt(|q'|^2 + 1e-6) / sqrt(d_k)``, ``k = k' /
  sqrt(|k'|^2 + 1e-6)``; per value head ``beta = sigmoid(b)``, ``alpha =
  exp(-exp(A_log) softplus(a + dt_bias))``; value head ``h`` takes q and k of
  key head ``h // (H_v / H_k)``: ``S_t = alpha_t S_(t-1) + beta_t (v_t -
  alpha_t S_(t-1) k_t) k_t^T`` from ``S_0 = 0``, ``o_t = S_t q_t``; ``y = W_o
  concat_h(RMSNorm(o_h) * SiLU(z_h))``, one ``d_v``-wide scale for all heads.
* full attention of input ``u``: ``q = W_q u`` (``H`` heads of ``head_dim``),
  ``gate = W_g u`` (as wide), ``k = W_k u``, ``v = W_v u`` (``kv_heads``
  heads); ``q_h <- RMSNorm(q_h)``, ``k_j <- RMSNorm(k_j)`` over a head's own
  lanes (one scale for q's heads, one for k's); rotate-half rotary on the
  first ``rotary_lanes`` lanes of q and k (``inv_freq_i = theta^(-2i /
  rotary_lanes)``), the rest pass; query head ``h`` reads key-value head ``h
  // (H / kv_heads)``; causal ``softmax(q k^T / sqrt(head_dim)) v``; ``y = W_o
  (o * sigmoid(gate))``, lane by lane.
* expert layer of input ``u``: ``p = softmax(W_r u)`` over ALL the experts;
  the chosen are the ``top_k`` of ``p``; ``w_e = p_e / sum_chosen p_e'``;
  ``y = sigmoid(w_sg . u) SwiGLU_shared(u) + sum over e chosen AND held of w_e
  SwiGLU_e(u)``: the layer holds the experts ``held = (first, count)`` and
  leaves out what the others would add.
* balance: a layer's term is the mean over rows of ``E * sum_e f_e P_e``,
  ``f_e`` the share of the row's ``seq * top_k`` assignments that chose
  ``e``, ``P_e`` the row's mean of ``p_e``.
* loss = ``xent + balance_coef * mean over the layers of the balance term``.

Departures from the published description, none of which changes a number:
norm scales are stored as ``s`` and start at one where the source stores ``s
= 1 + w`` with ``w`` at zero; the source's fused ``in_proj_qkvz`` /
``in_proj_ba`` / ``q_proj`` (query and gate) are separate matrices; the
recurrence's gradient is taken through ``jax.checkpoint`` a segment of
positions at a time, attention takes a block of queries at a time, and each
held expert's hidden activations are made again in the backward pass.

Compile ``loss`` under ``jax.value_and_grad``, as ``reference.make_step``
does (``reference_olmo_hybrid.py`` says why).
"""
import math

import jax
import jax.numpy as jnp

#: Positions whose states the recurrence's backward pass holds at once.
SEGMENT = 64
#: Queries whose scores against every key exist at once.
QUERY_BLOCK = 512
LINEAR = "linear_attention"


def rmsnorm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def swiglu(p, x):
    return (silu(x @ p["glu"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


# -- the gated-delta mixer ------------------------------------------------------

def short_convolution(kernel, x):
    """Each channel of ``x`` (rows, seq, channels) against its own ``taps``
    weights, as shifted multiplies: tap j meets the position ``taps - 1 - j``
    back; what lies before the row's start is zero."""
    taps, seq = kernel.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :seq - back]], axis=1)
        y = y + kernel[j] * shifted
    return y


def key_head_of(heads, key_heads):
    """The key head each of ``heads`` value heads reads: ``h // group``."""
    return jnp.arange(heads) // (heads // key_heads)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, one position at a time.  ``q``, ``k`` (rows, seq,
    key heads, d_k), ``v`` (rows, seq, heads, d_v), ``alpha``, ``beta`` (rows,
    seq, heads); gives ``o`` (rows, seq, heads, d_v)."""
    rows, seq, _, d_k = q.shape
    heads, d_v = v.shape[2:]
    of_head = key_head_of(heads, q.shape[2])

    def position(state, x):
        q, k, v, alpha, beta = x                    # (rows, heads, ...)
        q, k = q[:, of_head], k[:, of_head]
        state = alpha[..., None, None] * state      # (rows, heads, d_v, d_k)
        written = beta[..., None] * (v - jnp.einsum("rhvk,rhk->rhv",
                                                    state, k))
        state = state + written[..., :, None] * k[..., None, :]
        return state, jnp.einsum("rhvk,rhk->rhv", state, q)

    def segment(state, xs):
        return jax.lax.scan(position, state, xs)

    pad = -seq % SEGMENT    # padded positions write nothing: beta is 0
    xs = tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
               for t in (q, k, v, alpha, beta))
    xs = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (-1, SEGMENT) + t.shape[:1] + t.shape[2:]) for t in xs)
    _, o = jax.lax.scan(jax.checkpoint(segment),
                        jnp.zeros((rows, heads, d_v, d_k)), xs)
    o = o.reshape((seq + pad, rows, heads, d_v))[:seq]
    return jnp.moveaxis(o, 0, 1)


def linear_attention(p, x, *, heads, key_heads, eps):
    rows, seq, _ = x.shape
    kernel = p["conv"]["kernel"]
    width = p["q"]["kernel"].shape[1]
    q, k, v = (silu(short_convolution(kernel[:, lo:hi], x @ p[name]["kernel"]))
               .reshape(rows, seq, n, -1)
               for name, lo, hi, n in (
                   ("q", 0, width, key_heads),
                   ("k", width, 2 * width, key_heads),
                   ("v", 2 * width, kernel.shape[1], heads)))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / math.sqrt(q.shape[-1])
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(x @ p["b"]["kernel"])
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        x @ p["a"]["kernel"] + p["dt_bias"]))
    o = delta_rule(q, k, v, alpha, beta)
    z = (x @ p["z"]["kernel"]).reshape(o.shape)
    o = rmsnorm(p["norm"]["scale"], o, eps) * silu(z)
    return o.reshape(rows, seq, -1) @ p["out"]["kernel"]


# -- full attention -------------------------------------------------------------

def rotary_tables(seq, lanes, theta):
    """``(cos, sin)``, each (seq, lanes / 2): pair ``i`` turns by ``t *
    theta^(-2i / lanes)``."""
    inv_freq = theta ** (-2.0 * jnp.arange(lanes // 2, dtype=jnp.float32)
                         / lanes)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, tables):
    """Rotary positions on the first ``2 x tables' width`` lanes of ``x``
    (..., seq, head_dim): lane ``i`` pairs with lane ``i + lanes / 2``; the
    lanes behind them pass."""
    cos, sin = tables
    half = cos.shape[-1]
    first, second, rest = x[..., :half], x[..., half:2 * half], \
        x[..., 2 * half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin, rest], axis=-1)


def attention_core(q, k, v):
    """Causal ``softmax(q k^T / sqrt(head_dim)) v`` for q (rows, heads, seq,
    head_dim) and k, v (rows, kv_heads, seq, head_dim): query head ``h``
    reads key-value head ``h // (heads / kv_heads)``."""
    rows, heads, seq, head_dim = q.shape
    of_head = jnp.arange(heads) // (heads // k.shape[1])
    keys, values = k[:, of_head], v[:, of_head]
    block = math.gcd(seq, QUERY_BLOCK)

    def some_queries(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qs, keys) \
            / math.sqrt(head_dim)
        seen = jnp.arange(seq)[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
                          values)

    out = jax.lax.map(jax.checkpoint(some_queries),
                      jnp.arange(0, seq, block))     # (blocks, b, h, block, d)
    return jnp.moveaxis(out, 0, 2).reshape(rows, heads, seq, head_dim)


def full_attention(p, x, *, head_dim, tables, eps):
    rows, seq, _ = x.shape

    def split(t):
        return t.reshape(rows, seq, -1, head_dim).transpose(0, 2, 1, 3)

    q, k, v = (split(x @ p[name]["kernel"])
               for name in ("query", "key", "value"))
    q = rotate(rmsnorm(p["q_norm"]["scale"], q, eps), tables)
    k = rotate(rmsnorm(p["k_norm"]["scale"], k, eps), tables)
    out = attention_core(q, k, v).transpose(0, 2, 1, 3).reshape(rows, seq, -1)
    return (out * jax.nn.sigmoid(x @ p["gate"]["kernel"])) @ p["out"]["kernel"]


# -- the expert layer -----------------------------------------------------------

def route(p, x, *, top_k):
    """``(weights, chosen, probs)``: ``chosen`` (rows, seq, top_k) are the
    experts of each position, the ``top_k`` of the softmax; ``weights``
    (rows, seq, E) the probability over the chosen ones' sum where the expert
    was chosen and 0 elsewhere."""
    probs = jax.nn.softmax(x @ p["gate"]["kernel"], axis=-1)
    _, chosen = jax.lax.top_k(probs, top_k)
    mask = (chosen[..., None] == jnp.arange(probs.shape[-1])).any(axis=-2)
    picked = jnp.where(mask, probs, 0.0)
    return picked / picked.sum(axis=-1, keepdims=True), chosen, probs


def held_experts(p, x, weights):
    """``sum over the held experts e of weights_e SwiGLU_e(x)``: one held
    expert at a time over every position, weighted by 0 where the expert was
    not chosen; ``weights`` (rows, seq, held) are the held experts' columns."""
    def one_expert(y, expert):
        glu, up, down, weight = expert
        hidden = silu(x @ glu) * (x @ up)
        return y + weight[..., None] * (hidden @ down), None

    stacked = (p["glu"]["kernel"], p["up"]["kernel"], p["down"]["kernel"],
               jnp.moveaxis(weights, -1, 0))
    return jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                        stacked)[0]


def shared_expert(p, x):
    """The shared expert's SwiGLU times the sigmoid of its gate's scalar."""
    return jax.nn.sigmoid(x @ p["shared_gate"]["kernel"]) \
        * swiglu(p["shared"], x)


def experts_layer(p, x, *, top_k, held):
    """``(y, balance term, rms of the held experts' part of y)`` of one
    expert layer that holds the experts ``held = (first, count)``; the
    matrices are stacked over the held experts."""
    weights, chosen, probs = route(p, x, top_k=top_k)
    rows, seq, experts = probs.shape
    first, count = held
    routed = held_experts(p, x, weights[..., first:first + count])
    assigned = (chosen[..., None] == jnp.arange(experts)).sum(axis=(1, 2))
    share = assigned / (seq * top_k)                        # (rows, E)
    balance = jnp.mean(experts * jnp.sum(share * probs.mean(axis=1), axis=-1))
    return shared_expert(p, x) + routed, balance, rms(routed)


# -- the model --------------------------------------------------------------------

def rms(x):
    return jnp.sqrt(jnp.mean(jnp.square(x)))


def block(p, x, *, kind, tables, heads, key_heads, head_dim, eps, top_k,
          held):
    """``(out, balance term, the mixer's output's root mean square about
    its mean over a row's positions, rms of the held experts' part)`` of one
    block of layer type ``kind``."""
    def mixer_half(p, x):
        u = rmsnorm(p["ln1"]["scale"], x, eps)
        if kind == LINEAR:
            y = linear_attention(p["gdn"], u, heads=heads,
                                 key_heads=key_heads, eps=eps)
        else:
            y = full_attention(p["attn"], u, head_dim=head_dim,
                               tables=tables, eps=eps)
        return x + y, rms(y - y.mean(axis=1, keepdims=True))

    x, mixed_std = jax.checkpoint(mixer_half)(p, x)
    y, balance, routed_rms = experts_layer(
        p["moe"], rmsnorm(p["ln2"]["scale"], x, eps), top_k=top_k, held=held)
    return x + y, balance, mixed_std, routed_rms


def head_xent(params, hidden, labels):
    logits = hidden @ params["lm_head"]["kernel"]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def forward(params, tokens, *, layer_types, rotary_lanes, theta, eps,
            **layer):
    """``(xent, mean balance term, probes)`` of ``tokens`` (rows, seq + 1):
    inputs are all but the last of a row.  ``probes`` are means over layers:
    ``held_output_rms`` of the root mean square of what the held experts add
    (every layer), ``attn_output_std`` and ``gdn_output_std`` of the root
    mean square of what the mixer adds about its mean over a row's positions
    (the layers of each kind)."""
    ids, nxt = tokens[:, :-1], tokens[:, 1:]
    tables = rotary_tables(ids.shape[1], rotary_lanes, theta)
    x = params["embed"]["embedding"][ids]
    terms, probes = [], {"held_output_rms": [], "attn_output_std": [],
                         "gdn_output_std": []}
    for i, kind in enumerate(layer_types):
        x, balance, mixed_std, routed_rms = block(
            params[f"layer{i}"], x, kind=kind, tables=tables, eps=eps,
            **layer)
        terms.append(balance)
        probes["held_output_rms"].append(routed_rms)
        probes["gdn_output_std" if kind == LINEAR
               else "attn_output_std"].append(mixed_std)
    xent = head_xent(params, rmsnorm(params["ln_f"]["scale"], x, eps), nxt)
    return xent, sum(terms) / len(terms), {
        name: sum(values) / len(values)
        for name, values in probes.items() if values}


def loss_and_probes(params, tokens, *, balance_coef, **model):
    """The loss, and beside it :func:`forward`'s probes (the program's
    ``aux["moe.held_output_rms"]``, ``aux["attn.output_std"]``,
    ``aux["gdn.output_std"]``)."""
    xent, balance, probes = forward(params, tokens, **model)
    return xent + balance_coef * balance, probes


def loss(params, tokens, **model):
    return loss_and_probes(params, tokens, **model)[0]
