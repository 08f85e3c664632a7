"""The plain reference of the Olmo-Hybrid block: ``jax.numpy``, float32,
the gated delta rule one position at a time, dense causal attention.

It imports nothing from ``autodist_tpu``, uses no kernel and no chunked
form.  It reads the parameter tree by the names the program gives it
(``embed/embedding``, ``layer<i>/{ln1,ln2}/scale``,
``layer<i>/gdn/{q,k,v,z,a,b,out}/kernel``, ``layer<i>/gdn/conv/kernel``,
``layer<i>/gdn/{A_log,dt_bias}``, ``layer<i>/gdn/norm/scale``,
``layer<i>/attn/{query,key,value,out}/kernel``,
``layer<i>/attn/{q_norm,k_norm}/scale``,
``layer<i>/mlp/{gate,up,down}/kernel``, ``ln_f/scale``,
``lm_head/kernel``) because the two sides of the check start from the same
values.  The model, as ISSUE 29 writes it down from the keys of
``allenai/Olmo-Hybrid-7B``'s ``config.json`` and the Gated DeltaNet paper
(arXiv:2412.06464); no bias anywhere:

* both kinds of layer: ``h = x + RMSNorm(mixer(x))``, ``out = h +
  RMSNorm(W_down(SiLU(W_gate h) * W_up h))``: the norms sit on the
  sublayers' outputs (the Olmo 2 order); a final RMSNorm and the head's own
  matrix; loss = next-token cross-entropy.
* ``linear_attention``: ``q~ = W_q x``, ``k~ = W_k x``, ``v~ = W_v x``,
  ``z = W_z x``, ``a = W_a x``, ``b = W_b x``; each channel of q~, k~, v~
  convolved causally over time with its own taps (zeros before the row's
  start), then SiLU; per head ``q = q' / sqrt(|q'|^2 + 1e-6) / sqrt(d_k)``,
  ``k = k' / sqrt(|k'|^2 + 1e-6)``; ``beta = 2 sigmoid(b)`` (1 x without
  ``neg_eigval``), ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``;
  ``S_t = alpha_t S_(t-1) + beta_t (v_t - alpha_t S_(t-1) k_t) k_t^T`` from
  ``S_0 = 0``, ``o_t = S_t q_t``; ``y = W_o concat_h(RMSNorm(o_h) *
  SiLU(z_h))``, the norm's scale shared by the heads.
* ``full_attention``: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over
  the whole projected vector, before the split into heads; no positions of
  any kind; causal ``softmax(q k^T / sqrt(head width)) v``; ``W_o``.

Departures from the published description, none of which changes a number:
the recurrence's gradient is taken through ``jax.checkpoint`` a segment of
positions at a time (4,096 saved states of 30 x 192 x 96 would be 9 GB a
layer), and attention takes a block of queries at a time (30 heads of 4,096
x 4,096 float32 scores are 2 GB, and again in the backward pass).

Compile ``loss`` under ``jax.value_and_grad``, as ``reference.make_step``
does.  Its forward-only jit is not sound on the v5e (PERF.md, section 6,
PR 29): there a linear layer's mixer came out 3.55e-2 off once the layer's
tail was compiled with it, and the loss up to 2.0e-4 off, where the same
function under ``value_and_grad`` agrees with the program in float32 to
1e-7 and the CPU agrees with both.
"""
import math

import jax
import jax.numpy as jnp

#: Positions whose states the recurrence's backward pass holds at once.
SEGMENT = 64
#: Queries whose scores against every key exist at once.
QUERY_BLOCK = 512


def rmsnorm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def short_convolution(kernel, x):
    """Each channel of ``x`` (rows, seq, channels) against its own
    ``taps`` weights, as shifted multiplies: tap j meets the position
    ``taps - 1 - j`` back; what lies before the row's start is zero."""
    taps, seq = kernel.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :seq - back]], axis=1)
        y = y + kernel[j] * shifted
    return y


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, one position at a time.  ``q``, ``k`` (rows, seq,
    heads, d_k), ``v`` (rows, seq, heads, d_v), ``alpha``, ``beta`` (rows,
    seq, heads); gives ``o`` (rows, seq, heads, d_v)."""
    rows, seq, heads, d_k = q.shape
    d_v = v.shape[-1]

    def position(state, x):
        q, k, v, alpha, beta = x                    # (rows, heads, ...)
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


def linear_attention(p, x, *, heads, eps, neg_eigval):
    rows, seq, _ = x.shape
    kernel = p["conv"]["kernel"]
    width = p["q"]["kernel"].shape[1]
    q, k, v = (silu(short_convolution(kernel[:, lo:hi], x @ p[name]["kernel"]))
               .reshape(rows, seq, heads, -1)
               for name, lo, hi in (("q", 0, width), ("k", width, 2 * width),
                                    ("v", 2 * width, kernel.shape[1])))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / math.sqrt(q.shape[-1])
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(x @ p["b"]["kernel"]) * (2.0 if neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        x @ p["a"]["kernel"] + p["dt_bias"]))
    o = delta_rule(q, k, v, alpha, beta)
    z = (x @ p["z"]["kernel"]).reshape(o.shape)
    o = rmsnorm(p["norm"]["scale"], o, eps) * silu(z)
    return o.reshape(rows, seq, -1) @ p["out"]["kernel"]


def full_attention(p, x, *, heads, eps):
    rows, seq, _ = x.shape

    def split(t):
        return t.reshape(rows, seq, heads, -1).transpose(0, 2, 1, 3)

    q = split(rmsnorm(p["q_norm"]["scale"], x @ p["query"]["kernel"], eps))
    k = split(rmsnorm(p["k_norm"]["scale"], x @ p["key"]["kernel"], eps))
    v = split(x @ p["value"]["kernel"])
    block = math.gcd(seq, QUERY_BLOCK)

    def some_queries(args):
        q, first = args                             # (rows, heads, block, d)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        keep = (first + jnp.arange(block))[:, None] >= jnp.arange(seq)[None]
        scores = jnp.where(keep, scores, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
                          v)

    blocks = jnp.moveaxis(q.reshape(rows, heads, seq // block, block, -1),
                          2, 0)
    out = jax.lax.map(jax.checkpoint(some_queries),
                      (blocks, jnp.arange(0, seq, block)))
    out = jnp.moveaxis(out, 0, 2).reshape(rows, heads, seq, -1)
    return out.transpose(0, 2, 1, 3).reshape(rows, seq, -1) @ p["out"]["kernel"]


def mlp(p, x):
    return (silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def logits(params, ids, *, layer_types, heads, linear_heads, eps, neg_eigval):
    """Token ids (rows, seq) -> logits (rows, seq, vocabulary)."""
    x = params["embed"]["embedding"][ids]
    for i, layer_type in enumerate(layer_types):
        p = params[f"layer{i}"]
        if layer_type == "linear_attention":
            mixed = linear_attention(p["gdn"], x, heads=linear_heads, eps=eps,
                                     neg_eigval=neg_eigval)
        else:
            mixed = full_attention(p["attn"], x, heads=heads, eps=eps)
        x = x + rmsnorm(p["ln1"]["scale"], mixed, eps)
        x = x + rmsnorm(p["ln2"]["scale"], mlp(p["mlp"], x), eps)
    return rmsnorm(params["ln_f"]["scale"], x, eps) @ params["lm_head"]["kernel"]


def loss(params, tokens, **model):
    """Next-token cross-entropy of ``tokens`` (rows, seq + 1): inputs are
    all but the last of a row, targets all but the first."""
    lg = logits(params, tokens[:, :-1], **model)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
