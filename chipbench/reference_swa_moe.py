"""The plain reference of the Laguna-S-2.1 block: ``jax.numpy``, float32,
dense attention under an explicit window-and-causal mask with keys and values
indexed by ``h // group``, YaRN tables from their equations, every held
expert applied by mask.

It imports nothing from ``autodist_tpu``, uses no kernel and sorts nothing.
It reads the parameter tree by the names the program gives it
(``embed/embedding``, ``layer<i>/{ln1,ln2}/scale``,
``layer<i>/attn/{query,key,value,gate,out}/kernel``,
``layer<i>/mlp/{gate,up,down}/kernel`` (the dense layers),
``layer<i>/moe/{gate,glu,up,down}/kernel``,
``layer<i>/moe/shared/{glu,up,down}/kernel``, ``ln_f/scale``,
``lm_head/kernel``) because the two sides of the check start from the same
values.  ``x`` is a position's input; RMSNorm everywhere, no bias, SwiGLU is
``W_down(silu(W_gate u) * W_up u)``:

* block ``i``: ``h = x + Attention_i(RMSNorm(x))``; ``out = h +
  FFN_i(RMSNorm(h))``; the FFN of the layers in ``dense_layers`` is a dense
  SwiGLU, every other one the expert layer.
* attention of layer ``i``: ``H_i = heads[i]`` query heads and ``kv_heads``
  key-value heads of ``head_dim``; ``q = W_q u``, ``k = W_k u``, ``v = W_v
  u``; rotary (rotate-half) on q and k by the tables of the layer's kind;
  query head ``h`` reads key-value head ``h // (H_i / kv_heads)``; scores
  over ``sqrt(head_dim)``; position ``t`` sees the keys ``s <= t`` and, in a
  ``"sliding_attention"`` layer, only those with ``t - window < s``; ``g =
  sigmoid(W_g u)``, one scalar a head; ``y = W_o concat_h(g_h o_h)``.
* rotary tables of a kind (``rope[kind]``): ``lanes`` lanes of a head are
  rotated, pair ``i`` (lanes ``i`` and ``i + lanes / 2``) by ``t *
  inv_freq_i``; the other lanes pass.  Plain: ``inv_freq_i = theta^(-2i /
  lanes)``.  YaRN: ``inv_freq_i = (1 - r_i) / (factor theta^(2i / lanes)) +
  r_i / theta^(2i / lanes)``, ``r_i = 1 - clip((i - low) / (high - low), 0,
  1)``, ``low`` / ``high`` the floor / ceiling of ``lanes ln(original /
  (2 pi beta)) / (2 ln theta)`` at ``beta_fast`` / ``beta_slow`` clamped to
  ``[0, lanes - 1]``; cos and sin times ``attention_factor``.
* expert layer of input ``u``: ``p = softmax(W_r u)`` over ALL the experts;
  the chosen are the ``top_k`` of ``p``; ``w_e = route_scale * p_e /
  sum_chosen p_e'``; ``y = SwiGLU_shared(u) + sum over e chosen AND held of
  w_e SwiGLU_e(u)``: the layer holds the experts ``held = (first, count)``
  and leaves out what the others would add.
* balance: a layer's term is the mean over rows of ``E * sum_e f_e P_e``,
  ``f_e`` the share of the row's ``seq * top_k`` assignments that chose
  ``e``, ``P_e`` the row's mean of ``p_e``.
* loss = ``xent + balance_coef * mean over the expert layers of the balance
  term``.
"""
import math

import jax
import jax.numpy as jnp

#: Queries of the dense attention are taken this many at a time, so that the
#: f32 scores of a long row are (heads, QUERY_BLOCK, seq) and not (heads,
#: seq, seq).
QUERY_BLOCK = 512
SLIDING = "sliding_attention"


def rmsnorm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate" if "gate" in p else "glu"]["kernel"])
            * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


def inverse_frequencies(lanes, theta, yarn=None):
    """``inv_freq`` (lanes / 2,) of one kind's tables (the module docstring);
    ``yarn`` holds factor, original_len, beta_fast, beta_slow (and
    attention_factor, which :func:`rotary_tables` applies)."""
    pair = jnp.arange(lanes // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * pair / lanes)
    if yarn is None:
        return plain

    def correction(beta):
        return lanes * math.log(yarn["original_len"] / (2 * math.pi * beta)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), lanes - 1)
    r = 1.0 - jnp.clip((pair - low) / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 - r) * plain / yarn["factor"] + r * plain


def rotary_tables(seq, lanes, theta, yarn=None):
    """``(cos, sin)``, each (seq, lanes / 2)."""
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * inverse_frequencies(lanes, theta, yarn)[None, :]
    scale = 1.0 if yarn is None else yarn["attention_factor"]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


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


def visible(start, block, seq, window):
    """(block, seq) True where the query at ``start + row`` sees the key."""
    t = (start + jnp.arange(block))[:, None]
    s = jnp.arange(seq)[None, :]
    seen = s <= t
    return seen if window is None else seen & (t - window < s)


def attention_core(q, k, v, window):
    """``softmax(q k^T / sqrt(head_dim) under the mask) v`` for q (rows,
    heads, seq, head_dim) and k, v (rows, kv_heads, seq, head_dim): query
    head ``h`` reads key-value head ``h // (heads / kv_heads)``."""
    rows, heads, seq, head_dim = q.shape
    of_head = jnp.arange(heads) // (heads // k.shape[1])
    keys, values = k[:, of_head], v[:, of_head]
    block = min(QUERY_BLOCK, seq)

    def some_queries(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qs, keys) \
            / jnp.sqrt(float(head_dim))
        scores = jnp.where(visible(start, block, seq, window), scores,
                           -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
                          values)

    out = jax.lax.map(jax.checkpoint(some_queries),
                      jnp.arange(0, seq, block))     # (blocks, b, h, block, d)
    return jnp.moveaxis(out, 0, 2).reshape(rows, heads, seq, head_dim)


def attention(p, x, *, kv_heads, head_dim, window, tables):
    rows, seq, _ = x.shape

    def split(t):
        return t.reshape(rows, seq, -1, head_dim).transpose(0, 2, 1, 3)

    q, k, v = (split(x @ p[name]["kernel"])
               for name in ("query", "key", "value"))
    assert k.shape[1] == kv_heads
    out = attention_core(rotate(q, tables), rotate(k, tables), v, window)
    gate = jax.nn.sigmoid(x @ p["gate"]["kernel"])          # (rows, seq, H)
    out = out.transpose(0, 2, 1, 3) * gate[..., None]
    return out.reshape(rows, seq, -1) @ p["out"]["kernel"]


def route(p, x, *, top_k, route_scale):
    """``(weights, chosen, probs)``: ``chosen`` (rows, seq, top_k) are the
    experts of each position, the ``top_k`` of the softmax; ``weights``
    (rows, seq, E) ``route_scale`` times the probability over the chosen
    ones' sum where the expert was chosen and 0 elsewhere."""
    probs = jax.nn.softmax(x @ p["gate"]["kernel"], axis=-1)
    _, chosen = jax.lax.top_k(probs, top_k)
    mask = (chosen[..., None] == jnp.arange(probs.shape[-1])).any(axis=-2)
    picked = jnp.where(mask, probs, 0.0)
    weights = route_scale * picked / picked.sum(axis=-1, keepdims=True)
    return weights, chosen, probs


def experts_layer(p, x, *, top_k, route_scale, held):
    """``(y, balance term, counts (E,), rms of the held experts' part of
    y)`` of one expert layer that holds the experts ``held = (first,
    count)``; the matrices are stacked over the held experts."""
    weights, chosen, probs = route(p, x, top_k=top_k, route_scale=route_scale)
    rows, seq, experts = probs.shape
    first, count = held

    # One held expert at a time over every position, weighted by 0 where the
    # expert was not chosen; its hidden activations are made again in the
    # backward pass.
    def one_expert(y, expert):
        glu, up, down, weight = expert
        hidden = jax.nn.silu(x @ glu) * (x @ up)
        return y + weight[..., None] * (hidden @ down), None

    stacked = (p["glu"]["kernel"], p["up"]["kernel"], p["down"]["kernel"],
               jnp.moveaxis(weights[..., first:first + count], -1, 0))
    routed, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                             stacked)
    y = swiglu(p["shared"], x) + routed

    assigned = (chosen[..., None] == jnp.arange(experts)).sum(axis=(1, 2))
    share = assigned / (seq * top_k)                        # (rows, E)
    balance = jnp.mean(experts * jnp.sum(share * probs.mean(axis=1), axis=-1))
    return (y, balance, assigned.sum(axis=0),
            jnp.sqrt(jnp.mean(jnp.square(routed))))


def block(p, x, *, kind, tables, kv_heads, head_dim, window, eps, top_k,
          route_scale, held):
    """``(out, balance term, rms of the held experts' part)`` of one block of
    layer type ``kind``, the last two None where its feed-forward is dense;
    the parameters say which it is."""
    def attn_half(p, x):
        return x + attention(
            p["attn"], rmsnorm(p["ln1"]["scale"], x, eps), kv_heads=kv_heads,
            head_dim=head_dim, tables=tables,
            window=window if kind == SLIDING else None)

    x = jax.checkpoint(attn_half)(p, x)
    u = rmsnorm(p["ln2"]["scale"], x, eps)
    if "mlp" in p:
        return x + swiglu(p["mlp"], u), None, None
    y, balance, _, routed_rms = experts_layer(
        p["moe"], u, top_k=top_k, route_scale=route_scale, held=held)
    return x + y, balance, routed_rms


def head_xent(params, hidden, labels):
    logits = hidden @ params["lm_head"]["kernel"]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def forward(params, tokens, *, layer_types, rope, head_dim, eps, **layer):
    """``(xent, mean balance term, mean over the expert layers of the rms
    of what the held experts add)`` of ``tokens`` (rows, seq + 1): inputs
    are all but the last of a row.  ``rope`` is ``{kind: {"lanes", "theta",
    "yarn"}}``."""
    ids, nxt = tokens[:, :-1], tokens[:, 1:]
    tables = {kind: rotary_tables(ids.shape[1], r["lanes"] or head_dim,
                                  r["theta"], r.get("yarn"))
              for kind, r in rope.items()}
    x = params["embed"]["embedding"][ids]
    terms, routed = [], []
    for i, kind in enumerate(layer_types):
        x, balance, routed_rms = block(
            params[f"layer{i}"], x, kind=kind, tables=tables[kind],
            head_dim=head_dim, eps=eps, **layer)
        if balance is not None:
            terms.append(balance)
            routed.append(routed_rms)
    xent = head_xent(params, rmsnorm(params["ln_f"]["scale"], x, eps), nxt)
    return xent, sum(terms) / len(terms), sum(routed) / len(routed)


def loss_and_held_output_rms(params, tokens, *, balance_coef, **model):
    """The loss, and beside it the mean over the expert layers of the rms of
    what the held experts add to their layer's output (the program's
    ``aux["moe.held_output_rms"]``)."""
    xent, balance, routed = forward(params, tokens, **model)
    return xent + balance_coef * balance, routed


def loss(params, tokens, **model):
    return loss_and_held_output_rms(params, tokens, **model)[0]
