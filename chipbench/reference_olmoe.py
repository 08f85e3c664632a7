"""The plain reference of the OLMoE block: ``jax.numpy``, float32, dense
causal attention, every expert applied by mask.

It imports nothing from ``autodist_tpu``, uses no kernel and sorts nothing.
It reads the parameter tree by the names the program gives it
(``embed/embedding``, ``layer<i>/{ln1,ln2}/scale``,
``layer<i>/attn/{query,key,value,out}/kernel``,
``layer<i>/attn/{q_norm,k_norm}/scale``,
``layer<i>/moe/{gate,glu,up,down}/kernel``, ``ln_f/scale``,
``lm_head/kernel``) because the two sides of the check start from the same
values.  The layer, as ``modeling_olmoe.py`` of the published model and
arXiv:2409.02060 have it (pre-norm, no bias anywhere):

* ``h = RMSNorm(x)``; ``q = RMSNorm(W_q h)``, ``k = RMSNorm(W_k h)`` over
  the whole projected vector, before the split into heads; ``v = W_v h``;
  rotary positions in the rotate-half form on q and k; causal
  ``softmax(q k^T / sqrt(head width)) v``; ``x = x + W_o(.)``.
* ``h = RMSNorm(x)``; ``p = softmax(W_r h)``; ``S`` = the ``top_k`` largest
  of ``p``; ``y = sum over e in S of p_e * W_down^e(SiLU(W_gate^e h) *
  W_up^e h)``, the weights not renormalised unless ``norm_topk``;
  ``x = x + y``.  No capacity.
* a final RMSNorm and the head's own matrix.
* loss = next-token cross-entropy + ``aux_coef`` * load balance +
  ``z_coef`` * router z-loss, each of the two a mean over the layers.  Load
  balance is ``E * sum_e f_e P_e`` with ``f_e`` the share of a row's
  ``seq * top_k`` assignments that went to expert e (it sums to one over
  the experts) and ``P_e`` the row's mean router probability, computed a
  row at a time and averaged over the rows; the z-loss is the mean over
  positions of ``logsumexp(router logits)^2``.
"""
import math

import jax
import jax.numpy as jnp


def rmsnorm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary_tables(seq, head_width, theta):
    """``(cos, sin)`` of (seq, head_width): angle ``t * theta^(-2i / w)``
    for i < w / 2, the same again for the upper half."""
    inv_freq = theta ** (-jnp.arange(0, head_width, 2, dtype=jnp.float32)
                         / head_width)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """Rotate-half: element i of a head pairs with element i + w / 2."""
    half = x.shape[-1] // 2
    swapped = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + swapped * sin


def attention(p, x, *, heads, eps, theta):
    rows, seq, _ = x.shape

    def split(t):
        return t.reshape(rows, seq, heads, -1).transpose(0, 2, 1, 3)

    q = split(rmsnorm(p["q_norm"]["scale"], x @ p["query"]["kernel"], eps))
    k = split(rmsnorm(p["k_norm"]["scale"], x @ p["key"]["kernel"], eps))
    v = split(x @ p["value"]["kernel"])
    head_width = q.shape[-1]
    cos, sin = rotary_tables(seq, head_width, theta)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head_width)
    keep = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(keep, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    return out.transpose(0, 2, 1, 3).reshape(rows, seq, -1) @ p["out"]["kernel"]


def route(p, x, *, top_k, norm_topk):
    """``(weights, chosen, logits, probabilities)``: ``chosen`` (rows, seq,
    top_k) are the experts of each position, ``weights`` (rows, seq, E) the
    router's probability where the expert was chosen and 0 elsewhere."""
    logits = x @ p["gate"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(probs, top_k)
    experts = probs.shape[-1]
    mask = (chosen[..., None] == jnp.arange(experts)).any(axis=-2)
    weights = jnp.where(mask, probs, 0.0)
    if norm_topk:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights, chosen, logits, probs


def experts_layer(p, x, *, top_k, norm_topk):
    """``(y, load balance, z-loss)`` of one expert layer."""
    weights, chosen, logits, probs = route(p, x, top_k=top_k,
                                           norm_topk=norm_topk)
    rows, seq, experts = probs.shape

    # One expert at a time over every position, weighted by 0 where the
    # expert was not chosen: no (positions, experts, width) array exists,
    # and an expert's hidden activations are made again in the backward
    # pass and not kept for all the experts at once.
    def one_expert(y, expert):
        glu, up, down, weight = expert
        hidden = jax.nn.silu(x @ glu) * (x @ up)
        return y + weight[..., None] * (hidden @ down), None

    stacked = (p["glu"]["kernel"], p["up"]["kernel"], p["down"]["kernel"],
               jnp.moveaxis(weights, -1, 0))
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                        stacked)

    assigned = (chosen[..., None] == jnp.arange(experts)).sum(axis=(1, 2))
    share = assigned / (seq * top_k)                        # (rows, E)
    balance = jnp.mean(experts * jnp.sum(share * probs.mean(axis=1), axis=-1))
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return y, balance, z


def loss(params, tokens, *, layers, heads, top_k, norm_topk, eps, theta,
         aux_coef, z_coef):
    """The training loss of ``tokens`` (rows, seq + 1): inputs are all but
    the last of a row, targets all but the first."""
    ids, labels = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"]["embedding"][ids]
    balance = z = 0.0
    for i in range(layers):
        p = params[f"layer{i}"]

        # Each half of a block is made again in the backward pass, or the
        # dense f32 scores of a 4,096-token row would be kept beside the
        # experts' work.  That changes no number.
        def attn_half(p, x):
            return x + attention(p["attn"], rmsnorm(p["ln1"]["scale"], x,
                                                    eps),
                                 heads=heads, eps=eps, theta=theta)

        x = jax.checkpoint(attn_half)(p, x)
        y, b, zl = experts_layer(p["moe"], rmsnorm(p["ln2"]["scale"], x, eps),
                                 top_k=top_k, norm_topk=norm_topk)
        x, balance, z = x + y, balance + b / layers, z + zl / layers
    hidden = rmsnorm(params["ln_f"]["scale"], x, eps)
    logits = hidden @ params["lm_head"]["kernel"]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    xent = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return xent + aux_coef * balance + z_coef * z
